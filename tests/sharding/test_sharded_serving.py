"""Mesh-sharded compiled serving (DESIGN.md §4).

Contract pinned here:
laying a serving batch out along a mesh's data axes may only change WHERE
frames execute, never what any client sees —

* responses under ``Runtime(mesh=...)`` are bitwise identical to
  single-device serving at batch {1, 4, 8};
* stateful server plans keep the FIFO single-device scan (state threads in
  arrival order — sharding such a plan would change frame ``i``'s inputs);
* the chaos acceptance scenario survives unchanged: a serving device dying
  mid-batch under the sharded path loses zero requests, answers bitwise;
* the executable cache is mesh-aware: same mesh never retraces (failover
  reconnects stay trace-free), different meshes never share executables.

The conftest in this directory forges 8 host devices before jax
initializes, so tier-1 exercises the real 8-way data axis on CPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TensorSpec, parse_launch
from repro.core.element import Element, register_element
from repro.core.elements import register_model
from repro.launch.mesh import data_axis_size, make_host_mesh, mesh_fingerprint
from repro.runtime import Device, Runtime

pytestmark = pytest.mark.multidevice


@pytest.fixture(scope="module", autouse=True)
def models():
    def init(rng):
        return {"w": jax.random.normal(rng, (12, 4)) * 0.3}

    def apply(p, x):
        return x.astype(jnp.float32).reshape(1, -1) @ p["w"]

    register_model("shsvc", init, apply,
                   out_specs=(TensorSpec((1, 4), "float32"),))


@register_element("running_sum4")
class RunningSum4(Element):
    """Stateful test element: accumulates the first 4 features across every
    frame it ever sees — serving order is observable in every answer, so a
    batch layout that broke FIFO threading could not pass bitwise."""

    def init_state(self):
        return {"acc": jnp.zeros((1, 4), jnp.float32)}

    def negotiate(self, in_caps):
        from repro.core.formats import Caps
        return [Caps(media="other/tensors",
                     tensors=(TensorSpec((1, 4), "float32"),))]

    def apply(self, params, inputs, ctx=None):
        buf = inputs[0]
        x = buf.tensors[0].astype(jnp.float32).reshape(1, -1)[:, :4]
        acc = ctx.get_state(self.name)["acc"] + x
        ctx.set_state(self.name, {"acc": acc})
        return [buf.with_(tensors=(acc,))]


def _server(rt, name="hub", operation="op", model="shsvc", filt=None, **specs):
    dev = Device(name)
    extra = " ".join(f"{k}={v}" for k, v in specs.items())
    mid = filt or f"tensor_filter model={model}"
    ps = parse_launch(
        f"tensor_query_serversrc operation={operation} name=ssrc {extra} ! "
        f"{mid} ! tensor_query_serversink name=ssink")
    ps.elements["ssink"].pair_with(ps.elements["ssrc"])
    run = dev.add_pipeline(ps, jit=False)
    rt.add_device(dev)
    return dev, run, ps.elements["ssrc"]


def _clients(rt, n, operation="op", codec="none"):
    runs = []
    for i in range(n):
        dev = Device(f"tv{i}")
        pc = parse_launch(
            f"testsrc width=2 height=2 ! tensor_converter ! "
            f"tensor_query_client operation={operation} codec={codec} "
            f"name=qc ! appsink name=res")
        runs.append(dev.add_pipeline(pc, jit=False))
        rt.add_device(dev)
    return runs


def _responses(run):
    return [np.asarray(b.tensor) for b in run.sink_log["res"]]


class TestBitwiseParity:
    @pytest.mark.parametrize("batch", [1, 4, 8])
    def test_sharded_matches_single_device_bitwise(self, batch):
        """Acceptance: mesh-sharded responses at batch {1,4,8} == the
        single-device runtime's responses, bitwise, for every client.  Only
        the 8-tiling batches actually shard (the rest fall back inside the
        same jitted call) — either way the numbers must not move."""
        ticks, n_clients = 3, 8
        rt_ref = Runtime(query_batch=batch)
        _server(rt_ref)
        ref_runs = _clients(rt_ref, n_clients)
        rt_ref.run(ticks)

        rt_m = Runtime(query_batch=batch, mesh=make_host_mesh(),
                       shard_mode="always")
        _, srv_run, _ = _server(rt_m)
        m_runs = _clients(rt_m, n_clients)
        rt_m.run(ticks)

        for rr, mr in zip(ref_runs, m_runs):
            assert rr.frames == ticks and mr.frames == ticks
            for a, b in zip(_responses(rr), _responses(mr)):
                np.testing.assert_array_equal(a, b)
        assert srv_run.frames == ticks * n_clients

    def test_uniform_codec_groups_still_shard_bitwise(self):
        """PR-5 composition rule: codec fusion is single-device, so a codec
        group the mesh may take serves dense (host decode → placement →
        sharded serve → host encode).  A full batch of
        same-codec clients therefore still shards — and stays bitwise with
        the meshless runtime."""
        def build(mesh):
            rt = Runtime(query_batch=8, mesh=mesh, shard_mode="always")
            _server(rt)
            runs = _clients(rt, 8, codec="quant8")
            rt.run(2)
            return rt, runs

        rt_m, m_runs = build(make_host_mesh())
        _, ref_runs = build(None)
        assert rt_m.stats()["query_batching"]["sharded_frames"] == 16
        for mr, rr in zip(m_runs, ref_runs):
            for a, b in zip(_responses(mr), _responses(rr)):
                np.testing.assert_array_equal(a, b)

    def test_mixed_codecs_on_a_mesh_split_by_codec_and_stay_bitwise(self):
        """PR-5 contract change: mixed-codec ticks split into consecutive
        same-codec groups (the codec is the fused executable's static trace
        parameter).  On this 8-way mesh the groups of 4 no longer tile the
        data axes, so they serve codec-fused on a single device — and the
        numbers still must not move vs the meshless runtime."""
        def build(mesh):
            rt = Runtime(query_batch=8, mesh=mesh, shard_mode="always")
            _server(rt)
            runs = _clients(rt, 4, codec="none") + \
                _clients(rt, 4, codec="quant8")
            rt.run(2)
            return rt, runs

        rt_m, m_runs = build(make_host_mesh())
        _, ref_runs = build(None)
        qb = rt_m.stats()["query_batching"]
        assert qb["sharded_frames"] == 0 and qb["fused_frames"] == 8
        for mr, rr in zip(m_runs, ref_runs):
            for a, b in zip(_responses(mr), _responses(rr)):
                np.testing.assert_array_equal(a, b)

    def test_mixed_codec_groups_that_tile_the_mesh_shard(self):
        """Mixed codecs split per codec, and a per-codec group that tiles
        the 8-way mesh still shards under ``shard_mode="always"``: 8 "none"
        then 8 quant8 clients make two groups of 8 per tick, both served
        sharded (a group the mesh may take serves dense, so none fuses),
        answers bitwise vs the meshless runtime, where quant8 fuses."""
        def build(mesh):
            rt = Runtime(query_batch=16, mesh=mesh, shard_mode="always")
            _server(rt)
            runs = _clients(rt, 8, codec="none") + \
                _clients(rt, 8, codec="quant8")
            rt.run(2)
            return rt, runs

        rt_m, m_runs = build(make_host_mesh())
        rt_r, ref_runs = build(None)
        qb = rt_m.stats()["query_batching"]
        assert qb["sharded_frames"] == 32 and qb["fused_frames"] == 0
        assert rt_r.stats()["query_batching"]["fused_frames"] == 16
        for mr, rr in zip(m_runs, ref_runs):
            assert len(_responses(mr)) == 2
            for a, b in zip(_responses(mr), _responses(rr)):
                np.testing.assert_array_equal(a, b)


class TestShardingMechanics:
    def test_sharded_path_used_at_batch_8(self):
        mesh = make_host_mesh()
        assert data_axis_size(mesh) >= 2
        rt = Runtime(query_batch=8, mesh=mesh, shard_mode="always")
        _, srv_run, _ = _server(rt)
        _clients(rt, 8)
        rt.run(3)
        qb = rt.stats()["query_batching"]
        assert qb["batched_frames"] == 24
        assert qb["sequential_frames"] == 0
        # every full batch tiled the data axis: all three flushes sharded
        assert qb["sharded_batches"] == 3
        assert qb["sharded_frames"] == 24
        assert srv_run.frames == 24

    def test_non_tiling_batch_falls_back_single_device(self):
        """5 requests cannot tile an 8-way data axis: the group serves on
        the single-device scan inside the same compiled call — served fully,
        just not sharded."""
        rt = Runtime(query_batch=8, mesh=make_host_mesh(),
                     shard_mode="always")
        _, srv_run, _ = _server(rt)
        runs = _clients(rt, 5)
        rt.run(2)
        qb = rt.stats()["query_batching"]
        assert qb["batched_frames"] == 10
        assert qb["sharded_frames"] == 0
        assert srv_run.frames == 10
        assert all(r.frames == 2 for r in runs)

    def test_stateful_server_keeps_fifo_scan(self):
        """A server plan threading cross-frame state must never shard — the
        running sum makes arrival order observable in every answer, so this
        doubles as a FIFO-threading bitwise check under the mesh runtime."""
        def build(mesh):
            rt = Runtime(query_batch=8, mesh=mesh, shard_mode="always")
            _, srv_run, ssrc = _server(rt, filt="running_sum4 name=acc")
            runs = _clients(rt, 8)
            rt.run(3)
            return rt, srv_run, runs

        rt_m, srv_m, m_runs = build(make_host_mesh())
        _, _, ref_runs = build(None)
        qb = rt_m.stats()["query_batching"]
        assert qb["sharded_frames"] == 0          # stateful: refused
        assert qb["batched_frames"] == 24         # ... but still batched
        for mr, rr in zip(m_runs, ref_runs):
            for a, b in zip(_responses(mr), _responses(rr)):
                np.testing.assert_array_equal(a, b)
        # the accumulator really threaded: answers grow tick over tick
        last = _responses(m_runs[-1])
        assert np.all(np.abs(last[-1]) >= np.abs(last[0]))

    def test_runtime_mesh_auto_builds_host_mesh(self):
        rt = Runtime(query_batch=8, mesh="auto")
        assert rt.mesh is not None
        assert data_axis_size(rt.mesh) == len(jax.devices())


class TestPlacementPolicy:
    """shard_mode: placement is a cost decision (core/batching.py) — auto
    probes both executables per batch size and keeps the faster; either
    pick is bitwise-correct, so policy may only move latency, never data."""

    def test_auto_mode_calibrates_once_and_stays_correct(self):
        rt = Runtime(query_batch=8, mesh=make_host_mesh())  # default auto
        _, srv_run, ssrc = _server(rt)
        runs = _clients(rt, 8)
        rt.run(3)
        batcher = rt._batchers[ssrc.endpoint.endpoint_id]
        assert batcher.placements.get(8) in ("sharded", "single")
        assert srv_run.frames == 24                # every request answered
        assert all(r.frames == 3 for r in runs)
        # the decision is sticky: stats are consistent with it
        qb = rt.stats()["query_batching"]
        if batcher.placements[8] == "sharded":
            assert qb["sharded_frames"] == 24
        else:
            assert qb["sharded_frames"] == 0
        assert qb["batched_frames"] == 24          # batched either way

    def test_auto_matches_forced_modes_bitwise(self):
        """Whatever auto picks, the answers equal both forced modes'."""
        streams = {}
        for mode in ("auto", "always", "never"):
            rt = Runtime(query_batch=8, mesh=make_host_mesh(),
                         shard_mode=mode)
            _server(rt)
            runs = _clients(rt, 8)
            rt.run(2)
            streams[mode] = [_responses(r) for r in runs]
        for mode in ("always", "never"):
            for ref, got in zip(streams["auto"], streams[mode]):
                for a, b in zip(ref, got):
                    np.testing.assert_array_equal(a, b)

    def test_auto_single_placement_reclaims_codec_fusion(self):
        """Regression (PR-5 review): a mesh runtime in auto mode used to
        route every mesh-tiling codec group down the eager wire path even
        after the probe had picked "single" — forfeiting codec fusion for
        nothing.  Only the probe-carrying flushes may serve eager; once the
        calibrated placement says "single", groups of that size must serve
        codec-FUSED."""
        rt = Runtime(query_batch=8, mesh=make_host_mesh(),
                     shard_mode="auto")
        _, srv_run, ssrc = _server(rt)
        _clients(rt, 8, codec="quant8")
        rt.run(3)
        batcher = rt._batchers[ssrc.endpoint.endpoint_id]
        qb = rt.stats()["query_batching"]
        if batcher.placements.get(8) == "single":
            # on this host-forged mesh the probe picks "single" (PR-4
            # documented outcome): ticks after the probe must be fused
            assert qb["fused_frames"] >= 16
        else:   # a real mesh where sharding wins keeps the eager path
            assert qb["sharded_frames"] > 0
        assert srv_run.frames == 24

    def test_never_mode_stays_single_device(self):
        rt = Runtime(query_batch=8, mesh=make_host_mesh(),
                     shard_mode="never")
        _, srv_run, ssrc = _server(rt)
        _clients(rt, 8)
        rt.run(2)
        assert rt.stats()["query_batching"]["sharded_frames"] == 0
        assert srv_run.frames == 16
        assert rt._batchers[ssrc.endpoint.endpoint_id].placements == {}

    def test_bad_mode_rejected(self):
        from repro.core.batching import BatchingPolicy, QueryBatcher
        with pytest.raises(ValueError, match="shard_mode"):
            QueryBatcher(None, None, BatchingPolicy(), shard_mode="bogus")
        # the Runtime validates too: a pub/sub-only deployment never builds
        # a batcher, and the burst path's string compare would otherwise
        # turn a typo into a silent "never"
        with pytest.raises(ValueError, match="shard_mode"):
            Runtime(mesh=make_host_mesh(), shard_mode="Always")

    def test_shardable_batch_predicate(self):
        mesh = make_host_mesh()
        d = data_axis_size(mesh)
        ps = parse_launch(
            "tensor_query_serversrc operation=x name=ssrc ! "
            "tensor_filter model=shsvc ! tensor_query_serversink name=ssink")
        ps.elements["ssink"].pair_with(ps.elements["ssrc"])
        ps.realize()
        plan = ps.plan
        assert plan.shardable_batch(d, {}, mesh)
        assert plan.shardable_batch(2 * d, {}, mesh)
        assert not plan.shardable_batch(d + 1, {}, mesh)
        assert not plan.shardable_batch(d, {}, None)
        assert not plan.shardable_batch(0, {}, mesh)
        # any state leaf forces the FIFO scan
        assert not plan.shardable_batch(
            d, {"acc": {"v": jnp.zeros((1,))}}, mesh)


class TestExecCacheMeshAware:
    def test_same_mesh_never_retraces_different_mesh_never_shares(self):
        mesh = make_host_mesh()
        rt = Runtime(query_batch=8, mesh=mesh, shard_mode="always")
        _, srv_run, _ = _server(rt)
        _clients(rt, 8)
        rt.run(1)
        fns = srv_run.pipe.plan._cache()["fns"]
        n_after_first = len(fns)
        # mesh-keyed entry exists and is distinct from the no-mesh key space
        # (serve_batch keys: (tag, donate, mesh fingerprint, codec))
        assert any(k[0] == "serve_batch" and k[2] == mesh_fingerprint(mesh)
                   for k in fns)
        rt.run(3)
        assert len(fns) == n_after_first      # same mesh: no new executables
        # an equivalent mesh object (same devices/layout) hits the same key
        mesh2 = make_host_mesh()
        assert mesh_fingerprint(mesh2) == mesh_fingerprint(mesh)
        srv_run.pipe.plan.compiled_serve_batch(mesh=mesh2)
        assert len(fns) == n_after_first
        # the single-device executable is a distinct entry (the mesh wrapper
        # created it eagerly as its non-tiling fallback) — requesting it
        # directly resolves to the cached one, no collision, no retrace
        assert ("serve_batch", False, None, None) in fns
        srv_run.pipe.plan.compiled_serve_batch(mesh=None)
        assert len(fns) == n_after_first
        # codec-fused executables never collide with the plain ones: the
        # codec fingerprint is part of the key
        srv_run.pipe.plan.compiled_serve_batch(codec="quant8")
        assert ("serve_batch", False, None, "quant8") in fns
        assert len(fns) == n_after_first + 1

    def test_failover_rewire_reuses_sharded_executable(self, chaos):
        """Kill + revive the serving device under the mesh runtime: the
        revived topology keeps its fingerprint AND its mesh, so nothing
        retraces across the outage."""
        mesh = make_host_mesh()
        rt = Runtime(query_batch=8, mesh=mesh, shard_mode="always")
        dev, srv_run, ssrc = _server(rt)
        cl = _clients(rt, 8)
        harness = chaos(rt)
        harness.kill_server(3, dev, ssrc)
        harness.revive_server(5, dev, ssrc)
        harness.run(2)
        fns = srv_run.pipe.plan._cache()["fns"]
        n_mid = len(fns)
        harness.run(5)
        assert len(fns) == n_mid
        assert all(r.frames >= 5 for r in cl)


class TestChaosUnderSharding:
    def test_mid_batch_server_death_sharded_loses_nothing_bitwise(self, chaos):
        """THE §3 acceptance scenario re-run on the sharded path: the
        primary dies while this tick's batch is mid-gather; orphans
        re-dispatch to the survivor (also mesh-sharded) within the tick —
        zero requests lost, answers bitwise vs the fault-free mesh twin."""
        ticks, n_clients, kill_tick = 6, 8, 3
        mesh = make_host_mesh()

        rt0 = Runtime(query_batch=8, mesh=mesh, shard_mode="always")
        _server(rt0, name="hubA")
        _server(rt0, name="hubB")
        ref_runs = _clients(rt0, n_clients)
        rt0.run(ticks)

        rt = Runtime(query_batch=8, mesh=mesh, shard_mode="always")
        devA, runA, ssrcA = _server(rt, name="hubA")
        devB, runB, ssrcB = _server(rt, name="hubB")
        cl_runs = _clients(rt, n_clients)
        harness = chaos(rt)
        harness.kill_server_mid_batch(kill_tick, devA, ssrcA, after_n=3)
        harness.run(ticks)

        assert any("mid-batch" in label and "DISARMED" not in label
                   for _, label in harness.log)
        for ref, got in zip(ref_runs, cl_runs):
            assert got.frames == ticks            # zero lost requests
            a, b = _responses(ref), _responses(got)
            assert len(a) == len(b) == ticks
            for x, y in zip(a, b):
                np.testing.assert_array_equal(x, y)
        fo = rt.stats()["failover"]
        assert fo["redispatches"] >= 1
        assert fo["parked_now"] == 0
        # the healthy ticks really exercised the mesh layout
        assert rt.stats()["query_batching"]["sharded_frames"] > 0
        assert runB.frames >= (ticks - kill_tick) * n_clients
