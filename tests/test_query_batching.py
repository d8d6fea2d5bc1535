"""Server-side query micro-batching (core/batching.py, DESIGN.md §2).

Semantics-preservation contract: for every batch size, each client's
response stream is IDENTICAL (bitwise, per execution mode) to the
sequential one-round-trip-per-frame path — batching may only change how
many dispatches the server pays, never what any client sees.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Broker, StreamBuffer, TensorSpec, parse_launch)
from repro.core.batching import BatchingPolicy, QueryBatcher
from repro.core.elements import register_model
from repro.core.plan import PendingQuery
from repro.edge.edge import EdgeQueryClient
from repro.runtime import Device, Runtime


@pytest.fixture(scope="module", autouse=True)
def models():
    def init(rng):
        return {"w": jax.random.normal(rng, (12, 4)) * 0.3}

    def apply(p, x):
        return x.astype(jnp.float32).reshape(1, -1) @ p["w"]

    register_model("qbsvc", init, apply,
                   out_specs=(TensorSpec((1, 4), "float32"),))

    def apply_stateful(p, x):
        return jnp.cumsum(x.astype(jnp.float32).reshape(-1))[:4].reshape(1, 4)

    register_model("qbsvc2", lambda rng: {}, apply_stateful,
                   out_specs=(TensorSpec((1, 4), "float32"),))


def _server(rt, name="hub", operation="op", model="qbsvc"):
    dev = Device(name)
    ps = parse_launch(
        f"tensor_query_serversrc operation={operation} name=ssrc ! "
        f"tensor_filter model={model} ! tensor_query_serversink name=ssink")
    ps.elements["ssink"].pair_with(ps.elements["ssrc"])
    run = dev.add_pipeline(ps, jit=False)
    rt.add_device(dev)
    return run, ps.elements["ssrc"]


def _clients(rt, n, operation="op", codec="none", width=2):
    runs = []
    for i in range(n):
        dev = Device(f"tv{i}")
        pc = parse_launch(
            f"testsrc width={width} height=2 ! tensor_converter ! "
            f"tensor_query_client operation={operation} codec={codec} "
            f"name=qc ! appsink name=res")
        runs.append(dev.add_pipeline(pc, jit=False))
        rt.add_device(dev)
    return runs


def _responses(run):
    return [np.asarray(b.tensor) for b in run.sink_log["res"]]


class TestSemanticsPreserving:
    @pytest.mark.parametrize("batch", [1, 4, 8])
    def test_batched_matches_sequential_bitwise(self, batch):
        """Acceptance: responses at batch {1,4,8} == sequential responses.

        The sequential reference (query_batch=0) serves interpreted; the
        batched path serves through the jitted hoisted scan.  On this
        element set the two execution modes agree bitwise; the per-mode
        guarantee is pinned separately below."""
        ticks, n_clients = 3, 8
        rt_seq = Runtime(query_batch=0)
        _server(rt_seq)
        seq_runs = _clients(rt_seq, n_clients)
        rt_seq.run(ticks)

        rt_b = Runtime(query_batch=batch)
        srv_run, _ = _server(rt_b)
        b_runs = _clients(rt_b, n_clients)
        rt_b.run(ticks)

        for sr, br in zip(seq_runs, b_runs):
            assert sr.frames == ticks and br.frames == ticks
            for a, b in zip(_responses(sr), _responses(br)):
                np.testing.assert_array_equal(a, b)
        # server served every request exactly once
        assert srv_run.frames == ticks * n_clients

    def test_batch_sizes_agree_bitwise_with_each_other(self):
        """Same execution mode (compiled hoisted scan) across batch sizes:
        scan-of-1 vs scan-of-4 vs scan-of-8 must agree bitwise — batch
        composition must never leak into any client's numerics."""
        streams = {}
        for batch in (1, 4, 8):
            rt = Runtime(query_batch=batch)
            _server(rt)
            runs = _clients(rt, 8)
            rt.run(2)
            streams[batch] = [_responses(r) for r in runs]
        for batch in (4, 8):
            for ref, got in zip(streams[1], streams[batch]):
                for a, b in zip(ref, got):
                    np.testing.assert_array_equal(a, b)

    def test_server_state_threads_in_arrival_order(self):
        """Stateless here, but arrival order still defines the scan order;
        client ids must route answers regardless of batch position."""
        rt = Runtime(query_batch=8)
        _server(rt)
        runs = _clients(rt, 5)
        rt.run(2)
        ids = [r.pipe.elements["qc"].client_id for r in runs]
        assert len(set(ids)) == 5
        for r in runs:
            assert len(r.sink_log["res"]) == 2


class TestBatchingMechanics:
    def test_one_dispatch_per_tick_at_batch_8(self):
        rt = Runtime(query_batch=8)
        srv_run, _ = _server(rt)
        _clients(rt, 8)
        rt.run(3)
        qb = rt.stats()["query_batching"]
        assert qb["batched_frames"] == 24
        assert qb["sequential_frames"] == 0
        assert qb["flushes"] == 3              # exactly one flush per tick
        assert srv_run.bursts == 3             # one scan dispatch per flush
        assert srv_run.burst_frames == 24

    def test_batch_1_serves_through_compiled_path(self):
        """Regression: ``max_batch == 1`` used to be shunted onto the
        sequential interpreted fallback (`max_batch > 1` in flush),
        contradicting the module contract that a group of one still serves
        through the compiled hoisted path — turning the batch knob down to 1
        silently changed execution mode.  Batch 1 must batch."""
        rt = Runtime(query_batch=1)
        srv_run, _ = _server(rt)
        _clients(rt, 3)
        rt.run(2)
        qb = rt.stats()["query_batching"]
        assert qb["batched_frames"] == 6
        assert qb["sequential_frames"] == 0
        assert srv_run.frames == 6

    def test_batch_1_matches_larger_batches_bitwise(self):
        """...and the compiled group-of-one agrees bitwise with the compiled
        scan-of-8, so the knob never leaks into numerics."""
        streams = {}
        for batch in (1, 8):
            rt = Runtime(query_batch=batch)
            _server(rt)
            runs = _clients(rt, 4)
            rt.run(2)
            streams[batch] = [_responses(r) for r in runs]
        for ref, got in zip(streams[1], streams[8]):
            for a, b in zip(ref, got):
                np.testing.assert_array_equal(a, b)

    def test_max_batch_chunks_oversized_ticks(self):
        rt = Runtime(query_batch=4)
        srv_run, _ = _server(rt)
        _clients(rt, 8)
        rt.run(1)
        assert srv_run.bursts == 2             # 8 requests → two scan-4s
        assert rt.stats()["query_batching"]["batched_frames"] == 8

    def test_flush_on_full_serves_before_tick_deadline(self):
        rt = Runtime(query_batch=2)
        srv_run, ssrc = _server(rt)
        _clients(rt, 4)
        rt.run(1)
        # 4 clients, batch cap 2: the batcher flushed mid-gather at least
        # once (full()), leaving nothing for the deadline flush to do twice
        assert srv_run.frames == 4
        assert len(ssrc.endpoint.requests) == 0

    def test_mixed_client_caps_fall_back_to_grouped_serving(self):
        """Clients with different tensor shapes cannot share one stacked
        scan: consecutive same-structure groups serve separately, answers
        stay correct per client."""
        rt = Runtime(query_batch=8)
        srv_run, _ = _server(rt, model="qbsvc2")
        wide = _clients(rt, 2, width=3)
        narrow = _clients(rt, 2, width=2)
        rt.run(2)
        for r in wide + narrow:
            assert r.frames == 2
            assert r.last_outputs["res"].tensor.shape == (1, 4)
        assert srv_run.frames == 8

    def test_mixed_codecs_group_by_codec(self):
        """PR-5 contract: the fused wire path decodes/encodes INSIDE the
        serving jit with the codec as a static trace parameter, so mixed-
        codec ticks split into consecutive same-codec groups — exactly how
        mixed-structure ticks have always split — and every client still
        matches its own sequential stream bitwise."""
        def build(batch, **kw):
            rt = Runtime(query_batch=batch, **kw)
            _server(rt)
            runs = _clients(rt, 2, codec="none") + \
                _clients(rt, 2, codec="quant8")
            rt.run(2)
            return rt, runs

        rt_b, batched = build(8)
        qb = rt_b.stats()["query_batching"]
        assert qb["batches"] == 4          # one per codec group per tick
        # quant8 groups fuse; "none" groups have nothing to fuse and keep
        # the lazy eager path (no per-flush answer fetch)
        assert qb["fused_frames"] == 4
        _, seq = build(0)
        for br, sr in zip(batched, seq):
            for a, b in zip(_responses(br), _responses(sr)):
                np.testing.assert_array_equal(a, b)

    def test_interleaved_codecs_serve_in_arrival_order_groups(self):
        """Clients whose codecs alternate (none, quant8, none, quant8) split
        into consecutive per-codec groups — arrival order is never
        regrouped — so every quant8 request serves codec-fused, the "none"
        ones serve dense, and every answer is bitwise the sequential
        path's."""
        def build(batch):
            rt = Runtime(query_batch=batch)
            _server(rt)
            runs = []
            for codec in ("none", "quant8", "none", "quant8"):
                runs += _clients(rt, 1, codec=codec)
            rt.run(2)
            return rt, runs

        rt, runs = build(8)
        qb = rt.stats()["query_batching"]
        assert qb["batched_frames"] == 8
        assert qb["fused_frames"] == qb["fused_batches"] == 4
        _, seq = build(0)
        for br, sr in zip(runs, seq):
            assert len(_responses(br)) == len(_responses(sr)) == 2
            for a, b in zip(_responses(br), _responses(sr)):
                np.testing.assert_array_equal(a, b)

    def test_non_batchable_server_plan_serves_sequentially(self):
        """Server plans the hoisted scan cannot express (extra impure
        elements, multiple serversrcs) must serve every request through the
        legacy interpreted step; forcing the flag exercises that fallback
        without building an exotic topology."""
        rt = Runtime(query_batch=8)
        srv_run, ssrc = _server(rt)
        srv_run.pipe.plan.query_batchable = False  # force the fallback
        _clients(rt, 4)
        rt.run(2)
        qb = rt.stats()["query_batching"]
        assert qb["sequential_frames"] == 8 and qb["batched_frames"] == 0
        assert srv_run.frames == 8

    def test_gather_never_overflows_request_channel(self):
        """Backpressure regression: with more concurrent clients than the
        request Channel's capacity (64) and a batch cap that would gather
        past it, the batcher must flush at the capacity floor instead of
        leaky-dropping requests (which killed the whole tick with
        BrokerError 'no answer')."""
        rt = Runtime(query_batch=BatchingPolicy(max_batch=100,
                                                flush_on_full=False))
        srv_run, ssrc = _server(rt)
        runs = _clients(rt, 70)
        rt.run(1)
        assert srv_run.frames == 70
        for r in runs:
            assert r.frames == 1
        assert ssrc.endpoint.requests.drops == 0

    def test_edge_client_contract_unchanged(self):
        """EdgeQueryClient.infer must still get its answer before returning
        (the endpoint's inline_runner is now the batcher's flush)."""
        rt = Runtime(query_batch=8)
        _server(rt)
        ec = EdgeQueryClient(rt.broker, "op")
        out = ec.infer([np.arange(12, dtype=np.uint8).reshape(2, 2, 3)])
        assert out[0].shape == (1, 4)

    def test_failover_mid_stream_keeps_batching(self):
        rt = Runtime(query_batch=8)
        run1, ssrc1 = _server(rt, name="hub1")
        run2, ssrc2 = _server(rt, name="hub2")
        runs = _clients(rt, 4)
        rt.run(1)
        assert run1.frames == 4 and run2.frames == 0
        ssrc1.endpoint.alive = False
        rt.broker.mark_down(ssrc1.registration)
        rt.run(2)
        assert run2.frames == 8  # all four clients re-bound and batched
        for r in runs:
            assert r.frames == 3

    def test_trace_cached_per_batch_size(self):
        """Batch sizes are jit trace dimensions within one fingerprint —
        ticking twice at one size must not add executables."""
        rt = Runtime(query_batch=8)
        srv_run, _ = _server(rt)
        _clients(rt, 8)
        rt.run(1)
        fns = srv_run.pipe.plan._cache()["fns"]
        n_after_first = len(fns)
        rt.run(3)
        assert len(fns) == n_after_first


class TestPlanFlags:
    def test_server_plan_is_query_batchable(self):
        ps = parse_launch(
            "tensor_query_serversrc operation=x name=ssrc ! "
            "tensor_filter model=qbsvc ! tensor_query_serversink name=ssink")
        ps.elements["ssink"].pair_with(ps.elements["ssrc"])
        ps.realize()
        assert ps.plan.query_batchable
        assert not ps.plan.burstable  # runtime bursts still refuse servers

    def test_client_plan_has_query_clients(self):
        pc = parse_launch(
            "testsrc ! tensor_converter ! tensor_query_client operation=x "
            "name=qc ! appsink name=o").realize()
        assert pc.plan.has_query_clients
        assert not pc.plan.query_batchable

    def test_deferred_run_pauses_and_resumes(self):
        pc = parse_launch(
            "testsrc width=2 height=2 ! tensor_converter ! "
            "tensor_query_client operation=x name=qc ! appsink name=o"
        ).realize()
        params, s0 = pc.init(jax.random.PRNGKey(0)), pc.init_state()
        pq = pc.plan.run_deferred(params, s0)
        assert isinstance(pq, PendingQuery)
        assert pq.client is pc.elements["qc"]
        assert pq.request.tensor.shape == (2, 2, 3)
        answer = pq.request.with_(tensors=(jnp.ones((1, 4)),))
        res = pq.resume(answer)
        assert not isinstance(res, PendingQuery)
        outputs, state = res
        np.testing.assert_array_equal(np.asarray(outputs["o"].tensor),
                                      np.ones((1, 4)))
        src_name = next(n for n, e in pc.elements.items()
                        if e.factory_name == "testsrc")
        assert int(state[src_name]["frame"]) == 1  # upstream stepped once

    def test_policy_coercion(self):
        assert BatchingPolicy.of(8).max_batch == 8
        assert not BatchingPolicy.of(0).enabled
        p = BatchingPolicy(max_batch=4, flush_on_full=False)
        assert BatchingPolicy.of(p) is p
