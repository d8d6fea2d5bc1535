"""Zero-loss live reconfiguration — prepare/commit hot swap (DESIGN.md §6).

A topology edit on a RUNNING pipeline (swap an element, re-route a link,
add/remove an endpoint or pubsub binding) is a first-class runtime
operation: ``Runtime.reconfigure`` prepares and warms the new plan off the
serving path, commits at a tick boundary with queued frames and in-flight
queries carried across, and rolls back cleanly when the prepare fails or
the target dies mid-warm.

Acceptance contract pinned here:

* the hot swap commits at a tick boundary with ZERO frames lost and every
  post-commit answer bitwise identical to a freshly-built pipeline at
  query batch 1, 4 and 8;
* a chaos kill landing during the prepare/warm window never leaves the
  reconfiguration in limbo — it terminates ``rolled_back`` (or
  ``committed``), with the old topology serving untouched;
* failover itself routes through the same machinery: a server death or
  revival shows up as an UNPLANNED reconfiguration in ``Runtime.stats``.

The swapped models use DETERMINISTIC inits (independent of the rng path)
so a swapped-in element's params are bitwise what a fresh build computes —
the bitwise comparisons compare serving, not rng bookkeeping.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TensorSpec, parse_launch
from repro.core.element import element_factory
from repro.core.elements import register_model
from repro.core.reconfig import ReconfigError
from repro.runtime import Device, Runtime

pytestmark = pytest.mark.reconfig


@pytest.fixture(scope="module", autouse=True)
def models():
    # deterministic inits: params depend on nothing but the model, so the
    # hot-swapped element and the fresh-build reference are bitwise equal
    def init_a(rng):
        return {"w": jnp.linspace(-1.0, 1.0, 48).reshape(12, 4)}

    def apply_a(p, x):
        return x.astype(jnp.float32).reshape(1, -1) @ p["w"]

    def init_b(rng):
        return {"w": jnp.linspace(1.0, -1.0, 48).reshape(12, 4),
                "b": jnp.full((4,), 0.5)}

    def apply_b(p, x):
        return x.astype(jnp.float32).reshape(1, -1) @ p["w"] + p["b"]

    register_model("rcA", init_a, apply_a,
                   out_specs=(TensorSpec((1, 4), "float32"),))
    register_model("rcB", init_b, apply_b,
                   out_specs=(TensorSpec((1, 4), "float32"),))


def _server(rt, model, name="hub"):
    dev = Device(name)
    ps = parse_launch(
        f"tensor_query_serversrc operation=svc name=ssrc ! "
        f"tensor_filter model={model} name=filt ! "
        f"tensor_query_serversink name=ssink")
    ps.elements["ssink"].pair_with(ps.elements["ssrc"])
    run = dev.add_pipeline(ps, jit=False)
    rt.add_device(dev)
    return dev, run, ps.elements["ssrc"]


def _clients(rt, n):
    runs = []
    for i in range(n):
        dev = Device(f"tv{i}")
        pc = parse_launch(
            "testsrc width=2 height=2 ! tensor_converter ! "
            "tensor_query_client operation=svc name=qc ! appsink name=res")
        runs.append(dev.add_pipeline(pc, jit=False))
        rt.add_device(dev)
    return runs


def _responses(run):
    return [np.asarray(b.tensor) for b in run.sink_log["res"]]


def _swap_filt(run, model):
    return run.pipe.reconfig().swap(
        "filt", element_factory("tensor_filter", model=model))


class TestHotSwap:
    @pytest.mark.parametrize("query_batch", [1, 4, 8])
    def test_swap_commits_at_tick_boundary_bitwise_identical(self,
                                                             query_batch):
        """THE acceptance scenario: swap the serving model under live
        traffic.  Every pre-commit answer is bitwise the old model's, every
        answer from the commit tick onward is bitwise what a pipeline BUILT
        with the new model computes — and not one frame is lost to the
        cutover, at batch 1, 4 and 8."""
        ticks_pre, ticks_post, n_clients = 4, 6, 3
        total = ticks_pre + ticks_post

        refs = {}
        for model in ("rcA", "rcB"):
            rt0 = Runtime(query_batch=query_batch)
            _server(rt0, model)
            refs[model] = _clients(rt0, n_clients)
            rt0.run(total)

        rt = Runtime(query_batch=query_batch)
        _, hub_run, _ = _server(rt, "rcA")
        cl = _clients(rt, n_clients)
        rt.run(ticks_pre)
        rc = rt.reconfigure(hub_run, _swap_filt(hub_run, "rcB"),
                            warm_ticks=1)
        assert rc.status == "warming"          # prepared+warmed off-path
        rt.run(ticks_post)

        assert rc.status == "committed"
        # tick boundary: warm window of 1 tick after the request tick, then
        # the commit lands at the TOP of the next tick — which is therefore
        # the first tick served by the new plan
        assert rc.committed_tick == ticks_pre + 2
        cut = rc.committed_tick - 1            # index of first new answer
        for ref_a, ref_b, got in zip(refs["rcA"], refs["rcB"], cl):
            assert got.frames == total         # zero lost requests
            a, b, g = _responses(ref_a), _responses(ref_b), _responses(got)
            assert len(g) == total
            for x, y in zip(a[:cut], g[:cut]):
                np.testing.assert_array_equal(x, y)   # old epoch: bitwise A
            for x, y in zip(b[cut:], g[cut:]):
                np.testing.assert_array_equal(x, y)   # new epoch: bitwise B
        assert "b" in hub_run.params["filt"]   # the swapped params landed
        st = rt.stats()["reconfig"]
        assert st["planned"] == 1 and st["reconfigs"] == 1
        assert st["rollbacks"] == 0 and st["pending"] == 0

    def test_relink_and_remove_reroute_midstream(self):
        """Re-route a link around an element and drop it, mid-stream: the
        sink's input dtype flips exactly at the commit tick and no frame is
        lost on either side of the cutover.  Also exercises the callable
        edit form (``reconfigure(run, lambda plan: ...)``)."""
        rt = Runtime()
        dev = Device("edge")
        p = parse_launch(
            "testsrc name=s width=3 height=2 ! tensor_converter name=c ! "
            "tensor_transform mode=arithmetic option=typecast:float32 "
            "name=t ! appsink name=o")
        run = dev.add_pipeline(p, jit=False)
        rt.add_device(dev)
        rt.run(4)
        rc = rt.reconfigure(run, lambda plan: plan.relink("c", "o")
                            .remove("t"), warm_ticks=1)
        rt.run(4)
        assert rc.status == "committed"
        assert "t" not in run.pipe.elements
        log = run.sink_log["o"]
        assert len(log) == 8                   # zero loss across the cutover
        # control: what the converter emits without the typecast stage
        ctrl = parse_launch("testsrc name=s2 width=3 height=2 ! "
                            "tensor_converter name=c2 ! appsink name=o2")
        cdev = Device("ctrl")
        crun = cdev.add_pipeline(ctrl, jit=False)
        crt = Runtime()
        crt.add_device(cdev)
        crt.tick()
        native = crun.sink_log["o2"][0].tensor.dtype
        assert native != jnp.float32           # the transform did something
        cut = rc.committed_tick - 1
        assert all(b.tensor.dtype == jnp.float32 for b in log[:cut])
        assert all(b.tensor.dtype == native for b in log[cut:])

    def test_remove_all_decommissions_and_clients_rebind(self):
        """Removing every element retires the run: its registrations
        unregister at commit and the clients re-bind to the surviving hub
        with zero frames lost — a planned decommission is the graceful twin
        of the chaos kill."""
        total = 8
        rt = Runtime(query_batch=8)
        _, run_a, ssrc_a = _server(rt, "rcA", name="hubA")
        _, run_b, _ = _server(rt, "rcA", name="hubB")
        cl = _clients(rt, 3)
        rt.run(3)
        rc = rt.reconfigure(run_a, run_a.pipe.reconfig()
                            .remove("ssrc").remove("filt").remove("ssink"),
                            warm_ticks=1)
        rt.run(total - 3)
        assert rc.status == "committed"
        assert run_a.retired
        assert ssrc_a.registration is None     # left the control plane
        assert all(r.frames == total for r in cl)   # zero loss
        # hubB took over from the commit tick onward
        assert run_b.frames >= 3 * (total - rc.committed_tick + 1)
        st = rt.stats()["reconfig"]
        # the commit's own unregister events are its bookkeeping, not a
        # second (unplanned) reconfiguration
        assert st["planned"] == 1 and st["unplanned"] == 0

    def test_hot_add_pubsub_binding_publishes_at_commit(self):
        """Grow the graph mid-stream: the local sink is replaced by a
        pubsub publisher.  The new mqttsink registers only AT COMMIT (a
        prepared publisher must never be discoverable before it serves),
        and a viewer joining afterwards receives the stream."""
        total_pre = 6
        rt = Runtime()
        edge = Device("edge")
        p = parse_launch("testsrc name=s width=2 height=2 ! "
                         "tensor_converter name=c ! appsink name=o")
        run = edge.add_pipeline(p, jit=False)
        rt.add_device(edge)
        rt.run(3)
        snk = element_factory("mqttsink", name="snk", pub_topic="cam/live")
        rc = rt.reconfigure(run, lambda plan: plan.remove("o").add(snk)
                            .link("c", "snk"), warm_ticks=1)
        assert snk.registration is None        # not discoverable pre-commit
        rt.run(total_pre - 3)
        assert rc.status == "committed"
        assert snk.registration is not None    # registered at commit
        assert run.frames == total_pre         # the stream never stalled
        published = snk.channel.msgs_sent
        assert published == total_pre - rc.committed_tick + 1
        # a late viewer binds to the hot-added publisher: the retained
        # history replays and every frame published since reaches it
        viewer = Device("viewer")
        vp = parse_launch("mqttsrc sub-topic=cam/live name=vsrc ! "
                          "appsink name=vo")
        vrun = viewer.add_pipeline(vp, jit=False)
        rt.add_device(viewer)
        rt.run(4)
        assert vrun.frames == published + 4    # retained + live, none lost

    def test_commit_defers_while_frame_in_flight(self, chaos):
        """Drain semantics: a run with a frame paused at its query client
        must not cut over mid-frame — the commit defers (``draining``)
        until the parked frame resolves, then lands at the next boundary."""
        rt = Runtime(query_batch=8)
        dev, _, ssrc = _server(rt, "rcA")
        (cl_run,) = _clients(rt, 1)
        harness = chaos(rt)
        harness.kill_server(3, dev, ssrc)      # the tick-3 frame parks
        harness.revive_server(7, dev, ssrc)
        harness.run(6)
        rc = rt.reconfigure(cl_run, cl_run.pipe.reconfig().swap(
            "res", element_factory("appsink")), warm_ticks=0)
        harness.run(1)                         # eligible, but in flight
        assert rc.status == "draining"
        harness.run(1)                         # drained → tick boundary
        assert rc.status == "committed"
        # ticks 1-2 served, the parked frame completed on its OLD epoch at
        # tick 7, and the first post-commit frame followed at tick 8
        assert cl_run.frames == 4
        assert rt.stats()["failover"]["parked_now"] == 0


class TestRollback:
    def test_failed_prepare_rolls_back_with_explicit_stats(self):
        """A bad edit (unknown element) fails at prepare: the request lands
        ``rolled_back`` with the error recorded, serving never blinks, and
        the rollback is an accounted stat — not a silent no-op."""
        rt = Runtime(query_batch=4)
        _, hub_run, _ = _server(rt, "rcA")
        cl = _clients(rt, 2)
        rt.run(3)
        rc = rt.reconfigure(hub_run, hub_run.pipe.reconfig().swap(
            "nope", element_factory("tensor_filter", model="rcB")))
        assert rc.status == "rolled_back"
        assert rc.reason == "prepare-failed"
        assert isinstance(rc.error, ReconfigError)
        rc2 = rt.reconfigure(hub_run,
                             hub_run.pipe.reconfig().relink("ghost", "ssink"))
        assert rc2.status == "rolled_back"
        rt.run(3)
        assert all(r.frames == 6 for r in cl)  # serving unaffected
        assert "b" not in hub_run.params["filt"]    # old params intact
        st = rt.stats()["reconfig"]
        assert st["rollbacks"] == 2
        assert st["planned"] == 0 and st["pending"] == 0

    def test_chaos_kill_mid_warm_rolls_back_never_limbo(self, chaos):
        """The target device dies inside the warm window: the pending
        reconfiguration terminates ``rolled_back`` (never limbo), the old
        params stay, and the kill itself fails the clients over to the
        survivor with zero loss."""
        total = 8
        rt = Runtime(query_batch=8)
        dev_a, run_a, _ = _server(rt, "rcA", name="hubA")
        _, run_b, _ = _server(rt, "rcA", name="hubB")
        cl = _clients(rt, 3)
        harness = chaos(rt)
        box = []
        harness.at(4, lambda: box.append(
            rt.reconfigure(run_a, _swap_filt(run_a, "rcB"), warm_ticks=3)),
            label="request swap on hubA")
        harness.kill_server(5, dev_a, run_a.pipe.elements["ssrc"])
        harness.run(total)
        rc = box[0]
        assert rc.status == "rolled_back"      # terminal, not limbo
        assert rc.reason == "target-dead"
        assert "b" not in run_a.params["filt"]  # rcB params never landed
        st = rt.stats()["reconfig"]
        assert st["pending"] == 0
        assert st["rollbacks"] == 1
        assert st["unplanned"] >= 1            # the kill, same machinery
        assert all(r.frames == total for r in cl)   # hubB served, zero loss
        assert run_b.frames >= 3 * (total - 5)


class TestFailoverIsAReconfiguration:
    def test_initial_construction_counts_no_reconfigs(self):
        rt = Runtime(query_batch=4)
        _server(rt, "rcA")
        _clients(rt, 2)
        rt.run(3)
        assert rt.stats()["reconfig"]["reconfigs"] == 0

    def test_kill_and_revival_are_unplanned_reconfigurations(self, chaos):
        """The PR-3 failover special case is gone: broker liveness events
        route through the reconfiguration manager, so a death and a revival
        each show up as one unplanned reconfiguration — with serving intact
        through both."""
        total = 8
        rt = Runtime(query_batch=8)
        dev_a, _, ssrc_a = _server(rt, "rcA", name="hubA")
        _server(rt, "rcA", name="hubB")
        cl = _clients(rt, 2)
        harness = chaos(rt)
        harness.kill_server(3, dev_a, ssrc_a)
        harness.revive_server(6, dev_a, ssrc_a)
        harness.run(total)
        st = rt.stats()["reconfig"]
        assert st["unplanned"] == 2            # down + register, one each
        assert st["planned"] == 0
        assert [(k, s) for _, k, s, _ in rt.reconfig.log] == \
            [("unplanned", "down"), ("unplanned", "register")]
        assert all(r.frames == total for r in cl)   # zero loss throughout
