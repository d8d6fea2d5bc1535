"""Fault-tolerant among-device serving (DESIGN.md §3).

The among-device requirement only matters if serving survives devices
leaving and joining — the normal state of consumer fleets.  These tests
drive the failover fabric with the deterministic chaos harness
(tests/chaoslib.py): scripted kills/revivals at chosen ticks, no
wall-clock, no flakes.

Acceptance contract pinned here:
killing a serving device mid-batch loses ZERO client requests — orphaned
queries re-dispatch to a surviving server and every answer is bitwise what
the fault-free run produces; frames with no live server park and recover
within 2 ticks of a server's (re-)registration.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Broker, BrokerError, Caps, StreamBuffer, TensorSpec, \
    parse_launch
from repro.core.elements import register_model
from repro.runtime import Device, Runtime


@pytest.fixture(scope="module", autouse=True)
def models():
    def init(rng):
        return {"w": jax.random.normal(rng, (12, 4)) * 0.3}

    def apply(p, x):
        return x.astype(jnp.float32).reshape(1, -1) @ p["w"]

    register_model("fosvc", init, apply,
                   out_specs=(TensorSpec((1, 4), "float32"),))


def _server(rt, name="hub", operation="op", **specs):
    """One serving device.  All servers init from PRNGKey(0), so any
    survivor computes bitwise-identical answers — the fault-free twin."""
    dev = Device(name)
    extra = " ".join(f"{k}={v}" for k, v in specs.items())
    ps = parse_launch(
        f"tensor_query_serversrc operation={operation} name=ssrc {extra} ! "
        f"tensor_filter model=fosvc ! tensor_query_serversink name=ssink")
    ps.elements["ssink"].pair_with(ps.elements["ssrc"])
    run = dev.add_pipeline(ps, jit=False)
    rt.add_device(dev)
    return dev, run, ps.elements["ssrc"]


def _clients(rt, n, operation="op", codec="none", prefix="tv"):
    runs = []
    for i in range(n):
        dev = Device(f"{prefix}{i}")
        pc = parse_launch(
            f"testsrc width=2 height=2 ! tensor_converter ! "
            f"tensor_query_client operation={operation} codec={codec} "
            f"name=qc ! appsink name=res")
        runs.append(dev.add_pipeline(pc, jit=False))
        rt.add_device(dev)
    return runs


def _responses(run):
    return [np.asarray(b.tensor) for b in run.sink_log["res"]]


class TestChaosAcceptance:
    def test_mid_batch_server_death_loses_nothing_bitwise(self, chaos):
        """THE acceptance scenario: the serving device dies while this
        tick's batch is mid-gather (3 of 6 requests already stranded on the
        dead endpoint).  The orphans re-dispatch to the survivor within the
        same tick: every client still gets one answer per tick, and every
        answer is bitwise identical to the fault-free run."""
        ticks, n_clients, kill_tick = 6, 6, 3

        # fault-free twin
        rt0 = Runtime(query_batch=8)
        _server(rt0, name="hubA")
        _server(rt0, name="hubB")
        ref_runs = _clients(rt0, n_clients)
        rt0.run(ticks)

        rt = Runtime(query_batch=8)
        devA, runA, ssrcA = _server(rt, name="hubA")
        devB, runB, ssrcB = _server(rt, name="hubB")
        cl_runs = _clients(rt, n_clients)
        harness = chaos(rt)
        harness.kill_server_mid_batch(kill_tick, devA, ssrcA, after_n=3)
        harness.run(ticks)

        assert any("mid-batch" in label and "DISARMED" not in label
                   for _, label in harness.log), "the scripted kill fired"
        for ref, got in zip(ref_runs, cl_runs):
            assert got.frames == ticks          # zero lost requests
            a, b = _responses(ref), _responses(got)
            assert len(a) == len(b) == ticks
            for x, y in zip(a, b):
                np.testing.assert_array_equal(x, y)  # bitwise vs fault-free
        fo = rt.stats()["failover"]
        assert fo["redispatches"] >= 1          # orphans were re-shipped
        assert fo["parked_now"] == 0
        # the survivor picked up all serving from the kill tick onward
        assert runB.frames >= (ticks - kill_tick) * n_clients

    def test_mid_batch_death_with_codec_fused_batches_in_flight(self, chaos):
        """PR-5 regression guard: the fused wire path must not weaken the
        zero-loss contract.  quant8 clients put codec-FUSED batches in
        flight (wire-form requests, decode/encode inside the serving jit);
        the serving device dies mid-gather with 3 requests stranded in wire
        form on the dead endpoint.  The orphans — still encoded — must
        re-dispatch to the survivor, serve through ITS fused executable,
        and answer bitwise what the fault-free twin produces."""
        ticks, n_clients, kill_tick = 6, 6, 3

        rt0 = Runtime(query_batch=8)
        _server(rt0, name="hubA")
        _server(rt0, name="hubB")
        ref_runs = _clients(rt0, n_clients, codec="quant8")
        rt0.run(ticks)

        rt = Runtime(query_batch=8)
        devA, runA, ssrcA = _server(rt, name="hubA")
        devB, runB, ssrcB = _server(rt, name="hubB")
        cl_runs = _clients(rt, n_clients, codec="quant8")
        harness = chaos(rt)
        harness.kill_server_mid_batch(kill_tick, devA, ssrcA, after_n=3)
        harness.run(ticks)

        assert any("mid-batch" in label and "DISARMED" not in label
                   for _, label in harness.log), "the scripted kill fired"
        for ref, got in zip(ref_runs, cl_runs):
            assert got.frames == ticks          # zero lost requests
            a, b = _responses(ref), _responses(got)
            assert len(a) == len(b) == ticks
            for x, y in zip(a, b):
                np.testing.assert_array_equal(x, y)  # bitwise vs fault-free
        fo = rt.stats()["failover"]
        assert fo["redispatches"] >= 1
        assert fo["parked_now"] == 0
        # the batches really were codec-fused on both servers' paths
        qb = rt.stats()["query_batching"]
        assert qb["fused_frames"] == ticks * n_clients
        assert runB.frames >= (ticks - kill_tick) * n_clients

    def test_mid_flush_death_orphans_the_popped_remainder(self, chaos):
        """Same-tick race pin (DESIGN.md §6 satellite): ``mark_down`` lands
        while ``QueryBatcher.flush`` is mid-serve — requests the flush
        already POPPED off the request channel are invisible to the down
        event's purge, so the dead endpoint's remaining groups must go to
        the orphan ledger, never be served by the corpse.  Mixed codecs put
        a group boundary exactly where the kill lands (grouping splits by
        codec): 3 plain answers push, the death fires, and the 3 quant8
        requests still in the batcher's hands orphan + re-dispatch.  The
        3 pushed answers die with the endpoint's purged response channels,
        so ALL six clients re-dispatch — and every answer stays bitwise
        the fault-free twin's."""
        ticks, kill_tick = 6, 3

        rt0 = Runtime(query_batch=8)
        _server(rt0, name="hubA")
        _server(rt0, name="hubB")
        ref_runs = _clients(rt0, 3) + _clients(rt0, 3, codec="quant8",
                                               prefix="q8tv")
        rt0.run(ticks)

        rt = Runtime(query_batch=8)
        devA, runA, ssrcA = _server(rt, name="hubA")
        devB, runB, ssrcB = _server(rt, name="hubB")
        cl_runs = _clients(rt, 3) + _clients(rt, 3, codec="quant8",
                                             prefix="q8tv")
        harness = chaos(rt)
        harness.kill_server_mid_flush(kill_tick, devA, ssrcA,
                                      runA.pipe.elements["ssink"],
                                      after_answers=3)
        harness.run(ticks)

        assert any("mid-flush" in label and "DISARMED" not in label
                   for _, label in harness.log), "the scripted kill fired"
        for ref, got in zip(ref_runs, cl_runs):
            assert got.frames == ticks          # zero lost requests
            a, b = _responses(ref), _responses(got)
            assert len(a) == len(b) == ticks
            for x, y in zip(a, b):
                np.testing.assert_array_equal(x, y)  # bitwise vs fault-free
        # the popped-but-unserved quant8 group hit the flush-orphan ledger
        qb = rt.stats()["query_batching"]
        assert qb["flush_orphans"] == 3
        fo = rt.stats()["failover"]
        assert fo["orphaned_requests"] >= 3
        assert fo["redispatches"] >= 6          # purged answers + orphans
        # hubA answered 2 full ticks plus the pre-kill group, nothing more
        assert runA.frames == (kill_tick - 1) * 6 + 3
        assert runB.frames >= (ticks - kill_tick) * 6

    def test_dead_fleet_parks_then_recovers_within_two_ticks(self, chaos):
        """No live server at all: frames park (no errors, nothing dropped)
        and complete within 2 ticks of the revival's register event."""
        rt = Runtime(query_batch=8)
        dev, _, ssrc = _server(rt)
        cl_runs = _clients(rt, 3)
        harness = chaos(rt)
        harness.kill_server(3, dev, ssrc, crash=True)
        harness.revive_server(6, dev, ssrc)
        harness.run(5)          # ticks 1..5: two served, then parked
        assert all(r.frames == 2 for r in cl_runs)
        assert rt.stats()["failover"]["parked_now"] == 3
        revive_tick = rt.ticks + 1              # revival fires before tick 6
        harness.run(2)
        recovery = rt.ticks - revive_tick
        assert recovery <= 2
        # parked frames resumed; per-tick cadence restored
        assert rt.stats()["failover"]["parked_now"] == 0
        assert all(r.frames >= 3 for r in cl_runs)

    def test_silent_death_detected_by_lease_expiry(self, chaos):
        """crash=False: the device stops heartbeating and serving but sends
        no mark_down — the broker must notice via the lease and fail the
        clients over on its own."""
        rt = Runtime(query_batch=8, lease_ticks=2)
        devA, _, ssrcA = _server(rt, name="hubA")
        devB, runB, ssrcB = _server(rt, name="hubB")
        cl_runs = _clients(rt, 4)
        harness = chaos(rt)
        harness.kill_server(4, devA, ssrcA, crash=False)
        harness.run(10)
        assert rt.broker.expiries >= 1
        assert ssrcA.registration.alive is False
        assert ssrcA.registration.down_reason == "lease-expired"
        # every tick still answered: the binding's data-plane liveness check
        # bridges the gap between the silent death and the lease expiry
        assert all(r.frames == 10 for r in cl_runs)
        assert runB.frames >= 4 * 6

    def test_forced_lease_expiry_fails_over(self, chaos):
        """chaoslib.expire_lease: a stalled device's lease lapses on the
        next broker tick even though the lease horizon is far away — the
        down event re-routes clients with no frame lost."""
        rt = Runtime(query_batch=8, lease_ticks=50)
        devA, _, ssrcA = _server(rt, name="hubA")
        _, runB, _ = _server(rt, name="hubB")
        cl = _clients(rt, 2)
        harness = chaos(rt)
        harness.expire_lease(4, devA, ssrcA.registration)
        harness.run(8)
        assert ssrcA.registration.down_reason == "lease-expired"
        assert rt.broker.expiries == 1
        assert all(r.frames == 8 for r in cl)
        assert runB.frames >= 2 * 5

    def test_leases_never_expire_for_heartbeating_devices(self):
        rt = Runtime(query_batch=8, lease_ticks=1)
        _server(rt)
        cl_runs = _clients(rt, 2)
        rt.run(8)
        assert rt.broker.expiries == 0
        assert all(r.frames == 8 for r in cl_runs)


class TestParkDeadline:
    """``Runtime(park_deadline_ticks=N)`` bounds how long a frame may stay
    parked with no live server (DESIGN.md §6 satellite): at the deadline the
    frame expires into an accounted ``parked_expired`` stat and a
    client-visible error buffer in the pipeline's sink log — EXPLICIT
    degradation instead of an unbounded busy-skip — and the pipeline is
    freed to start fresh frames."""

    def test_expiry_is_accounted_and_client_visible(self, chaos):
        rt = Runtime(query_batch=8, park_deadline_ticks=3)
        dev, _, ssrc = _server(rt)
        cl = _clients(rt, 3)
        harness = chaos(rt)
        harness.kill_server(3, dev, ssrc, crash=True)   # never revived
        harness.run(10)
        fo = rt.stats()["failover"]
        # tick-3 frames parked (t0=3) and expired at tick 6; the freed
        # pipelines parked fresh frames which expired at tick 9 in turn
        assert fo["parked_expired"] == 6
        assert fo["parked_now"] == 3            # the tick-9 generation
        for r in cl:
            assert r.frames == 2                # ticks 1-2 answered
            errs = r.sink_log.get("qc.error", [])
            assert len(errs) == 2               # one per expired frame
            for e in errs:
                assert e.meta["error"] == "park-deadline"
                assert e.meta["operation"] == "op"
                assert e.meta["parked_ticks"] == 3
                assert e.tensors == ()          # an error answer, not data

    def test_recovery_before_deadline_expires_nothing(self, chaos):
        """The deadline must never fire on a frame a revival saved: parked
        at tick 3 with a 5-tick deadline, the server returns at tick 5 —
        the frame completes normally, no error, nothing expired."""
        rt = Runtime(query_batch=8, park_deadline_ticks=5)
        dev, _, ssrc = _server(rt)
        cl = _clients(rt, 3)
        harness = chaos(rt)
        harness.kill_server(3, dev, ssrc, crash=True)
        harness.revive_server(5, dev, ssrc)
        harness.run(8)
        fo = rt.stats()["failover"]
        assert fo["parked_expired"] == 0
        assert fo["parked_now"] == 0
        for r in cl:
            assert "qc.error" not in r.sink_log
            # only the 2-tick outage is missing; the parked frame resumed
            assert r.frames == 8 - 2

    def test_deadline_measures_total_time_parked(self, chaos):
        """Re-parks must not reset the clock: a frame that parks, fails a
        retry, and parks again still expires ``park_deadline_ticks`` after
        it FIRST parked (the retry loop re-parks every tick — a reset would
        make the deadline unreachable)."""
        rt = Runtime(query_batch=8, park_deadline_ticks=4)
        dev, _, ssrc = _server(rt)
        _clients(rt, 1)
        harness = chaos(rt)
        harness.kill_server(3, dev, ssrc, crash=True)
        harness.run(6)                           # parked t0=3, retried 4-6
        assert rt.stats()["failover"]["parked_expired"] == 0
        harness.run(1)                           # tick 7: 7-3 >= 4 → expire
        assert rt.stats()["failover"]["parked_expired"] == 1


class TestResponseChannelLifecycle:
    """Regression: ``QueryServerEndpoint.responses`` channels were never
    RELEASED — death/revival only cleared their queues, so every chaos
    kill/revive epoch (and every client generation) left one orphaned
    Channel per client id on the endpoint, forever.  Liveness transitions
    must purge the dict; steady-state reuse must keep it at one channel per
    live bound client."""

    def test_kill_revive_cycles_keep_channels_bounded(self, chaos):
        n_clients, cycles = 4, 3
        rt = Runtime(query_batch=8)
        devA, _, ssrcA = _server(rt, name="hubA")
        _server(rt, name="hubB")
        cl = _clients(rt, n_clients)
        rt.run(2)
        ep = ssrcA.endpoint
        assert len(ep.responses) == n_clients      # one per bound client
        for c in range(cycles):
            harness = chaos(rt)
            t = rt.ticks
            harness.kill_server(t + 1, devA, ssrcA)
            harness.revive_server(t + 3, devA, ssrcA)
            harness.run(5)
            # the down event released every channel; clients that came back
            # after the revival re-created exactly theirs — no epoch leak
            assert len(ep.responses) <= n_clients
        assert all(r.frames == rt.ticks for r in cl)   # and nothing lost

    def test_down_event_purges_channels_not_just_queues(self):
        rt = Runtime(query_batch=8)
        _, _, ssrc = _server(rt)
        _clients(rt, 3)
        rt.run(1)
        ep = ssrc.endpoint
        assert len(ep.responses) == 3
        ssrc.endpoint.alive = False
        rt.broker.mark_down(ssrc.registration)
        assert len(ep.responses) == 0              # released, not drained

    def test_client_churn_across_outages_does_not_accumulate(self, chaos):
        """Fresh client generations across kill/revive epochs: dead
        generations' channels must not pile up on the endpoint."""
        rt = Runtime(query_batch=8)
        dev, _, ssrc = _server(rt)
        _clients(rt, 2)
        rt.run(1)
        harness = chaos(rt)
        for c in range(3):
            t = rt.ticks
            harness.kill_server(t + 1, dev, ssrc)
            harness.revive_server(t + 2, dev, ssrc)
            harness.run(3)
            _clients(rt, 2)                        # a new generation joins
        rt.run(1)
        # 2 original + 3x2 new = 8 live clients max; without the purge the
        # endpoint would also hold every pre-outage generation's channels
        assert len(ssrc.endpoint.responses) <= 8


class TestCapabilityRouting:
    def test_throughput_ranking_beats_registration_order(self):
        rt = Runtime(query_batch=8)
        _server(rt, name="slowhub", throughput=1)
        _, fast_run, _ = _server(rt, name="fasthub", throughput=8)
        cl = _clients(rt, 3)
        rt.run(2)
        assert fast_run.frames == 6       # all routed to the faster server
        assert all(r.frames == 2 for r in cl)

    def test_codec_support_ranking(self):
        """A quant8 client prefers a server declaring quant8 support over an
        earlier-registered one that declares it cannot."""
        rt = Runtime(query_batch=8)
        _, plain_run, ssrc1 = _server(rt, name="plainhub")
        _, q8_run, ssrc2 = _server(rt, name="q8hub")
        ssrc1.registration.specs["codecs"] = ("none",)
        ssrc2.registration.specs["codecs"] = ("none", "quant8")
        cl = _clients(rt, 2, codec="quant8")
        rt.run(2)
        assert q8_run.frames == 4 and plain_run.frames == 0
        assert all(r.frames == 2 for r in cl)

    def test_load_breaks_ties(self):
        b = Broker()
        r1 = b.register("query/op", Caps.ANY, "busy")
        r2 = b.register("query/op", Caps.ANY, "idle")
        r1.load = 5.0
        assert b.subscribe("query/op").endpoint == "idle"
        r1.load = 0.0
        assert b.subscribe("query/op").endpoint == "busy"  # reg-order tiebreak

    def test_runtime_refreshes_load_from_queue_depth(self):
        rt = Runtime(query_batch=8)
        _, _, ssrc = _server(rt)
        _clients(rt, 2)
        rt.run(1)
        # after a tick the queue has drained back to empty — the declared
        # load tracks the instantaneous backlog
        assert ssrc.registration.load == 0.0


class TestRebindOrdering:
    def test_preferred_down_then_revived_wins_back_exactly_once(self):
        """Regression pin: preferred registration marked down then revived
        must win the binding back exactly once, with no duplicate watch
        event delivery (idempotent mark_down/revive)."""
        b = Broker()
        fast = b.register("svc/a", Caps.ANY, "fast", throughput=10)
        b.register("svc/b", Caps.ANY, "slow", throughput=1)
        events = []
        b.watch(lambda ev, reg: events.append((ev, reg.endpoint)))
        sub = b.subscribe("svc/#")
        assert sub.endpoint == "fast"

        b.mark_down(fast)
        b.mark_down(fast)                      # duplicate: must not re-fire
        assert sub.endpoint == "slow"
        assert sub.failovers == 1

        b.revive(fast)
        b.revive(fast)                         # duplicate: must not re-fire
        assert sub.endpoint == "fast"          # won back ...
        assert sub.failovers == 2              # ... exactly once
        assert events.count(("down", "fast")) == 1
        assert events.count(("register", "fast")) == 1

    def test_equal_rank_newcomer_does_not_steal(self):
        b = Broker()
        b.register("svc/a", Caps.ANY, "first")
        sub = b.subscribe("svc/#")
        b.register("svc/a", Caps.ANY, "second")   # same rank, later reg_id
        assert sub.endpoint == "first"
        assert sub.failovers == 0

    def test_higher_throughput_newcomer_does_steal(self):
        b = Broker()
        b.register("svc/a", Caps.ANY, "weak", throughput=1)
        sub = b.subscribe("svc/#")
        b.register("svc/a", Caps.ANY, "strong", throughput=4)
        assert sub.endpoint == "strong"
        assert sub.failovers == 1

    def test_closed_binding_stops_receiving_events(self):
        b = Broker()
        r = b.register("svc/a", Caps.ANY, "first")
        sub = b.subscribe("svc/#")
        sub.close()
        b.mark_down(r)
        assert sub.current is r                # stale by design after close
        with pytest.raises(BrokerError):
            _ = b.subscribe("svc/#").endpoint


class TestPubSubRebind:
    def test_rebind_preserves_queued_frames(self, chaos):
        """Publisher dies with frames still queued at the subscriber: the
        rebind to the backup publisher must deliver those frames first —
        nothing queued is dropped (DESIGN.md §3 rebind guarantee).  The two
        publishers emit different frame shapes so every consumed frame is
        attributable to its producer."""
        rt = Runtime()
        pubs = []
        for name, w in (("pubA", 2), ("pubB", 4)):
            d = Device(name)
            p = parse_launch(
                f"testsrc width={w} height=2 ! tensor_converter ! "
                f"mqttsink pub-topic=cam/{name} name=snk")
            prun = d.add_pipeline(p, jit=False)
            rt.add_device(d)
            pubs.append((d, prun))
        sub = Device("screen")
        s = parse_launch("mqttsrc sub-topic=cam/# name=src ! appsink name=o")
        sub_run = sub.add_pipeline(s, jit=False)
        rt.add_device(sub)
        src = s.elements["src"]

        rt.run(3)                                  # consumes pubA pts 0..2
        devA, runA = pubs[0]
        # strand two frames: pubA publishes twice more without the consumer
        # running, then dies before they are drained
        rt._run_once(runA)
        rt._run_once(runA)
        assert len(src._rx) == 2
        harness = chaos(rt)
        harness.kill_device(4, devA)
        harness.run(4)
        log = sub_run.sink_log["o"]
        pts_shapes = [(int(b.pts), tuple(b.tensor.shape)) for b in log]
        # pubA's whole stream arrived — including the two frames stranded
        # at its death — in order, before any backup frame (pts are
        # sync-rebased, so assert per-producer ordering, not raw indices)
        a = [(p, s) for p, s in pts_shapes if s == (2, 2, 3)]
        back = pts_shapes[len(a):]
        assert len(a) == 5                       # 3 consumed + 2 stranded
        assert all(s == (2, 2, 3) for _, s in pts_shapes[:5])
        assert [p for p, _ in a] == sorted(p for p, _ in a)
        # then the backup publisher's stream, also in order
        assert back and all(s == (2, 4, 3) for _, s in back)
        assert [p for p, _ in back] == sorted(p for p, _ in back)

    def test_explicit_strand_and_rebind_keeps_frames(self):
        """Unit-level pin of the carry-over: frames sitting in the consumer
        queue when the binding flips publishers are decoded into the
        pushback line in order, ahead of the new publisher's frames."""
        from repro.core import Channel
        from repro.core.pubsub import MqttSrc

        b = Broker()
        chA, chB = Channel(), Channel()
        regA = b.register("cam/a", Caps.ANY, chA)
        b.register("cam/b", Caps.ANY, chB)
        src = MqttSrc(name="src", sub_topic="cam/#").connect(b)
        # bind to A and queue two frames
        chA.push(StreamBuffer(tensors=(jnp.zeros((2, 2)),), pts=jnp.int32(0)))
        assert src.pull().pts == 0
        chA.push(StreamBuffer(tensors=(jnp.ones((2, 2)),), pts=jnp.int32(1)))
        chA.push(StreamBuffer(tensors=(jnp.ones((2, 2)),), pts=jnp.int32(2)))
        chB.push(StreamBuffer(tensors=(jnp.ones((2, 2)),), pts=jnp.int32(9)))
        b.mark_down(regA)      # binding flips to B with 2 frames stranded
        got = [int(src.pull().pts) for _ in range(3)]
        assert got == [1, 2, 9]    # stranded frames first, in order

    def test_queued_counts_carried_frames_on_the_rebind_tick(self):
        """Regression: queued() must resolve BEFORE counting — the rebind
        moves stranded frames into the pushback line, and undercounting
        them would mark the pipeline not-ready for a tick."""
        from repro.core import Channel
        from repro.core.pubsub import MqttSrc

        b = Broker()
        chA, chB = Channel(), Channel()
        regA = b.register("cam/a", Caps.ANY, chA)
        b.register("cam/b", Caps.ANY, chB)
        src = MqttSrc(name="src", sub_topic="cam/#").connect(b)
        assert src.queued() == 0               # attaches to A
        chA.push(StreamBuffer(tensors=(jnp.zeros((2, 2)),), pts=jnp.int32(0)))
        chA.push(StreamBuffer(tensors=(jnp.ones((2, 2)),), pts=jnp.int32(1)))
        b.mark_down(regA)                      # flips to B, frames stranded
        assert src.queued() == 2               # counted on this very call

    def test_winback_rebind_no_duplicates_no_stranding(self):
        """Regression: re-binding BACK to a previously bound publisher must
        reuse its consumer queue — re-attaching would replay the retained
        history a second time (duplicate frames) while the publisher's
        post-revival frames rotted in the orphaned old queue."""
        from repro.core import Channel
        from repro.core.pubsub import MqttSrc

        b = Broker()
        chA, chB = Channel(), Channel()
        regA = b.register("cam/a", Caps.ANY, chA, throughput=2)
        b.register("cam/b", Caps.ANY, chB)
        # retained history on A before the subscriber ever attaches
        chA.push(StreamBuffer(tensors=(jnp.zeros((2, 2)),), pts=jnp.int32(0)))
        src = MqttSrc(name="src", sub_topic="cam/#").connect(b)
        assert int(src.pull().pts) == 0        # replayed once, consumed
        b.mark_down(regA)                      # fail over to B
        chB.push(StreamBuffer(tensors=(jnp.ones((2, 2)),), pts=jnp.int32(10)))
        assert int(src.pull().pts) == 10
        b.revive(regA)                         # throughput: A wins back
        chA.push(StreamBuffer(tensors=(jnp.ones((2, 2)),), pts=jnp.int32(1)))
        assert int(src.pull().pts) == 1        # fresh frame, NOT a replay of 0
        assert src.pull() is None              # and no duplicates after it
        assert len(chA.consumers) == 1         # no consumer leak per rebind
