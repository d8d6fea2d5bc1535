"""Multi-pipeline runtime: simulates an among-device deployment in-process.

Each Device owns a clock (with skew/jitter — real consumer devices disagree
about time) and a set of pipelines.  The Runtime drives everything with a
global tick (default 60 Hz frame cadence, matching the paper's evaluation):

  * per tick, every device advances its clock and runs each pipeline whose
    inputs are ready (mqttsrc with an empty channel = not ready, like a
    GStreamer src blocking on no data);
  * mqttsink pushes into its Channel; Channels can carry latency (the
    paper's queue2 latency-injection experiment) and bounded capacity with
    leaky-drop semantics;
  * query clients run synchronously against their server pipeline (the
    runtime wires ``inline_runner`` so a client step triggers the remote
    inference — one round-trip per frame, as in Fig. 2).

Burst draining (default on, ``burst=8``): when a subscriber pipeline has
frames queued in its Channels — a slow consumer that fell behind, or a late
joiner replaying retained history — the scheduler drains up to ``burst``
frames in ONE dispatch instead of one frame per tick.  The host pulls and
decodes the queued frames, stacks them (``stack_buffers``), and runs the
pipeline's compiled plan in hoisted-I/O mode: a single ``lax.scan`` executes
the whole DAG N times, then captured mqttsink frames are replayed through
the real (impure) sink ``apply`` in order.  Pipelines whose impure elements
are not hoistable fall back to per-frame stepping automatically.

Query micro-batching (default on, ``query_batch=8``, DESIGN.md §2): client
pipelines run *deferred* — the plan pauses at each ``tensor_query_client``,
the scheduler ships the request to the server endpoint's ``QueryBatcher``,
and once every ready pipeline has sent (the tick deadline — or earlier when
a batcher hits ``max_batch``), each server serves its gathered requests in
ONE hoisted scan dispatch and the paused frames resume with their routed
answers.  ``query_batch=0`` restores the legacy synchronous one-round-trip-
per-frame path inside ``tensor_query_client.apply``.

Failover fabric (DESIGN.md §3): the runtime heartbeats the broker on behalf
of every live device each tick and advances the broker's lease clock, so a
silently dead device's registrations expire and fire ``down`` events; query
requests whose serving endpoint dies in flight are re-dispatched — each
``PendingQuery`` retains its request buffer and records the endpoint it was
shipped to — to the next-ranked surviving server, or *parked* until one
registers (retried at the top of every tick; ``park_deadline_ticks`` bounds
how long — an expired park becomes an accounted, client-visible error
instead of an unbounded busy-skip).  Killing a server therefore loses zero
client requests; with a surviving (same-seeded) server the answers are
bitwise what the fault-free run produces.

Live reconfiguration (DESIGN.md §6): ``Runtime.reconfigure(run, edit)``
applies a topology edit — swap an element, re-route a link, add/remove an
endpoint or binding — to a RUNNING pipeline with prepare → warm → commit →
drain semantics (``core/reconfig.py``): the new plan realizes and warms off
the serving path, then cuts over at a tick boundary with queued frames and
in-flight queries carried across the swap.  Broker liveness events route
through the same machinery (``ReconfigManager.on_broker_event``): a server
death or revival is an unplanned topology edit, handled by the same
endpoint teardown/activation a planned remove/add uses.

Mesh-sharded serving (DESIGN.md §4): ``Runtime(mesh=...)`` can lay batched
query serves and hoisted pub/sub bursts out along the mesh's data axes —
one frame slice per device, params replicated — whenever the batch tiles
the mesh and the plan threads no cross-frame state
(``ExecutionPlan.shardable_batch``).  ``mesh="auto"`` builds a host mesh
over the local devices; ``shard_mode`` picks the placement policy
("auto" probes sharded-vs-single once per batch size and keeps the faster,
"always"/"never" force it).  Sharding never changes semantics: non-tiling
groups, stateful plans, and 1-device meshes serve exactly like
``Runtime(mesh=None)``, and the failover fabric re-dispatches sharded
batches' orphans identically (the mesh only places compute; the
request/answer plumbing is untouched).

Fused wire path (DESIGN.md §5): the whole codec/transport hot path of a
tick runs batched.  Client pipelines whose only impure elements are their
query clients start and resume through jitted deferred SEGMENTS
(``plan.run_deferred_compiled``) instead of interpreted walks; a dispatch
round gathers every freshly paused frame, encodes the requests per (codec,
structure) group in ONE batched codec dispatch, and pushes in arrival
order; each server flush serves wire-form groups through the codec-fused
executable (decode → stacked scan → answer re-encode inside one jit,
``core/batching.py``); and the drain batch-decodes the round's answers per
group before resuming.  Bitwise identical to per-frame serving at every
seam (codec kernels batch by tile/block merge, segments jit the same
program).

Statistics (frames, drops, bytes, bursts, batches, redispatches, per-sink
pts) feed the Fig. 7 benchmark.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax

from ..core.admission import (DEFAULT_TENANT, QoSConfig, merge_tenant_stats,
                              percentile_from_hist)
from ..core.batching import (BatchingPolicy, QueryBatcher,
                             StagedStreamingBatcher, StageQueryBatcher,
                             StreamingQueryBatcher, DEFAULT_QUERY_BATCH)
from ..core.broker import Broker, BrokerError
from ..core.buffers import (StreamBuffer, stack_buffers, structure_key,
                            unstack_buffers)
from ..core.element import Element
from ..core import netfault, tracing
from ..core.pipeline import Pipeline
from ..core.plan import PendingQuery
from ..core.pubsub import Channel, MqttSink, MqttSrc
from ..core.query import (QueryServerEndpoint, TensorQueryClient,
                          TensorQueryServerSrc)
from ..core.sync import PipelineClock, SimClock

TICK_NS = 16_666_667  # 60 Hz
DEFAULT_BURST = 8


@dataclass
class _PipeRun:
    pipe: Pipeline
    params: dict
    state: dict
    step_fn: Callable
    frames: int = 0
    skipped: int = 0
    bursts: int = 0              # multi-frame drains executed
    burst_frames: int = 0        # frames delivered via bursts
    last_outputs: Dict[str, StreamBuffer] = field(default_factory=dict)
    sink_log: Dict[str, list] = field(default_factory=dict)
    #: mesh-replicated copy of ``params``, placed lazily at first sharded
    #: burst (re-broadcasting params per dispatch costs more than the serve)
    mesh_params: Optional[dict] = None
    #: whether the run steps through the cached compiled plan (Device.add_
    #: pipeline's ``jit`` flag, retained so a hot swap rebuilds ``step_fn``
    #: in the same execution mode)
    jit: bool = True
    #: decommissioned by a reconfiguration that removed every element — the
    #: scheduler skips it without counting skips (there is nothing to run)
    retired: bool = False
    #: drops inherited from elements a reconfiguration REMOVED: their queued
    #: backlogs and leaky-drop histories leave the topology with them, and
    #: the conservation accounting must not forget those frames
    carried_drops: int = 0

    @property
    def host_srcs(self) -> List[MqttSrc]:
        return self.pipe.plan.host_sources

    @property
    def host_sinks(self) -> List[MqttSink]:
        return self.pipe.plan.host_sinks


class Device:
    def __init__(self, name: str, clock: Optional[SimClock] = None):
        self.name = name
        self.clock = clock or SimClock()
        self.pipeline_clock = PipelineClock(self.clock)
        self.runs: List[_PipeRun] = []
        #: liveness flag the chaos harness flips: a dead device's pipelines
        #: stop running and the runtime stops heartbeating its registrations
        #: (so leases expire and the broker announces the death)
        self.alive = True

    def add_pipeline(self, pipe: Pipeline, rng=None, jit: bool = True) -> _PipeRun:
        pipe.realize()
        # wire pipeline clock into pub/sub elements for §4.2.3 sync
        for e in pipe.elements.values():
            if isinstance(e, (MqttSink, MqttSrc)) and e.sync_clock is None:
                e.sync_clock = self.pipeline_clock
        params = pipe.init(rng if rng is not None else jax.random.PRNGKey(0))
        state = pipe.init_state()
        # pure pipelines step through the cached compiled plan; host-impure
        # ones run the plan interpreted (their apply does channel I/O)
        fn = pipe.compiled_step() if (jit and pipe.plan.pure) else pipe.step
        run = _PipeRun(pipe=pipe, params=params, state=state, step_fn=fn,
                       jit=jit)
        self.runs.append(run)
        return run


class Runtime:
    def __init__(self, broker: Optional[Broker] = None, tick_ns: int = TICK_NS,
                 burst: int = DEFAULT_BURST,
                 query_batch=DEFAULT_QUERY_BATCH,
                 lease_ticks: Optional[int] = None,
                 mesh=None, shard_mode: str = "auto",
                 park_deadline_ticks: Optional[int] = None,
                 qos: Optional[QoSConfig] = None,
                 delivery: Optional["netfault.DeliveryPolicy"] = None):
        self.broker = broker or Broker()
        if lease_ticks is not None:
            self.broker.default_lease_ticks = lease_ticks
        self.devices: List[Device] = []
        self.tick_ns = tick_ns
        self.burst = max(1, int(burst))
        #: jax Mesh for among-device serving (DESIGN.md §4): batched query
        #: serves and hoisted bursts lay their frame axis out along the
        #: mesh's data axes when shardable; ``mesh="auto"``/``True`` builds a
        #: host mesh over the local devices.  None = single-device serving.
        if mesh in ("auto", True):
            from ..launch.mesh import make_host_mesh
            mesh = make_host_mesh()
        self.mesh = mesh
        #: "auto" probes sharded-vs-single per batch size and keeps the
        #: faster (core/batching.py docstring); "always"/"never" force it.
        #: Validated HERE, not only in QueryBatcher: a pub/sub-only
        #: deployment never builds a batcher, and the burst path's string
        #: compare would silently turn a typo into "never".
        if shard_mode not in ("auto", "always", "never"):
            raise ValueError(f"shard_mode {shard_mode!r} not in "
                             f"('auto', 'always', 'never')")
        self.shard_mode = shard_mode
        #: tenant-aware admission policy (DESIGN.md §9): None keeps every
        #: batcher's AdmissionQueue in exact global-FIFO pass-through —
        #: the pre-QoS fabric, bit for bit
        self.qos = qos
        #: at-least-once delivery layer (DESIGN.md §10): None keeps the
        #: reliable-transport fabric bit for bit — no delivery ids, no
        #: checksums, no retransmits.  Set, every query/hop/answer frame
        #: carries a (sender, seq) id + CRC, receivers dedup and reject
        #: corruption, and unanswered requests retransmit on the backoff
        #: clock below.
        self.delivery = delivery
        #: FaultFabric (core/netfault.py) a chaos scenario installed —
        #: stepped at the top of every tick so delayed/reordered frames
        #: release on the scheduler's clock.  None outside chaos runs.
        self.fabric = None
        #: devices whose CONTROL plane is partitioned (heartbeats lost in
        #: the network, data plane per the installed fault links) — the §10
        #: suspicion scenario: their leases expire; their beats resume and
        #: heal the suspicion when removed from this set
        self._control_blocked: set = set()
        #: §10 retransmit ledger (client-side timeouts that re-shipped)
        self.retransmits = 0
        #: elastic-serving controllers (runtime/autoscale.py) — stepped at
        #: every tick boundary right after pending reconfigs; an Autoscaler
        #: registers itself here
        self.autoscalers: List = []
        #: transient per-tick dispatch load (QoS join-shortest-queue): a
        #: round's requests spread over replicas whose heartbeat load has
        #: not seen this tick's dispatches yet; cleared every tick
        self._load_bumps: Dict[int, int] = {}
        #: tenant sheds the RUNTIME owns (park/deadline expiries — frames
        #: that never reached a server's admission queue), same schema as
        #: AdmissionQueue.stats() entries so the ledgers merge
        self._tenant_shed: Dict[str, Dict] = {}
        #: per-tenant ledgers of batchers a reconfiguration retired —
        #: conservation must survive replica scale-down
        self._tenant_archive: Dict[str, Dict] = {}
        #: query micro-batching policy (int = max batch; 0 disables —
        #: legacy synchronous round-trips inside the client's apply)
        self.batching = BatchingPolicy.of(query_batch)
        #: endpoint_id -> QueryBatcher for every runtime-wired serversrc
        self._batchers: Dict[int, QueryBatcher] = {}
        #: frames paused at a query client with NO live server to take the
        #: request — retried at the top of every tick until one registers,
        #: each entry ``(run, pq, parked_at_tick)``; the park tick survives
        #: re-parks so ``park_deadline_ticks`` measures TOTAL time parked
        self._parked: List[Tuple[_PipeRun, PendingQuery, int]] = []
        #: frames whose request is a STREAM mid-generation on a live server
        #: (StreamingQueryBatcher.in_flight) — unlike parked frames they
        #: have a server, it just needs more decode ticks; re-enter the
        #: drain at the top of every tick until the stream finishes
        self._inflight: List[Tuple[_PipeRun, PendingQuery]] = []
        #: ticks a frame may stay parked before it expires into an accounted
        #: client-visible error (None = park forever, the pre-PR-6 behavior)
        self.park_deadline_ticks = park_deadline_ticks
        # failover accounting (DESIGN.md §3)
        self.redispatches = 0
        self.parked_total = 0
        self.parked_expired = 0
        self.orphaned_requests = 0
        self.ticks = 0
        # every topology change — planned hot swaps AND broker liveness
        # events (a server death/revival is an unplanned edit) — routes
        # through the reconfiguration manager (DESIGN.md §6)
        from ..core.reconfig import ReconfigManager
        self.reconfig = ReconfigManager(self)
        self.broker.watch(self.reconfig.on_broker_event)

    def add_device(self, device: Device) -> Device:
        self.devices.append(device)
        # connect broker-facing elements & calibrate NTP against the broker's
        # reference clock (a fresh zero-skew SimClock)
        if not hasattr(self, "_ntp_ref"):
            self._ntp_ref = SimClock()
        for run in device.runs:
            self._wire(device, run)
        device.pipeline_clock.calibrate(self._ntp_ref)
        device.pipeline_clock.start()
        return device

    def _wire(self, device: Device, run: _PipeRun):
        for e in run.pipe.elements.values():
            if isinstance(e, (MqttSink, MqttSrc, TensorQueryClient)) and e.broker is None:
                e.connect(self.broker)
            if isinstance(e, TensorQueryClient) and self.delivery is not None:
                e.delivery = self.delivery
            if isinstance(e, TensorQueryServerSrc) and e.registration is None:
                # the endpoint's inline_runner is the batcher's flush: edge
                # clients and direct pipe.step round-trips keep their
                # serve-before-return contract, while runtime-driven clients
                # go through the deferred queue-gather-flush path
                stream = any(getattr(el, "is_stream_serve", False)
                             for el in run.pipe.elements.values())
                staged = [el for el in run.pipe.elements.values()
                          if getattr(el, "is_stage_serve", False)]
                if staged and staged[0].stage > 0:
                    # downstream hop of an among-device pipeline-parallel
                    # chain (DESIGN.md §8): serves prefill/replay/decode-hop
                    # verbs against its layer slice, parking b=1 caches by
                    # stream id — no admission lifecycle of its own
                    batcher = StageQueryBatcher(
                        e.endpoint, run, self.batching,
                        inline_step=lambda r=run: self._run_once(r),
                        mesh=self.mesh, shard_mode=self.shard_mode,
                        on_orphans=self._count_orphans,
                        # hop traffic is NEVER re-scheduled (each hop is one
                        # step of a stream the stage-0 coordinator already
                        # admitted under its tenant's budget) — qos stays off
                        qos=None, clock=lambda: self.ticks)
                elif staged:
                    # stage-0 coordinator: owns the admission lifecycle AND
                    # drives the per-tick hop chain to downstream stages it
                    # discovers through the broker
                    batcher = StagedStreamingBatcher(
                        e.endpoint, run, self.batching,
                        inline_step=lambda r=run: self._run_once(r),
                        mesh=self.mesh, shard_mode=self.shard_mode,
                        on_orphans=self._count_orphans,
                        tick_source=lambda: self.ticks,
                        broker=self.broker,
                        qos=self.qos, clock=lambda: self.ticks)
                elif stream:
                    # streaming serve pipeline (model_serve): requests live
                    # across ticks in plan-state slots, so the endpoint gets
                    # the continuous-batching lifecycle instead of the
                    # stateless gather-stack-flush
                    batcher = StreamingQueryBatcher(
                        e.endpoint, run, self.batching,
                        inline_step=lambda r=run: self._run_once(r),
                        mesh=self.mesh, shard_mode=self.shard_mode,
                        on_orphans=self._count_orphans,
                        tick_source=lambda: self.ticks,
                        qos=self.qos, clock=lambda: self.ticks)
                else:
                    batcher = QueryBatcher(
                        e.endpoint, run, self.batching,
                        inline_step=lambda r=run: self._run_once(r),
                        mesh=self.mesh, shard_mode=self.shard_mode,
                        on_orphans=self._count_orphans,
                        qos=self.qos, clock=lambda: self.ticks)
                if self.delivery is not None:
                    # one guard per endpoint, shared by the batcher (request
                    # triage) and its paired serversink (answer CRC + replay
                    # cache) — §10's receiver half
                    guard = netfault.DeliveryGuard(self.delivery)
                    batcher.guard = guard
                    if isinstance(batcher, StagedStreamingBatcher):
                        batcher.delivery = self.delivery
                    for el in run.pipe.elements.values():
                        if getattr(el, "is_query_sink", False) and \
                                getattr(el, "serversrc", None) is e:
                            el.guard = guard
                self._batchers[e.endpoint.endpoint_id] = batcher
                e.connect(self.broker, inline_runner=batcher.flush)
        # (re)negotiate with broker wiring in place so mqttsink registers;
        # the rebuilt plan keeps its fingerprint, so compiled executables
        # from before the re-wire are reused, not retraced
        run.pipe._realized = False
        run.pipe.realize()

    # -- live reconfiguration (DESIGN.md §6) --------------------------------------
    def reconfigure(self, run: _PipeRun, edit, warm_ticks: int = 1,
                    rng=None):
        """Apply a topology edit to a RUNNING pipeline with prepare → warm →
        commit → drain semantics.  ``edit`` is a
        :class:`~repro.core.reconfig.ReconfigPlan` (``run.pipe.reconfig()``)
        or a callable receiving a fresh one; the edit prepares and warms
        immediately (off the serving path) and commits at the first tick
        boundary after ``warm_ticks`` ticks — or rolls back with explicit
        stats if the prepare fails or the target device dies mid-warm.
        Returns the :class:`~repro.core.reconfig.Reconfiguration` handle
        (``status``, ``frames_carried``, ``committed_tick``)."""
        from ..core.reconfig import ReconfigPlan
        plan = edit
        if not isinstance(edit, ReconfigPlan):
            plan = ReconfigPlan(run.pipe)
            edit(plan)
        return self.reconfig.request(run, plan, warm_ticks=warm_ticks,
                                     rng=rng)

    def _device_of(self, run: _PipeRun) -> Optional[Device]:
        for dev in self.devices:
            if run in dev.runs:
                return dev
        return None

    def _run_in_flight(self, run: _PipeRun) -> bool:
        """Whether the run has a frame paused mid-schedule across ticks (a
        parked PendingQuery, or a stream mid-generation) — a commit must
        drain those on the old epoch before cutting over, never swap a plan
        out from under a live walk.  Note this guards the CLIENT pipeline's
        run only: the server run itself carries no paused walk, so a server
        hot-swap commits mid-decode (the stateful-plan contract pins it)."""
        return any(r is run for r, _, _ in self._parked) or \
            any(r is run for r, _ in self._inflight)

    def _count_orphans(self, n: int):
        """Orphan-ledger hook for mid-flush deaths (QueryBatcher)."""
        self.orphaned_requests += n

    def _retire_element(self, e: Element):
        """Take an element a committed reconfiguration removed out of the
        control plane: unregister standing registrations (fires
        ``unregister`` — clients re-bind via the exactly-once win-back, and
        a query endpoint tears down through the manager's event path), close
        consumer bindings, and drop the endpoint's batcher."""
        reg = getattr(e, "registration", None)
        if reg is not None:
            self.broker.unregister(reg)
            e.registration = None
        binding = getattr(e, "binding", None)
        if binding is not None:
            binding.close()
            e.binding = None
        ep = getattr(e, "endpoint", None)
        if isinstance(ep, QueryServerEndpoint):
            b = self._batchers.pop(ep.endpoint_id, None)
            if b is not None:
                # fold the retired batcher's per-tenant ledgers into the
                # archive — scale-down must not forget served/shed history
                # or the conservation law breaks at the next stats() call
                merge_tenant_stats(self._tenant_archive, b.tenant_stats())

    # -- liveness: heartbeats, leases -----------------------------------------
    def _heartbeat_and_lease(self):
        """Beat on behalf of every live device's registrations, refresh load
        declarations from the serving queues, then advance the broker's
        lease clock (expiring whoever went silent)."""
        for dev in self.devices:
            if not dev.alive:
                continue
            if dev in self._control_blocked:
                # control partition (§10): the device is up and serving but
                # its heartbeats are lost in the network — the broker sees
                # silence, the lease lapses, and the expiry lands as
                # SUSPICION rather than declared death
                continue
            for run in dev.runs:
                for e in run.pipe.elements.values():
                    reg = getattr(e, "registration", None)
                    if reg is None:
                        continue
                    if not reg.alive and reg.suspected:
                        # the suspected device is beating again: the expiry
                        # was delay/partition, not death.  Win-back is the
                        # ordinary revive "register" event; requests already
                        # re-dispatched stay wherever dedup settles them.
                        self.broker.heal(reg)
                    self.broker.heartbeat(reg)
                    if isinstance(e, TensorQueryServerSrc):
                        # "server workload status": instantaneous backlog —
                        # channel depth plus whatever admission already
                        # ingested; under QoS the load signal also counts
                        # active decode slots (streams occupying capacity
                        # across ticks), which the autoscaler and the JSQ
                        # dispatch read.  Pre-QoS deployments keep the
                        # channel-only signal bit for bit (binding choices
                        # in the failover pins depend on it).
                        load = float(len(e.endpoint.requests))
                        b = self._batchers.get(e.endpoint.endpoint_id)
                        if b is not None:
                            load += float(len(b.admission))
                            if self.qos is not None and \
                                    hasattr(b, "active_streams"):
                                load += float(b.active_streams())
                        reg.load = load
        self.broker.tick()

    # -- readiness ---------------------------------------------------------------
    def _ready(self, run: _PipeRun) -> bool:
        for e in run.pipe.elements.values():
            if isinstance(e, MqttSrc):
                if e.queued() == 0:
                    return False
            if isinstance(e, TensorQueryServerSrc):
                if len(e.endpoint.requests) == 0:
                    return False
        return True

    def _finish_frame(self, run: _PipeRun, outputs: Dict[str, StreamBuffer]):
        run.frames += 1
        run.last_outputs = outputs
        for name, buf in outputs.items():
            run.sink_log.setdefault(name, []).append(buf)
        return outputs

    def _run_once(self, run: _PipeRun):
        # host-level elements (mqttsrc pull / query send) are impure, so
        # pipelines containing them run the plan interpreted; pure pipelines
        # step through the cached compiled executable.
        outputs, run.state = run.step_fn(run.params, run.state)
        return self._finish_frame(run, outputs)

    # -- deferred query clients (micro-batched offloading + failover) ------------
    def _begin_deferred(self, run: _PipeRun
                        ) -> Optional[Tuple[_PipeRun, PendingQuery]]:
        """Begin a frame for a pipeline containing query clients: the plan
        pauses at the first client.  Plans whose only impure elements are
        query clients run the walk as ONE jitted segment
        (plan.run_deferred_compiled) — bitwise the interpreted deferral
        without its per-element dispatch cost.  Returns the paused
        frame (NOT yet dispatched — the tick batches a whole round's
        request encodes), or None if the frame completed without pausing."""
        plan = run.pipe.plan
        if plan.deferred_compilable:
            res = plan.run_deferred_compiled(run.params, run.state)
        else:
            res = plan.run_deferred(run.params, run.state)
        if isinstance(res, PendingQuery):
            return run, res
        outputs, run.state = res
        self._finish_frame(run, outputs)
        return None

    @staticmethod
    def _codec_round(pairs, batch_fn) -> List:
        """Shared shape of a batched codec round: group ``(client, buffer)``
        pairs by (codec, TENSORS structure), run ``batch_fn(buffers,
        codec)`` once per group, scatter results back in input order.  The
        key covers the tensors only: the codec batch helpers stack payloads
        and keep each frame's own meta, so differing meta (client ids, pts
        tags) must not split a batchable group."""
        res: List = [None] * len(pairs)
        groups: Dict[Tuple, List[int]] = {}
        for i, (qc, buf) in enumerate(pairs):
            key = (qc.codec, structure_key(buf.tensors))
            groups.setdefault(key, []).append(i)
        for (codec, _), idxs in groups.items():
            for i, out in zip(idxs, batch_fn([pairs[i][1] for i in idxs],
                                             codec)):
                res[i] = out
        return res

    def _encode_requests(self, pairs) -> List[Tuple]:
        """Encode a dispatch round's requests: one batched codec dispatch
        per (codec, structure) group instead of one per request, results
        returned in input order.  Bitwise per-request ``encode`` (payload,
        meta, wire bytes — core/compression.py batch contract)."""
        from ..core import compression as comp
        with tracing.span("sched.encode_round"):
            return self._codec_round(pairs, comp.encode_batch)

    def _dispatch_round(self, fresh: List[Tuple[_PipeRun, PendingQuery]]
                        ) -> List[Tuple[_PipeRun, PendingQuery]]:
        """Ship a round of freshly paused frames: resolve every endpoint
        first (unplaceable frames park before any encode is paid),
        batch-encode the requests per codec group, then push in arrival
        order — server channels stay FIFO, so batching the encodes never
        reorders what the scan serves.  Early flushes still fire the moment
        an endpoint's gather fills."""
        if not fresh:
            return []
        out: List[Tuple[_PipeRun, PendingQuery]] = []
        ready = []
        for run, pq in fresh:
            qc = pq.client
            try:
                ep = self._select_endpoint(qc)
            except BrokerError:
                # keep pq.endpoint (the dead server) — a later successful
                # dispatch of this parked frame is still a failover hop
                self._park(run, pq)
                continue
            ready.append((run, pq, qc, ep))
        encs = self._encode_requests([(qc, pq.request)
                                      for _, pq, qc, _ in ready])
        for (run, pq, qc, ep), (enc, nbytes) in zip(ready, encs):
            if self.delivery is not None:
                if pq.dseq is None:
                    pq.dseq = qc.next_dseq()
                pq.next_retry = self.ticks + \
                    self.delivery.retry_in(pq.retries)
            qc.send_query_wire(enc, nbytes, ep, dseq=pq.dseq)
            if pq.endpoint is not None and pq.endpoint is not ep:
                self.redispatches += 1
                pq.redispatches += 1
            pq.endpoint = ep
            batcher = self._batchers.get(ep.endpoint_id)
            if batcher is None:
                runner = ep.spec.get("inline_runner")
                if runner is not None:
                    runner()
            elif batcher.full():
                batcher.flush()
            out.append((run, pq))
        return out

    def _dispatch_query(self, pq: PendingQuery) -> bool:
        """Ship a paused frame's request to the best-ranked live endpoint
        (encode + client_id tag + push), recording on the PendingQuery where
        the request actually went — if that server dies before answering,
        the drain loop re-dispatches from this record.  Flushes early when
        the endpoint's batch fills.  Endpoints the runtime does not manage
        (manually wired servers) serve inline immediately.  Returns False
        when no live server matches (the caller parks the frame)."""
        qc = pq.client
        try:
            ep = self._select_endpoint(qc)
        except BrokerError:
            # keep pq.endpoint (the dead server) — a later successful
            # dispatch of this parked frame is still a failover hop and
            # must count in `redispatches`
            return False
        if self.delivery is not None:
            # the delivery id is minted ONCE per logical request: parks,
            # failover re-dispatches, and timeout retransmits all reuse it,
            # so receiver dedup makes every duplicate path harmless (§10)
            if pq.dseq is None:
                pq.dseq = qc.next_dseq()
            pq.next_retry = self.ticks + self.delivery.retry_in(pq.retries)
        qc.send_query(pq.request, ep=ep, dseq=pq.dseq)
        if pq.endpoint is not None and pq.endpoint is not ep:
            self.redispatches += 1
            pq.redispatches += 1
        pq.endpoint = ep
        batcher = self._batchers.get(ep.endpoint_id)
        if batcher is None:
            runner = ep.spec.get("inline_runner")
            if runner is not None:
                runner()
        elif batcher.full():
            batcher.flush()
        return True

    def _select_endpoint(self, qc) -> QueryServerEndpoint:
        """Endpoint for one dispatch.  Pre-QoS this is exactly the sticky
        binding (``qc._endpoint()`` — the failover pins depend on its
        exactly-once win-back semantics).  Under QoS with multiple live
        replicas it becomes join-shortest-queue: requests spread over the
        candidates by heartbeat load PLUS this tick's own dispatches
        (``_load_bumps`` — heartbeat load lags by a tick, and without the
        bump every frame of a round would pile onto the same replica).
        Hard preferences (stage, tenant affinity, codec) still dominate;
        the binding itself is untouched, so win-back behavior and the
        recorded failover semantics are identical."""
        ep = qc._endpoint()
        if self.qos is None or qc.binding is None:
            return ep
        cands = [r for r in qc.binding._candidates()
                 if getattr(r.endpoint, "alive", True)]
        if len(cands) <= 1:
            return ep
        prefer = qc.binding.prefer

        def key(r):
            hard = self.broker.rank_key(r, prefer)[:3]
            return (hard, r.load + self._load_bumps.get(r.reg_id, 0),
                    r.reg_id)
        best = min(cands, key=key)
        self._load_bumps[best.reg_id] = \
            self._load_bumps.get(best.reg_id, 0) + 1
        return best.endpoint

    def _park(self, run: _PipeRun, pq: PendingQuery,
              t0: Optional[int] = None):
        """``t0`` is the tick the frame FIRST parked — re-parks preserve it
        so the park deadline measures total time stranded, not time since
        the latest failed retry."""
        self.parked_total += 1
        self._parked.append((run, pq, self.ticks if t0 is None else t0))

    def _retry_parked(self) -> List[Tuple[_PipeRun, PendingQuery]]:
        """Give every parked frame another shot at dispatch (a server may
        have registered or revived since last tick); still-unplaceable
        frames stay parked."""
        parked, self._parked = self._parked, []
        pending = []
        for run, pq, t0 in parked:
            if self._dispatch_query(pq):
                pending.append((run, pq))
            else:
                self._park(run, pq, t0)
        return pending

    def _park_limit(self, qc) -> Optional[int]:
        """Ticks a frame of this client may stay parked: the tighter of the
        runtime-wide ``park_deadline_ticks`` and the client tenant's own
        ``deadline_ticks`` (DESIGN.md §9 — the deadline clock keeps running
        while a request is parked: parked time IS queue time, the tenant
        just never reached a server's queue)."""
        limits = [self.park_deadline_ticks]
        if self.qos is not None:
            tenant = getattr(qc, "tenant", None) or DEFAULT_TENANT
            limits.append(self.qos.spec(tenant).deadline_ticks)
        limits = [m for m in limits if m is not None]
        return min(limits) if limits else None

    def _expire_parked(self):
        """Park deadline (DESIGN.md §6 satellite, §9 tenant interaction): a
        frame parked past its limit stops burning a busy-skip per tick and
        degrades EXPLICITLY — counted in ``parked_expired`` AND on its
        tenant's shed ledger, and answered with a client-visible error
        buffer in the pipeline's sink log; the pipeline is freed to start
        fresh frames next tick."""
        if not self._parked:
            return
        keep = []
        for run, pq, t0 in self._parked:
            limit = self._park_limit(pq.client)
            if limit is not None and self.ticks - t0 >= limit:
                self.parked_expired += 1
                self._account_tenant_shed(pq.client, "deadline",
                                          self.ticks - t0)
                self._expire_query(run, pq, parked_ticks=limit)
            else:
                keep.append((run, pq, t0))
        self._parked = keep

    def _account_tenant_shed(self, qc, reason: str, waited: int = 0):
        """Book a runtime-owned shed (park/deadline expiry — the request
        never reached a server's admission queue) on the tenant's ledger in
        the AdmissionQueue.stats() schema: one admission, one shed, so the
        merged conservation law stays exact."""
        tenant = getattr(qc, "tenant", None) or DEFAULT_TENANT
        led = self._tenant_shed.setdefault(tenant, {
            "admitted": 0, "served": 0, "shed": 0, "queued": 0,
            "in_flight": 0, "shed_reasons": {}, "latency_hist": {}})
        if self.qos is not None:
            led["priority"] = self.qos.spec(tenant).priority
        led["admitted"] += 1
        led["shed"] += 1
        led["shed_reasons"][reason] = led["shed_reasons"].get(reason, 0) + 1

    def _expire_query(self, run: _PipeRun, pq: PendingQuery,
                      parked_ticks: Optional[int] = None):
        """Answer an expired park with an error frame: empty tensors, meta
        naming the operation that never found a server — logged under
        ``<client>.error`` so clients distinguish degradation from silence.
        The frame itself is abandoned (its walk never resumes)."""
        qc = pq.client
        err = StreamBuffer(tensors=(), meta={
            "error": "park-deadline",
            "operation": qc.operation,
            "parked_ticks": (parked_ticks if parked_ticks is not None
                             else self.park_deadline_ticks),
            "redispatches": pq.redispatches,
            "tick": self.ticks})
        run.sink_log.setdefault(f"{qc.name}.error", []).append(err)

    def _shed_query(self, run: _PipeRun, pq: PendingQuery, reason: str):
        """Answer an admission-shed request with an explicit client-visible
        error (zero silent drops — the §9 contract): the server's admission
        layer refused the request (rate budget, queue cap, or deadline
        expiry) and already booked the shed on the tenant ledger; here the
        paused frame learns WHY and is freed."""
        qc = pq.client
        err = StreamBuffer(tensors=(), meta={
            "error": "shed", "reason": reason,
            "operation": qc.operation,
            "tenant": getattr(qc, "tenant", None) or DEFAULT_TENANT,
            "tick": self.ticks})
        run.sink_log.setdefault(f"{qc.name}.error", []).append(err)

    def _drain_queries(self, pending: List[Tuple[_PipeRun, PendingQuery]]):
        """Tick-deadline flush: serve every gathered request, resume the
        paused frames with their answers, and repeat for pipelines that
        pause again at a later query client.

        In-flight failover lives here: a frame whose recorded endpoint died
        before answering re-dispatches its retained request buffer to the
        next-ranked survivor (served on the next flush round) or parks until
        a server registers.  A missing answer from a LIVE endpoint is still
        a hard error — that is a serving bug, not a device death.

        Termination: every round each frame is answered, parked, raised on,
        or re-dispatched to a live endpoint different from its dead one —
        and a chain of re-dispatches is bounded by the number of live
        servers (nothing revives mid-drain; revivals are tick events).

        The round's answers are popped raw and decoded in one batched codec
        dispatch per (codec, structure) group before the resumes — bitwise
        the per-frame decode, minus ``batch × tensors`` eager dispatches."""
        pending = list(pending)
        while pending:
            for batcher in self._batchers.values():
                batcher.flush()
            nxt: List[Tuple[_PipeRun, PendingQuery]] = []
            answered: List[Tuple[_PipeRun, PendingQuery, StreamBuffer]] = []
            for run, pq in pending:
                qc = pq.client
                ep = pq.endpoint
                raw = qc.recv_answer_raw(ep, want=pq.dseq) \
                    if ep is not None else None
                if raw is None:
                    if ep is not None and ep.alive:
                        b = self._batchers.get(ep.endpoint_id)
                        if b is not None:
                            reason = b.admission.pop_notice(qc.client_id)
                            if reason is not None:
                                # admission refused the request (rate /
                                # queue-full / deadline): the shed is on the
                                # tenant ledger, the client gets an explicit
                                # error — never a silent drop, never a
                                # failover (the server is fine)
                                self._shed_query(run, pq, reason)
                                continue
                            if b.in_flight(qc.client_id):
                                # streaming serve mid-generation, or a QoS
                                # serve budget holding the request queued —
                                # not an error, it needs more ticks.  Leave
                                # the drain (bounding this round) and
                                # re-enter next tick.
                                self._inflight.append((run, pq))
                                continue
                        if self.delivery is not None and \
                                pq.dseq is not None:
                            # lossy transport (§10): a missing answer from a
                            # LIVE server means the request or its answer
                            # is lost/delayed in the network — retransmit on
                            # the backoff clock (same delivery id: the
                            # server dedups and replays a committed answer
                            # bitwise), or wait out the current timeout
                            if self.ticks >= pq.next_retry:
                                pq.retries += 1
                                self.retransmits += 1
                                if self._dispatch_query(pq):
                                    nxt.append((run, pq))
                                else:
                                    self._park(run, pq)
                            else:
                                self._inflight.append((run, pq))
                            continue
                        raise BrokerError(
                            f"{qc.name}: no answer from {qc.operation!r}")
                    if self._dispatch_query(pq):
                        nxt.append((run, pq))
                    else:
                        self._park(run, pq)
                    continue
                answered.append((run, pq, raw))
            answers = self._decode_answers(
                [(pq.client, raw) for _, pq, raw in answered])
            for (run, pq, _), answer in zip(answered, answers):
                res = pq.resume(answer)
                if isinstance(res, PendingQuery):
                    if self._dispatch_query(res):
                        nxt.append((run, res))
                    else:
                        self._park(run, res)
                else:
                    outputs, run.state = res
                    self._finish_frame(run, outputs)
            pending = nxt

    def _decode_answers(self, pairs) -> List[StreamBuffer]:
        """Decode a drain round's raw answers, batched per (codec,
        structure) group."""
        from ..core import compression as comp
        with tracing.span("sched.decode_answers"):
            return self._codec_round(pairs, comp.decode_batch)

    # -- burst draining ----------------------------------------------------------
    def _burst_size(self, run: _PipeRun) -> int:
        """Frames to drain this tick: bounded by the runtime burst cap and by
        the shortest queue across the pipeline's subscriber channels."""
        plan = run.pipe.plan
        if self.burst <= 1 or not plan.burstable:
            return 1
        if not plan.all_sources_host_driven:
            # a self-driven source (live camera) mixed in would be
            # fast-forwarded by a burst — stay on the tick cadence
            return 1
        return max(1, min([self.burst] +
                          [s.queued() for s in run.host_srcs]))

    def _deliver_frame(self, run: _PipeRun, frame_outs: Dict[str, StreamBuffer]):
        """Route one frame's outputs: captured host-sink frames replay
        through the element's real apply (encode + channel push + broker
        accounting); app-sink frames land in the log.  Matches _run_once's
        bookkeeping (last_outputs replaced per frame, frames counted)."""
        app_outs = {}
        for name, buf in frame_outs.items():
            elem = run.pipe.elements[name]
            if isinstance(elem, MqttSink):
                elem.apply(run.params.get(name, {}), [buf])
            else:
                app_outs[name] = buf
                run.sink_log.setdefault(name, []).append(buf)
        run.last_outputs = app_outs
        run.frames += 1

    def _run_burst(self, run: _PipeRun, n: int):
        """Drain ``n`` queued frames with one scan-batched dispatch."""
        pulls = {s.name: s.pull_burst(n) for s in run.host_srcs}
        if any(len(v) != n for v in pulls.values()):
            # a channel raced us below n; replay what we got per-frame
            return self._replay_frames(run, pulls)
        try:
            stacked = {k: stack_buffers(v) for k, v in pulls.items()}
        except ValueError:
            # heterogeneous frame structure (e.g. mixed meta after failover):
            # burst stacking needs one treedef — fall back to per-frame
            return self._replay_frames(run, pulls)
        # pub/sub bursts shard only in forced mode: they run off the serving
        # hot path (catch-up drains), so they follow the explicit placement
        # rather than paying their own calibration probes
        mesh = self.mesh if self.shard_mode == "always" else None
        sharded = mesh is not None and \
            run.pipe.plan.shardable_batch(n, run.state, mesh)
        params = run.params
        if sharded:
            if run.mesh_params is None:
                from ..launch.shardings import replicated
                run.mesh_params = jax.device_put(
                    run.params, replicated(mesh, run.params))
            params = run.mesh_params
        step_n = run.pipe.compiled_step_n(hoist_io=True, mesh=mesh)
        outs, run.state = step_n(params, run.state, stacked)
        if sharded:
            # mesh-sharded burst: fetch the stacked outputs in one gather —
            # eager per-frame slicing of SPMD-sharded arrays would pay a
            # cross-device transfer per leaf per frame
            outs = jax.device_get(outs)
        for frame_outs in unstack_buffers(outs, n):
            self._deliver_frame(run, frame_outs)
        run.bursts += 1
        run.burst_frames += n

    def _replay_frames(self, run: _PipeRun, pulls: Dict[str, list]):
        """Per-frame fallback for frames already pulled off the channels.
        The DAG needs every source injected each frame, so only the shortest
        pull count can run; surplus frames are returned to the front of
        their queues (not dropped) for the next tick."""
        n = min(len(v) for v in pulls.values()) if pulls else 0
        for name, frames in pulls.items():
            if len(frames) > n:
                run.pipe.elements[name].unread(frames[n:])
        for i in range(n):
            inputs = {k: v[i] for k, v in pulls.items()}
            outputs, run.state = run.pipe.plan.run(
                run.params, run.state, inputs, hoist_io=True)
            self._deliver_frame(run, outputs)

    def tick(self):
        with tracing.span("sched.tick"):
            self.ticks += 1
            if self.fabric is not None:
                # advance the fault clock first: frames the network held
                # (delay/reorder) from earlier ticks land before anything runs,
                # and this tick's scripted partitions take effect
                self.fabric.step(self.ticks)
            self._ntp_ref.advance(self.tick_ns)
            for dev in self.devices:
                dev.clock.advance(self.tick_ns)
            self._heartbeat_and_lease()
            # tick boundary: pending reconfigurations commit (or drain/roll
            # back) BEFORE any frame of this tick starts — a swap never lands
            # under a frame mid-walk
            self.reconfig.step()
            # elastic serving (DESIGN.md §9): autoscalers read the broker's
            # scaling signal AFTER pending reconfigs settled and request their
            # own add/remove reconfigs — which commit through the same §6
            # lifecycle on later ticks (autoscaling is a reconfig, not a new
            # mechanism)
            for scaler in list(self.autoscalers):
                scaler.step()
            self._load_bumps.clear()
            self._expire_parked()
            # frames parked from earlier ticks go first (a server may be
            # back); their pipelines must not start a second concurrent frame
            pending = self._retry_parked()
            # streams mid-generation re-enter the drain: a live server keeps
            # decoding them (one tick = one token per active stream); a dead
            # one routes them through the same dispatch-or-park failover as
            # any in-flight query (prefill replay on the survivor)
            inflight, self._inflight = self._inflight, []
            pending.extend(inflight)
            busy = {id(run) for run, _ in pending} | \
                   {id(run) for run, _, _ in self._parked}
            fresh: List[Tuple[_PipeRun, PendingQuery]] = []
            for dev in self.devices:
                if not dev.alive:
                    continue  # a dead device runs nothing (chaos harness)
                for run in dev.runs:
                    if run.retired:
                        continue  # decommissioned by a reconfiguration
                    if any(isinstance(e, TensorQueryServerSrc)
                           for e in run.pipe.elements.values()):
                        # servers run batched/inline, driven by clients
                        continue
                    if id(run) in busy:
                        # frame still in flight from a past tick
                        run.skipped += 1
                        continue
                    if not self._ready(run):
                        run.skipped += 1
                        continue
                    if run.pipe.plan.has_query_clients and \
                            self.batching.enabled:
                        paused = self._begin_deferred(run)
                        if paused is not None:
                            fresh.append(paused)
                        continue
                    n = self._burst_size(run)
                    if n > 1:
                        self._run_burst(run, n)
                    else:
                        self._run_once(run)
            # the whole round's request encodes batch into one codec dispatch
            # per group before anything ships (arrival order kept)
            pending.extend(self._dispatch_round(fresh))
            self._drain_queries(pending)

    def run(self, n_ticks: int):
        for _ in range(n_ticks):
            self.tick()
        return self

    # -- stats --------------------------------------------------------------------
    def stats(self) -> Dict[str, Dict]:
        out = {}
        for dev in self.devices:
            for i, run in enumerate(dev.runs):
                key = f"{dev.name}/p{i}"
                # carried_drops: backlogs of elements a reconfiguration
                # removed — their frames left the topology accounted, and
                # conservation (published == consumed + drops + queued)
                # must survive the swap
                drops = run.carried_drops
                for e in run.pipe.elements.values():
                    if isinstance(e, MqttSrc):
                        drops += e.drops   # across every publisher bound
                    elif isinstance(e, MqttSink):
                        drops += e.channel.drops
                out[key] = {"frames": run.frames, "skipped": run.skipped,
                            "bursts": run.bursts,
                            "burst_frames": run.burst_frames,
                            "drops": drops}
        out["broker"] = {"relay_msgs": self.broker.relay_msgs,
                         "relay_bytes": self.broker.relay_bytes,
                         "lease_expiries": self.broker.expiries,
                         "suspicions": self.broker.suspicions,
                         "heals": self.broker.heals}
        out["failover"] = {"redispatches": self.redispatches,
                           "parked_total": self.parked_total,
                           "parked_now": len(self._parked),
                           "inflight_now": len(self._inflight),
                           "parked_expired": self.parked_expired,
                           "orphaned_requests": self.orphaned_requests}
        out["reconfig"] = self.reconfig.stats()
        agg = {"flushes": 0, "batches": 0, "batched_frames": 0,
               "sequential_frames": 0, "sharded_batches": 0,
               "sharded_frames": 0, "fused_batches": 0, "fused_frames": 0,
               "flush_orphans": 0}
        for b in self._batchers.values():
            # streaming batchers report extra keys (prefills, token
            # conservation lanes, ...) — aggregate whatever each reports,
            # with the stateless keys always present
            for k, v in b.stats().items():
                agg[k] = agg.get(k, 0) + v
        out["query_batching"] = {"max_batch": self.batching.max_batch, **agg}
        # unified per-tenant SLO accounting (DESIGN.md §9): live batcher
        # ledgers + retired-replica archive + runtime-owned sheds (park
        # expiries), with exact tick-latency percentiles — and the
        # conservation law asserted over the merged whole
        tenants: Dict[str, Dict] = {}
        for b in self._batchers.values():
            merge_tenant_stats(tenants, b.tenant_stats())
        merge_tenant_stats(tenants, self._tenant_archive)
        merge_tenant_stats(tenants, self._tenant_shed)
        for tid, t in tenants.items():
            t["p50_ticks"] = percentile_from_hist(t["latency_hist"], 0.50)
            t["p99_ticks"] = percentile_from_hist(t["latency_hist"], 0.99)
            assert t["admitted"] == t["served"] + t["shed"] + \
                t["queued"] + t["in_flight"], \
                f"tenant {tid!r} leaks requests: {t}"
        out["tenants"] = tenants
        if self.delivery is not None:
            d = {"retransmits": self.retransmits, "accepted": 0,
                 "deduped": 0, "rejected_corrupt": 0, "replayed": 0,
                 "answer_drops": 0, "client_answer_dups": 0,
                 "client_answer_corrupt": 0, "client_push_drops": 0}
            for b in self._batchers.values():
                if b.guard is not None:
                    for k, v in b.guard.stats().items():
                        d[k] += v
            for dev in self.devices:
                for run in dev.runs:
                    for e in run.pipe.elements.values():
                        if isinstance(e, TensorQueryClient):
                            d["client_answer_dups"] += e.answer_dups
                            d["client_answer_corrupt"] += e.answer_corrupt
                            d["client_push_drops"] += e.push_drops
                        elif getattr(e, "is_query_sink", False):
                            d["answer_drops"] += e.answer_drops
            out["delivery"] = d
        if self.fabric is not None:
            out["netfault"] = self.fabric.stats()
        if self.autoscalers:
            out["autoscale"] = [s.stats() for s in self.autoscalers]
        return out
