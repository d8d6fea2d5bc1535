"""Server-side micro-batching for the query protocol (paper §4.2.2, Fig. 2).

The paper's offloading protocol answers one round-trip per frame.  On edge
silicon the per-dispatch host cost dominates long before the model does
(arXiv 2210.10514) — the same amortize-the-dispatch argument behind the
PR-1 burst engine.  This module batches *across clients*: concurrent
``tensor_query_client`` requests that land on one ``QueryServerEndpoint``
within a scheduler tick are gathered, decoded, stacked along a leading
frame axis, and served by ONE hoisted ``step_n`` scan dispatch through the
server pipeline's compiled plan; the stacked answers are unstacked and
routed back per ``client_id`` through the real serversink ``apply``.

Semantics are preserved relative to sequential serving:

* requests are served in channel FIFO order (= arrival order), and the
  server state threads through the scan in that order — frame ``i`` of a
  batch is exactly the ``i``-th sequential serve;
* per-request codecs survive: decode happens at gather time, encode at
  routing time, both through the unchanged ``compression`` code paths;
* routing meta (``client_id``, ``codec``) is hoisted out of the buffers
  before stacking (meta is static pytree aux — differing client ids would
  otherwise make frames structurally unstackable) and re-attached to each
  answer before the serversink replay.

Fallback rules (automatic, per flush):

* server plans that are not :attr:`ExecutionPlan.query_batchable` (extra
  impure elements, multiple serversrcs) serve sequentially through the
  runtime's interpreted per-request step — the pre-batching behavior;
* requests whose decoded frames differ in pytree structure or tensor
  shapes/dtypes (mixed caps across clients) are split into consecutive
  same-structure groups; a group of one is still served through the
  compiled hoisted path, so every answer leaves through the same execution
  mode and batch composition never changes numerics.

Mesh sharding (DESIGN.md §4): when the runtime is built over a jax mesh
(``Runtime(mesh=...)``), the batcher also holds the mesh-sharded executable
— groups whose frame count tiles the mesh's data axes can serve with one
frame slice per device (``ExecutionPlan.shardable_batch``); every other
group keeps the single-device scan, so the answers are bitwise identical
either way.

Placement is a COST decision, not a faith decision: the dispatch-vs-silicon
gap (arXiv 2210.10514) cuts both ways — on real multi-chip meshes sharding
multiplies serving throughput, but on a host-forged mesh (8 "devices" on 2
cores) the SPMD dispatch overhead exceeds the whole single-device serve.
``shard_mode="auto"`` (default) therefore probes both executables once per
batch size at first use — a handful of extra dispatches, both bitwise
correct — and picks the faster for that size thereafter; ``"always"`` /
``"never"`` force the choice (tests force ``"always"`` to pin the sharded
path's semantics regardless of host speed).  Placement stays transparent to
elements and clients, NNStreamer-style: only latency changes.

Fused wire path (DESIGN.md §5): the batcher does NOT decode requests at
gather time.  Wire-form requests group by **(codec, wire
structure)** — consecutive, same as mixed-structure grouping; a codec
determines its wire pytree, so mixed-codec batches split per codec — and
each group serves through the codec-fused executable
(``plan.compiled_serve_batch(codec=...)``): per-request decode, stacked
scan, and per-frame answer re-encode all inside ONE jit.  Routing meta is
hoisted on the host exactly as before; the stacked wire answers are fetched
with ONE device_get, split as numpy, and pushed through the serversink's
wire-level route (``push_wire`` — byte accounting from static shapes, no
sync); deferred sparse-truncation counts ride out of the jit as one array
and sync once per flush.  Groups a mesh may take, and ``none``-codec groups
(nothing to fuse), serve through the dense path instead (one batched host
decode → placement probe → sharded or single-device serve → per-answer
encode), so the sharding guarantees are untouched.

Admission layer (DESIGN.md §9): every batcher's queueing now runs through
one shared :class:`~repro.core.admission.AdmissionQueue` — requests pop
off the endpoint Channel into per-tenant session queues at flush time, and
the dequeue is the scheduling function (pure global FIFO when QoS is off —
bitwise the old channel ``pop_n`` — weighted-fair across priority classes
with EDF within a class when a :class:`~repro.core.admission.QoSConfig` is
installed).  Scheduling changes ordering and admission, never answers:
whatever ``take`` returns flows through the exact serve paths documented
above, so the parity pins are out of scope by construction.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from .admission import AdmissionQueue, QoSConfig
from .buffers import StreamBuffer, structure_key, unstack_buffers
from .query import QueryServerEndpoint
from . import compression as comp
from . import netfault, tracing

__all__ = ["BatchingPolicy", "QueryBatcher", "StreamingQueryBatcher",
           "StagedStreamingBatcher", "StageQueryBatcher",
           "DEFAULT_QUERY_BATCH"]

DEFAULT_QUERY_BATCH = 8

#: buffer meta keys that carry per-request routing, not payload semantics —
#: hoisted out before stacking and re-attached to the routed answer
#: (``tenant_id`` rides along so admission can book the request before the
#: hoist and the answer still names its tenant; ``dseq`` — the §10 delivery
#: id — varies per frame, so leaving it in meta would split every stacking
#: group down to singletons)
_ROUTING_KEYS = ("client_id", "codec", "tenant_id", "dseq")


def _nbytes(tree) -> int:
    """Bytes of a pytree's array leaves, from their static shapes."""
    return sum(int(leaf.nbytes) for leaf in jax.tree_util.tree_leaves(tree))


@dataclass(frozen=True)
class BatchingPolicy:
    """How a runtime gathers and flushes query requests.

    * ``max_batch <= 0`` disables batching entirely: clients keep the
      legacy synchronous round-trip inside ``tensor_query_client.apply``
      (one interpreted server step per request).
    * ``max_batch >= 1`` turns on queue-gather-flush: the scheduler defers
      query clients, gathers their requests, and flushes every endpoint at
      the tick deadline — or as soon as ``flush_on_full`` sees ``max_batch``
      requests pending.  Each flush serves in chunks of ``max_batch``
      through the compiled hoisted plan.
    """

    max_batch: int = DEFAULT_QUERY_BATCH
    flush_on_full: bool = True

    @classmethod
    def of(cls, value) -> "BatchingPolicy":
        if isinstance(value, cls):
            return value
        return cls(max_batch=int(value))

    @property
    def enabled(self) -> bool:
        return self.max_batch >= 1


class QueryBatcher:
    """Gather-decode-stack-dispatch-route loop for one server endpoint.

    ``run`` is the scheduler's pipeline-run record for the server pipeline
    (duck-typed: ``pipe``, ``params``, ``state``, ``frames``, ``bursts``,
    ``burst_frames``, ``last_outputs``, ``sink_log``); ``inline_step`` is a
    zero-arg callable performing one legacy interpreted server step
    (serversrc pull → … → serversink route) — the sequential fallback.
    """

    def __init__(self, endpoint: QueryServerEndpoint, run: Any,
                 policy: BatchingPolicy,
                 inline_step: Optional[Callable[[], Any]] = None,
                 mesh=None, shard_mode: str = "auto",
                 on_orphans: Optional[Callable[[int], None]] = None,
                 *, qos: Optional[QoSConfig] = None,
                 clock: Optional[Callable[[], int]] = None):
        if shard_mode not in ("auto", "always", "never"):
            raise ValueError(f"shard_mode {shard_mode!r} not in "
                             f"('auto', 'always', 'never')")
        self.endpoint = endpoint
        self.run = run
        self.policy = policy
        self.inline_step = inline_step
        #: THE queueing/shedding/accounting core (module docstring): with
        #: qos=None this is an exact global-FIFO pass-through and the only
        #: change vs popping the channel directly is the per-tenant ledger
        self.admission = AdmissionQueue(qos=qos, clock=clock)
        #: called with the number of popped-but-unserved requests a flush
        #: abandons when its endpoint dies mid-flush (the runtime adds them
        #: to its orphan ledger; the paused frames re-dispatch from their
        #: PendingQuery records exactly like channel-purged orphans)
        self.on_orphans = on_orphans
        #: delivery guard (DESIGN.md §10), installed by the runtime when a
        #: DeliveryPolicy is on: every request this batcher ingests passes
        #: CRC + dedup triage first.  None (the default) is the pre-§10
        #: wire, bit for bit.
        self.guard = None
        #: jax Mesh to lay batches out on (None = single-device serving)
        self.mesh = mesh
        #: sharded-executable placement policy (module docstring)
        self.shard_mode = shard_mode
        #: batch size -> "sharded" | "single", decided by probe in auto mode
        self.placements: Dict[int, str] = {}
        #: mesh-placed (replicated) copy of the server params, built lazily
        #: at first sharded use: re-broadcasting params at every flush costs
        #: more than the serve itself, while handing mesh-replicated arrays
        #: to the single-device executable costs a gather per call — so each
        #: executable gets params in ITS OWN layout
        self._mesh_params = None
        # stats for Runtime.stats() / the batching benchmark
        self.flushes = 0
        self.batches = 0
        self.batched_frames = 0
        self.sequential_frames = 0
        self.sharded_batches = 0
        self.sharded_frames = 0
        self.fused_batches = 0
        self.fused_frames = 0
        self.orphaned = 0
        #: bytes of the fused serves' answers fetched to the host
        self.wire_d2h_bytes = 0

    # -- public API ------------------------------------------------------------
    def in_flight(self, client_id: int) -> bool:
        """Whether ``client_id`` has work the scheduler must keep waiting
        on at this server.  Stateless batching answers every DEQUEUED
        request within its flush, but a QoS serve budget may hold the
        request queued across ticks — still in flight, not lost; the
        streaming subclass additionally tracks mid-generation streams."""
        return self.admission.queued_for(client_id) > 0

    def pending(self) -> int:
        return len(self.endpoint.requests) + len(self.admission)

    def full(self) -> bool:
        # backpressure floor, independent of policy: the request Channel is
        # bounded (leaky-drop), so once the gather reaches its capacity we
        # MUST serve — one more send would silently drop a client's request
        # and its frame would then die with 'no answer' at the deadline
        if len(self.endpoint.requests) >= self.endpoint.requests.capacity:
            return True
        return self.policy.flush_on_full and \
            self.pending() >= max(1, self.policy.max_batch)

    def flush(self) -> int:
        """Serve every pending request; returns the number served.

        Also wired as the endpoint's ``inline_runner`` so edge clients
        (``EdgeQueryClient.infer``) and direct ``pipe.step`` round-trips
        keep their serve-before-return contract unchanged.
        """
        if not self.endpoint.alive:
            # dead server: never serve — requests still on the endpoint
            # channel are orphans the scheduler re-dispatches from its own
            # PendingQuery records (the runtime purges the channel on the
            # down event); requests already ADMITTED here close on this
            # queue's ledger as server-died sheds (their re-dispatch is a
            # fresh admission at the survivor, so conservation holds both
            # per queue and summed)
            self._shed_dead()
            return 0
        adm = self.admission
        served = 0
        plan = self.run.pipe.plan
        # max_batch == 1 is still batching-enabled: a group of one serves
        # through the compiled hoisted path (the module contract above), so
        # turning the batch size down never silently changes execution mode
        batchable = self.policy.enabled and plan.query_batchable
        # liveness is re-checked before EVERY group, not only at entry: a
        # mark_down can land mid-flush (the serving chain itself announces
        # a death), and frames this flush already dequeued are invisible to
        # the down event's purge — a corpse must not keep serving them, so
        # the remainder goes to the orphan ledger and re-dispatches like
        # any channel-purged orphan
        while self.endpoint.alive:
            # re-ingest every round: serving can land new requests on the
            # channel (inline chains), exactly as the old per-iteration
            # channel check saw them
            self._ingest()
            adm.expire()
            if not len(adm):
                break
            if not batchable:
                recs = adm.take(1)
                if not recs:
                    break               # serve budget spent this tick
                self._serve_sequential(recs[0])
                served += 1
                continue
            recs = adm.take(self.policy.max_batch)
            if not recs:
                break                   # serve budget spent this tick
            raws = [r.raw for r in recs]
            idx = 0
            for pairs, codec in self._group_wire(raws):
                if not self.endpoint.alive:
                    self._shed_flush_remainder(recs[idx:])
                    break
                if codec.partition(":")[0] == "none" or \
                        self._mesh_may_take(len(pairs)):
                    # nothing to fuse for "none" (decode/encode are
                    # identity — the fused executable would only add a
                    # per-flush answer fetch), and mesh placement needs
                    # dense frames (probe + sharded executable): both serve
                    # dense with lazy answers — but the request decode
                    # still batches into one stacked dispatch
                    decoded = comp.decode_batch(
                        [clean for clean, _ in pairs], codec)
                    self._serve_batched(
                        [(dec, routing) for dec, (_, routing)
                         in zip(decoded, pairs)])
                else:
                    self._serve_batched_wire(pairs, codec)
                for rec in recs[idx:idx + len(pairs)]:
                    adm.mark_served(rec)
                idx += len(pairs)
                served += len(pairs)
        if served:
            self.flushes += 1
        return served

    def _ingest(self):
        """Drain the endpoint channel into admission — through the delivery
        guard when the runtime installed one (DESIGN.md §10).  Guard triage:
        corrupt frames are rejected and counted (never silently consumed),
        duplicates dedup against the LRU window and re-fire the committed
        answer's bitwise replay (a retransmit means the client never saw
        it), and accepted frames shed their wire checksum — it
        authenticated THIS hop; the answer gets its own — before admitting
        exactly as the guard-less path would."""
        ch = self.endpoint.requests
        if self.guard is None:
            self.admission.ingest_channel(ch)
            return
        while True:
            raw = ch.pop()
            if raw is None:
                return
            verdict = self.guard.check(raw, ch)
            if verdict == "ok":
                meta = raw.meta or {}
                if "crc" in meta:
                    # the wire frame owns its meta dict (every send path
                    # builds it fresh), so shed the checksum in place —
                    # a with_ copy per accepted request is pure overhead
                    del meta["crc"]
                self.admission.ingest(raw)
            elif verdict == "dup":
                self.guard.replay_answer((raw.meta or {}).get("dseq"))
            # "corrupt": counted by the guard; the frame dies here

    def _forget_delivery(self, rec):
        """Evict a shed-unserved request's delivery id from the dedup
        window: its failover re-dispatch reuses the id (idempotence key),
        and a window that still remembers it would dedup the retry into a
        void — a silent loss the §10 conservation law forbids."""
        if self.guard is None or rec is None:
            return
        raw = getattr(rec, "raw", None)
        if raw is not None:
            self.guard.forget((raw.meta or {}).get("dseq"))

    def _orphan(self, n: int):
        """Account requests a dying flush popped but never served."""
        if n <= 0:
            return
        self.orphaned += n
        if self.on_orphans is not None:
            self.on_orphans(n)

    def _shed_flush_remainder(self, recs):
        """Close the dequeued-but-unserved tail of a dying flush: shed on
        the tenant ledger (reason ``server-died``, no client notice — the
        scheduler re-dispatches these from their PendingQuery records and
        the client gets a real answer elsewhere) + the orphan ledger."""
        for rec in recs:
            self.admission.mark_shed(rec, "server-died", notify=False)
            self._forget_delivery(rec)
        self._orphan(len(recs))

    def _shed_dead(self) -> int:
        """Endpoint is dead: everything still queued in admission sheds
        (``server-died``) and joins the orphan ledger for re-dispatch."""
        n = self.admission.shed_queued("server-died", on_shed=self._forget_delivery)
        self._orphan(n)
        return n

    def on_reconfig(self):
        """The served pipeline was hot-swapped under this batcher: calibrated
        placements and mesh-placed params belong to the OLD plan/params —
        drop them so the next flush re-probes and re-places against the new
        epoch (the plan itself is always read through ``run.pipe``)."""
        self.placements.clear()
        self._mesh_params = None

    # -- gather & grouping -----------------------------------------------------
    def _decode(self, raw: StreamBuffer) -> Tuple[StreamBuffer, Dict]:
        """Host-level decode + routing-meta hoist: returns the clean frame
        (payload meta only) and the routing dict to re-attach on the answer.
        Routing is read off the WIRE buffer — decode strips the wire-form
        ``codec`` claim from the decoded frame, but the client's codec
        preference must still route its answer's re-encode."""
        codec = raw.meta.get("codec", "none")
        buf = comp.decode(raw, codec)
        routing = {k: raw.meta[k] for k in _ROUTING_KEYS if k in raw.meta}
        clean = buf.with_(meta={k: v for k, v in buf.meta.items()
                                if k not in _ROUTING_KEYS})
        return clean, routing

    def _group_wire(self, raws: List[StreamBuffer]):
        """Split raw requests into consecutive same-(codec, WIRE structure)
        runs, preserving arrival order (so server state still threads
        through in FIFO order even when client caps are mixed) — no host
        decode.  The codec is part of the key because it is the fused
        executable's static trace parameter (and two codecs' wire pytrees
        differ anyway), so mixed-codec batches split exactly like
        mixed-structure batches.  Yields ``([(clean_wire, routing), ...],
        codec)`` — the hoisted pairs the key was built from, so serving
        never re-hoists."""
        groups: List[Tuple[List[Tuple[StreamBuffer, Dict]], str]] = []
        last_key = None
        for raw in raws:
            codec = raw.meta.get("codec", "none")
            pair = self._hoist_wire(raw)
            key = (codec, structure_key(pair[0]))
            if groups and key == last_key:
                groups[-1][0].append(pair)
            else:
                groups.append(([pair], codec))
                last_key = key
        return groups

    def _hoist_wire(self, raw: StreamBuffer) -> Tuple[StreamBuffer, Dict]:
        """Routing hoist for a WIRE request: strip routing meta (as always)
        plus the wire-form meta — ``codec`` becomes the group's static
        trace parameter and ``sparse_dropped`` differs per frame, either
        would make same-shaped requests structurally unstackable."""
        routing = {k: raw.meta[k] for k in _ROUTING_KEYS if k in raw.meta}
        keep = {k: v for k, v in raw.meta.items()
                if k not in _ROUTING_KEYS and k not in comp._WIRE_META}
        return raw.with_(meta=keep), routing

    def _mesh_may_take(self, n: int) -> bool:
        """Whether mesh placement might claim this group — those groups
        need host-decoded dense frames (calibration probe + sharded
        executable input), so they serve dense.  A batch size whose
        calibrated placement already said "single" is NOT claimed:
        forfeiting codec fusion there would re-pay the per-frame answer
        encode for nothing (only the first, probe-carrying flush of a size
        serves dense in auto mode)."""
        if self.mesh is None or self.shard_mode == "never":
            return False
        if not self.run.pipe.plan.shardable_batch(n, self.run.state,
                                                  self.mesh):
            return False
        return self.shard_mode == "always" or \
            self.placements.get(n) != "single"

    # -- serving ---------------------------------------------------------------
    def _serve_sequential(self, rec=None):
        """Legacy one-request interpreted step (also the fallback for server
        plans the hoisted scan cannot express).  ``rec`` is the admission
        record whose raw request this step serves: it re-enters the HEAD of
        the request channel (``appendleft`` — no double byte/msg
        accounting) so the interpreted serversrc pull sees exactly the
        pre-admission world, then closes served on the ledger."""
        if self.inline_step is None:
            raise RuntimeError("sequential fallback needs an inline_step")
        if rec is not None:
            self.endpoint.requests.q.appendleft(rec.raw)
        self.inline_step()
        self.sequential_frames += 1
        if rec is not None:
            self.admission.mark_served(rec)

    def _pick_placement(self, n: int, frames_in: Tuple) -> bool:
        """Whether THIS group serves through the mesh-sharded executable.
        Groups the mesh cannot take (non-tiling size, stateful plan) always
        serve single-device; shardable groups follow ``shard_mode`` —
        forced, or probed once per batch size in auto mode."""
        plan = self.run.pipe.plan
        if self.mesh is None or \
                not plan.shardable_batch(n, self.run.state, self.mesh):
            return False
        if self.shard_mode != "auto":
            return self.shard_mode == "always"
        dec = self.placements.get(n)
        if dec is None:
            dec = self._calibrate(n, frames_in)
        return dec == "sharded"

    def _mesh_placed_params(self):
        """Replicated-on-the-mesh params (the launch/shardings.py spec for
        serving params), placed once and reused by every sharded serve."""
        if self._mesh_params is None:
            from ..launch.shardings import replicated
            self._mesh_params = jax.device_put(
                self.run.params, replicated(self.mesh, self.run.params))
        return self._mesh_params

    def _calibrate(self, n: int, frames_in: Tuple) -> str:
        """Probe both executables on this very batch and keep the faster
        for this size.  Both are bitwise-correct and the plan is stateless
        (shardable), so the probe serves are just discarded warm-ups —
        placement costs a handful of dispatches, once."""
        import time as _time
        run = self.run
        best = {}
        for label, mesh, params in (
                ("sharded", self.mesh, self._mesh_placed_params()),
                ("single", None, run.params)):
            fn = run.pipe.plan.compiled_serve_batch(mesh=mesh)
            fn(params, run.state, frames_in)       # compile + warm, untimed
            ts = []
            for _ in range(3):
                t0 = _time.perf_counter()
                # block: the single-device jit returns lazy arrays while the
                # sharded wrapper device_gets internally — timing dispatch
                # only would structurally bias the probe toward "single"
                jax.block_until_ready(fn(params, run.state, frames_in))
                ts.append(_time.perf_counter() - t0)
            best[label] = min(ts)
        dec = "sharded" if best["sharded"] <= best["single"] else "single"
        self.placements[n] = dec
        return dec

    def _serve_batched(self, group: List[Tuple[StreamBuffer, Dict]]):
        """One compiled dispatch over the whole group: stack, hoisted scan
        (serversrc frames injected, serversink answers captured), and
        per-frame split all happen INSIDE the jitted serve_batch, so the
        host pays a single dispatch per batch; the captured answers then
        replay through the real serversink apply with routing restored.
        Placement (mesh-sharded vs single-device executable) is decided by
        :meth:`_pick_placement`."""
        run = self.run
        plan = run.pipe.plan
        n = len(group)
        src = plan.query_sources[0].name
        frames_in = tuple({src: clean} for clean, _ in group)
        use_mesh = self._pick_placement(n, frames_in)
        serve = plan.compiled_serve_batch(mesh=self.mesh if use_mesh
                                          else None)
        params = self._mesh_placed_params() if use_mesh else run.params
        frames_out, run.state = serve(params, run.state, frames_in)
        for (_, routing), frame in zip(group, frames_out):
            self._route(frame, routing)
            run.frames += 1
        self.batched_frames += n
        if use_mesh:
            self.sharded_batches += 1
            self.sharded_frames += n
        if n > 1:
            self.batches += 1
            run.bursts += 1
            run.burst_frames += n

    def _serve_batched_wire(self, pairs: List[Tuple[StreamBuffer, Dict]],
                            codec: str):
        """One codec-fused dispatch over a same-(codec, structure) group of
        hoisted ``(clean_wire, routing)`` pairs: the requests go into the
        jit in WIRE form; decode, stacked scan and answer re-encode all
        happen inside ``serve_batch_wire``; the stacked wire answers come
        back in ONE device fetch (plus the deferred sparse-truncation
        counts — one sync per flush, not per tensor) and are routed as
        numpy frames through the serversink's wire-level push, with routing
        meta and the loss signal restored host-side.  Byte accounting is
        computed from static payload shapes."""
        run = self.run
        plan = run.pipe.plan
        n = len(pairs)
        src = plan.query_sources[0].name
        frames_in = tuple({src: clean} for clean, _ in pairs)
        with tracing.span("wire.serve") as serve_span:
            serve_span.set(n=n, codec=codec)
            serve = plan.compiled_serve_batch(codec=codec)
            with tracing.span("wire.dispatch"):
                (wire_outs, app_outs, dropped), run.state = serve(
                    run.params, run.state, frames_in)
            with tracing.span("wire.fetch") as sp:
                wire_outs, app_outs, dropped = jax.device_get(
                    (wire_outs, app_outs, dropped))
                nbytes = _nbytes((wire_outs, app_outs, dropped))
                sp.set(bytes=nbytes)
            self.wire_d2h_bytes += nbytes
            with tracing.span("wire.route"):
                self._route_wire(pairs, codec, wire_outs, app_outs, dropped)
        self.batched_frames += n
        self.fused_batches += 1
        self.fused_frames += n
        if n > 1:
            self.batches += 1
            run.bursts += 1
            run.burst_frames += n

    def _route_wire(self, pairs, codec: str, wire_outs, app_outs, dropped):
        """Route a fused serve's fetched answers, one frame per request."""
        run = self.run
        n = len(pairs)
        base_codec = codec.partition(":")[0]
        wire_frames = {name: unstack_buffers(b, n)
                       for name, b in wire_outs.items()}
        app_frames = {name: unstack_buffers(b, n)
                      for name, b in app_outs.items()}
        for i, (_, routing) in enumerate(pairs):
            for name, frames in wire_frames.items():
                wb = frames[i]
                # per-sink deferred loss accounting: each sink's answer
                # carries ITS OWN truncation count, as the dense path's
                # per-answer encode would stamp it
                frame_dropped = (comp.account_sparse_dropped(
                    dropped[name][:, i]) if name in dropped else 0)
                # meta layering matches the dense path: scan answer meta,
                # then routing, then the wire-form claims encode would stamp
                meta = {**wb.meta, **routing, "codec": base_codec}
                if frame_dropped:
                    meta["sparse_dropped"] = frame_dropped
                wb = wb.with_(meta=meta)
                run.pipe.elements[name].push_wire(
                    wb, comp.wire_nbytes(wb), routing["client_id"])
            outs_i = {name: frames[i] for name, frames in app_frames.items()}
            for name, buf in outs_i.items():
                run.sink_log.setdefault(name, []).append(buf)
            run.last_outputs = outs_i
            run.frames += 1

    def _route(self, frame_outs: Dict[str, StreamBuffer], routing: Dict):
        """Deliver one frame's captured outputs: serversink answers replay
        through the element's real apply (encode + client-channel push) with
        the hoisted routing meta restored; any app sinks land in the server
        run's sink log, matching the sequential bookkeeping."""
        run = self.run
        app_outs = {}
        for name, buf in frame_outs.items():
            elem = run.pipe.elements[name]
            if getattr(elem, "is_query_sink", False):
                answer = buf.with_(meta={**buf.meta, **routing})
                elem.apply(run.params.get(name, {}), [answer])
            else:
                app_outs[name] = buf
                run.sink_log.setdefault(name, []).append(buf)
        run.last_outputs = app_outs

    def stats(self) -> Dict[str, int]:
        """Unified base schema every batcher shares (subclasses EXTEND this
        dict, never replace keys): flush/dispatch counters plus the
        admission totals whose conservation law ``admitted == served +
        shed + queued + in_flight`` Runtime.stats() asserts."""
        adm = self.admission.stats()
        return {"flushes": self.flushes, "batches": self.batches,
                "batched_frames": self.batched_frames,
                "sequential_frames": self.sequential_frames,
                "sharded_batches": self.sharded_batches,
                "sharded_frames": self.sharded_frames,
                "fused_batches": self.fused_batches,
                "fused_frames": self.fused_frames,
                "flush_orphans": self.orphaned,
                "wire_d2h_bytes": self.wire_d2h_bytes,
                "admitted_requests": sum(t["admitted"] for t in
                                         adm.values()),
                "served_requests": sum(t["served"] for t in adm.values()),
                "shed_requests": sum(t["shed"] for t in adm.values()),
                "queued_requests": sum(t["queued"] for t in adm.values())}

    def tenant_stats(self) -> Dict[str, Dict]:
        """Per-tenant ledgers for ``Runtime.stats()["tenants"]``."""
        return self.admission.stats()


class StreamingQueryBatcher(QueryBatcher):
    """Continuous-batching request lifecycle for a ``stream_serving`` server
    (DESIGN.md §7): prefill on arrival → N decode ticks in a slot of the
    plan-state decode batch → one answer when the budget is spent.

    Per flush (called every scheduler drain round):

    1. **admit** — pop every pending wire request, decode it (per-request
       codec, routing hoisted exactly like the stateless path), run the
       serve element's host prefill (first token + b=1 cache), and queue
       the stream for a slot.  ``gen <= 1`` answers immediately.
    2. **decode tick** — at most ONCE per scheduler tick (``tick_source``
       guard; the drain loop flushes many times per tick): assign free
       slots to waiting streams lowest-slot-first, write the joiners'
       caches into their slot rows on the device, assemble the admit
       lanes, and run ONE ``compiled_serve_tick`` dispatch over the whole
       slot table.  Joins and leaves are data (admit mask / finished lane),
       never a retrace.
    3. **finish** — slots whose ``finished`` lane fired deliver their
       accumulated tokens as one answer through the real serversink apply
       (per-client codec encode + channel route), and the slot frees.

    Conservation (pinned by the soak): ``tokens_generated ==
    tokens_delivered + tokens_dropped + inflight_tokens()`` — a dead
    endpoint aborts every live stream into ``tokens_dropped`` (their
    PendingQuery records re-dispatch with PREFILL REPLAY on a survivor,
    regenerating from scratch — greedy decode makes the re-generation
    bitwise, pinned by the chaos test)."""

    def __init__(self, *args, tick_source: Optional[Callable[[], int]] = None,
                 **kwargs):
        super().__init__(*args, **kwargs)
        if tick_source is None:
            # standalone batcher (no scheduler): a monotonic counter, so
            # EVERY flush is its own decode tick.  A constant default would
            # satisfy the once-per-tick guard exactly once ever and then
            # freeze decode forever (regression-pinned).
            counter = itertools.count()
            tick_source = lambda: next(counter)          # noqa: E731
        self.tick_source = tick_source
        self._slots: Dict[int, Dict] = {}       # slot -> stream record
        self._waiting: List[Dict] = []          # FIFO, no free slot yet
        self._replay: List[Dict] = []           # re-prefill on the next admit
        #: client_id -> FIFO of live stream records.  Keyed per REQUEST
        #: (a list per client), not one record per client: a client may
        #: pipeline a second prompt while its first stream is in flight,
        #: and overwriting would orphan the first record — undercounting
        #: inflight_tokens(), silently breaking conservation, and hiding
        #: the orphan from _abort_streams (regression-pinned).
        self._by_client: Dict[int, List[Dict]] = {}
        self._last_decode_tick: Optional[int] = None
        self.prefills = 0
        self.replays = 0
        self.decode_ticks = 0
        self.tokens_generated = 0
        self.tokens_delivered = 0
        self.tokens_dropped = 0
        self.streams_started = 0
        self.streams_finished = 0
        #: decode ticks that took at least one waiting stream into a slot
        self.join_ticks = 0
        #: occupied slots summed over decode ticks
        self.slot_ticks = 0
        #: bytes fetched to the host on join ticks (none: the joiners'
        #: caches stay on the device)
        self.admit_d2h_bytes = 0
        #: bytes shipped to the device on join ticks: the admit lanes, and
        #: any joiner cache handed over host-resident
        self.admit_h2d_bytes = 0
        #: slot rows the joiners' caches were written into on the device
        self.admit_rows = 0
        #: bytes of the joiners' caches written into their slot rows
        self.admit_write_bytes = 0
        #: bytes the decode ticks' cache commits wrote: per tick, one
        #: position row of every attention leaf of every slot plus the
        #: recurrent state (``ModelServeElement.commit_bytes``)
        self.commit_bytes = 0

    # -- introspection ---------------------------------------------------------
    def in_flight(self, client_id: int) -> bool:
        return bool(self._by_client.get(client_id)) or \
            super().in_flight(client_id)

    def inflight_tokens(self) -> int:
        return sum(len(rec["tokens"]) for recs in self._by_client.values()
                   for rec in recs)

    def active_streams(self) -> int:
        return sum(len(recs) for recs in self._by_client.values())

    def _track(self, rec: Dict):
        self._by_client.setdefault(rec["routing"]["client_id"],
                                   []).append(rec)

    def _untrack(self, rec: Dict):
        """Drop ONE record by identity (two streams of the same prompt from
        one client compare equal — ``list.remove`` would drop the wrong
        one)."""
        cid = rec["routing"]["client_id"]
        recs = self._by_client.get(cid)
        if not recs:
            return
        for i, r in enumerate(recs):
            if r is rec:
                del recs[i]
                break
        if not recs:
            del self._by_client[cid]

    def _serve_elem(self):
        plan = self.run.pipe.plan
        for op in plan.ops:
            if getattr(op.elem, "is_stream_serve", False):
                return op.elem
        raise RuntimeError("StreamingQueryBatcher on a non-streaming plan")

    # -- lifecycle -------------------------------------------------------------
    def flush(self) -> int:
        if not self.endpoint.alive:
            self._abort_streams()
            return 0
        served = self._admit()
        tick = self.tick_source()
        if tick != self._last_decode_tick and self._has_decode_work():
            self._last_decode_tick = tick
            served += self._decode_tick()
        if served:
            self.flushes += 1
        return served

    def _has_decode_work(self) -> bool:
        return bool(self._slots or self._waiting)

    def _admit(self) -> int:
        """Ingest + prefill every admitted request; short generations
        answer here, the rest join the waiting pool (slot assignment
        happens at the next decode tick — arrival order when QoS is off,
        ``(priority, deadline, arrival)`` order when it is on: slot
        admission honors tenant priority, and since a slotted stream is
        never evicted before its ``finished`` lane fires, preemption only
        ever happens at generation boundaries)."""
        with tracing.span("lm.admit"):
            finished = 0
            elem = self._serve_elem()
            params = self.run.params.get(elem.name, {})
            if self._replay:
                # hot-swap replay: streams orphaned by a committed reconfig
                # re-prefill on the NEW epoch's params (greedy decode — the
                # regeneration is bitwise what a fresh build answers)
                replays, self._replay = self._replay, []
                for rec in replays:
                    req = rec["trace"]
                    req.mark("prefill")
                    with req:
                        tok, cache = elem.host_prefill(params, rec["prompt"])
                    req.mark("first_token")
                    self.prefills += 1
                    self.tokens_generated += 1
                    rec["tokens"] = [tok]
                    rec["remaining"] = max(0, rec["gen"] - 1)
                    rec["cache"] = cache
                    if rec["remaining"] <= 0:
                        self._finish(rec)
                        finished += 1
                    else:
                        self._waiting.append(rec)
            adm = self.admission
            while self.endpoint.alive:
                self._ingest()
                adm.expire()
                recs = adm.take(1)
                if not recs:
                    break
                arec = recs[0]
                req = tracing.request(arec.arrived_ns)
                clean, routing = self._decode(arec.raw)
                gen = int(clean.meta.get("gen", 1))
                req.mark("prefill")
                with req:
                    tok, cache = elem.host_prefill(params, clean.tensors[0])
                req.mark("first_token")
                self.prefills += 1
                self.streams_started += 1
                self.tokens_generated += 1
                rec = {"routing": routing, "tokens": [tok], "prompt":
                       clean.tensors[0], "gen": gen,
                       "remaining": max(0, gen - 1), "cache": cache,
                       "adm": arec, "trace": req}
                self._track(rec)
                if rec["remaining"] <= 0:
                    self._finish(rec)
                    finished += 1
                else:
                    self._waiting.append(rec)
            return finished

    def _next_waiting(self) -> Dict:
        """The waiting stream the next free slot goes to: plain FIFO when
        QoS is off (the pre-QoS semantics, bit for bit), else the best
        ``(priority, deadline, arrival)`` key — tenant priority decides
        slot admission, never slot eviction."""
        if not self.admission.enabled or len(self._waiting) <= 1:
            return self._waiting.pop(0)
        best = min(range(len(self._waiting)),
                   key=lambda i: self._waiting[i]["adm"].order_key()
                   if "adm" in self._waiting[i] else (-1, 0.0, -1))
        return self._waiting.pop(best)

    def _decode_tick(self) -> int:
        """ONE stateful dispatch over the whole slot table: waiting streams
        join under the admit mask, every active slot emits a token, spent
        slots leave — all inside the same jitted program."""
        with tracing.span("lm.decode") as sp:
            run = self.run
            plan = run.pipe.plan
            elem = self._serve_elem()
            free = sorted(s for s in range(elem.slots)
                          if s not in self._slots)
            admits = []
            while free and self._waiting:
                rec = self._next_waiting()
                rec["trace"].mark("join")
                slot = free.pop(0)
                admits.append((slot, rec["tokens"][-1], rec["remaining"],
                               rec["cache"]))
                rec["cache"] = None     # lives in plan state from here on
                self._slots[slot] = rec
            if not self._slots:
                return 0
            sp.set(joins=len(admits), active=len(self._slots),
                   slots=elem.slots, commit_bytes=elem.commit_bytes)
            self.slot_ticks += len(self._slots)
            self.commit_bytes += elem.commit_bytes
            src = plan.query_sources[0].name
            sink = plan.query_sinks[0].name
            serve = plan.compiled_serve_tick(run.state)
            bundle = elem.build_admit(admits)
            if admits:
                rows = [(slot, cache) for slot, *_, cache in admits]
                with tracing.span("lm.admit.write") as wsp:
                    wsp.set(n=len(rows))
                    run.state = {**run.state, elem.name: elem.write_rows(
                        run.state[elem.name], rows)}
                # counted from static shapes: the joiners' caches written
                # into their slot rows on the device (nothing fetched), and
                # what the host ships: the lanes and any host-resident cache
                caches = [cache for _, cache in rows]
                written = _nbytes(caches)
                shipped = _nbytes(bundle.tensors) + sum(
                    int(leaf.nbytes)
                    for leaf in jax.tree_util.tree_leaves(caches)
                    if isinstance(leaf, np.ndarray))
                self.join_ticks += 1
                self.admit_h2d_bytes += shipped
                self.admit_rows += len(admits)
                self.admit_write_bytes += written
                sp.set(d2h_bytes=0, h2d_bytes=shipped,
                       admit_rows=len(admits), admit_write_bytes=written)
            with tracing.span("lm.decode.dispatch"):
                outputs, run.state = serve(run.params, run.state,
                                           {src: bundle})
            with tracing.span("lm.decode.fetch"):
                toks, emitted, finished, *load = jax.device_get(
                    outputs[sink].tensors)
            self.decode_ticks += 1
            run.frames += 1
            n_active = int(emitted.sum())
            if load:
                sp.set(**elem.note_routes(load[0], n_active, tick=True))
            self.batched_frames += n_active
            if n_active > 1:
                self.batches += 1
            done = 0
            for slot in sorted(self._slots):
                rec = self._slots[slot]
                if emitted[slot]:
                    rec["tokens"].append(int(toks[slot]))
                    self.tokens_generated += 1
                if finished[slot]:
                    self._finish(rec)
                    del self._slots[slot]
                    done += 1
            return done

    def _finish(self, rec: Dict):
        """Deliver one completed stream: all its tokens as ONE answer
        through the real serversink apply (per-client codec encode +
        client-channel route — identical to the stateless routing path)."""
        routing = rec["routing"]
        sink = self.run.pipe.plan.query_sinks[0]
        req = rec.pop("trace")
        answer = StreamBuffer(
            tensors=(np.asarray(rec["tokens"], np.int32),), meta=routing)
        with req, tracing.span("lm.finish"):
            sink.apply(self.run.params.get(sink.name, {}), [answer])
        req.finish()
        self.tokens_delivered += len(rec["tokens"])
        self.streams_finished += 1
        arec = rec.pop("adm", None)
        if arec is not None:
            self.admission.mark_served(arec)
        self._untrack(rec)

    def on_reconfig(self):
        """The serve topology was hot-swapped under live streams: a swapped
        serve element's plan state re-initialized at commit (kept elements
        carry theirs, but the batcher cannot tell which epoch a slot's
        cache belongs to), so every in-flight stream REPLAYS — its partial
        tokens become declared drops and the stream re-prefills on the new
        epoch at the next flush.  Greedy decode makes the replay bitwise a
        fresh build's answer (pinned in tests/test_model_serving.py);
        stale still-active slots in carried plan state self-clear (their
        ``remaining`` lane drains to zero with no record listening)."""
        super().on_reconfig()
        recs = [self._slots[s] for s in sorted(self._slots)] + self._waiting
        self._slots.clear()
        self._waiting = []
        for rec in recs:
            self.tokens_dropped += len(rec["tokens"])
            self.replays += 1
            rec["tokens"] = []
            rec["cache"] = None
        self._replay.extend(recs)

    def _abort_streams(self):
        """Endpoint died: every live stream's partial tokens are DECLARED
        drops (conservation law) — the orphaned PendingQuery records
        re-dispatch with prefill replay on a survivor, so the client still
        loses zero tokens end-to-end."""
        self._shed_dead()
        if not self._by_client:
            return
        total = 0
        for recs in self._by_client.values():
            for rec in recs:
                self.tokens_dropped += len(rec["tokens"])
                arec = rec.pop("adm", None)
                if arec is not None:
                    self.admission.mark_shed(arec, "server-died",
                                             notify=False)
                    self._forget_delivery(arec)
                total += 1
        self._orphan(total)
        self._slots.clear()
        self._waiting.clear()
        self._replay.clear()
        self._by_client.clear()

    def stats(self) -> Dict[str, int]:
        base = super().stats()
        base.update({
            "prefills": self.prefills,
            "decode_ticks": self.decode_ticks,
            "tokens_generated": self.tokens_generated,
            "tokens_delivered": self.tokens_delivered,
            "tokens_dropped": self.tokens_dropped,
            "tokens_in_flight": self.inflight_tokens(),
            "streams_started": self.streams_started,
            "streams_finished": self.streams_finished,
            "replays": self.replays,
            "join_ticks": self.join_ticks,
            "slot_ticks": self.slot_ticks,
            "admit_d2h_bytes": self.admit_d2h_bytes,
            "admit_h2d_bytes": self.admit_h2d_bytes,
            "admit_rows": self.admit_rows,
            "admit_write_bytes": self.admit_write_bytes,
            "commit_bytes": self.commit_bytes,
        })
        base.update(self._serve_elem().moe_counters)
        return base


class StageQueryBatcher(QueryBatcher):
    """Hop server for a DOWNSTREAM ``model_serve_stage`` pipeline (stage
    k >= 1 of an among-device chain, DESIGN.md §8).  Its endpoint receives
    hop requests from the chain's StagedStreamingBatcher, never
    client-facing prompts; ``meta["hop"]`` selects the verb:

    * ``"prefill"`` — stage-local prefill of one stream's boundary
      activations; the resulting b=1 cache PARKS here keyed by the
      coordinator's stream id (caches never cross the wire — only
      activations do), and the boundary output answers back.
    * ``"replay"``  — one b=1 decode step folded into a parked cache: the
      stage-local failover primitive (a replacement stage rebuilds exactly
      its own slice of a dead stage's state from the coordinator's
      retained activations).
    * ``"decode"``  — one slot-table hop through ``compiled_serve_tick``:
      ``meta["admit"]`` maps joining slots to parked stream ids (each
      parked cache is written into its slot row on the device before the
      hop, ``write_rows``), ``meta["live"]`` prunes parked caches of
      finished streams.

    Epoch fencing: every §6 reconfig of this pipeline bumps
    ``endpoint.spec["serve_epoch"]`` — the coordinator trusts a stage's
    slot caches only while (endpoint identity, epoch) are unchanged, so a
    hot-swapped stage is indistinguishable from a died-and-replaced one
    and both recover through the same stage-local replay rule."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._parked: Dict[int, Any] = {}      # stream id -> b=1 stage cache
        self.epoch = 0
        self.endpoint.spec.setdefault("serve_epoch", 0)
        self.prefills = 0
        self.replay_steps = 0
        self.decode_hops = 0
        self.slot_steps = 0

    def _serve_elem(self):
        plan = self.run.pipe.plan
        for op in plan.ops:
            if getattr(op.elem, "is_stage_serve", False):
                return op.elem
        raise RuntimeError("StageQueryBatcher on a non-stage plan")

    def flush(self) -> int:
        if not self.endpoint.alive:
            self._parked.clear()
            self._shed_dead()
            return 0
        # hop traffic shares the admission core for its ledger, but is
        # ALWAYS pass-through FIFO regardless of runtime QoS: each hop is
        # one step of a stream the coordinator already admitted under its
        # tenant's budget — re-scheduling mid-chain would deadlock the
        # synchronous hop round-trip (the runtime wires stage batchers
        # with qos=None for exactly this reason)
        adm = self.admission
        served = 0
        while self.endpoint.alive:
            self._ingest()
            recs = adm.take(1)
            if not recs:
                break
            self._serve_hop(recs[0].raw)
            adm.mark_served(recs[0])
            served += 1
        if served:
            self.flushes += 1
        return served

    def _serve_hop(self, raw: StreamBuffer):
        clean, routing = self._decode(raw)
        kind = clean.meta.get("hop", "decode")
        elem = self._serve_elem()
        params = self.run.params.get(elem.name, {})
        if kind == "prefill":
            sid = int(clean.meta["sid"])
            out, cache = elem.host_stage_prefill(params, clean.tensors[0])
            self._parked[sid] = cache
            self.prefills += 1
        elif kind == "replay":
            sid = int(clean.meta["sid"])
            # the hop's delivery id (if any) keys the stage element's
            # idempotence memo: even a duplicate that slipped past an
            # evicted dedup window cannot double-advance this cache
            out, cache = elem.host_stage_decode_idempotent(
                params, clean.tensors[0], self._parked[sid],
                hop_id=routing.get("dseq"))
            self._parked[sid] = cache
            self.replay_steps += 1
        else:
            out = self._serve_decode_hop(clean, elem)
        sink = self.run.pipe.plan.query_sinks[0]
        answer = StreamBuffer(tensors=(out,), meta=dict(routing))
        sink.apply(self.run.params.get(sink.name, {}), [answer])

    def _serve_decode_hop(self, clean: StreamBuffer, elem):
        x, active = clean.tensors
        rows = [(int(slot), self._parked.pop(int(sid)))
                for slot, sid in clean.meta.get("admit", ())]
        live = clean.meta.get("live")
        if live is not None:
            keep = set(int(s) for s in live)
            self._parked = {s: c for s, c in self._parked.items()
                            if s in keep}
        run = self.run
        plan = run.pipe.plan
        src = plan.query_sources[0].name
        sink = plan.query_sinks[0].name
        serve = plan.compiled_serve_tick(run.state)
        if rows:
            run.state = {**run.state, elem.name: elem.write_rows(
                run.state[elem.name], rows)}
        outputs, run.state = serve(run.params, run.state,
                                   {src: StreamBuffer(tensors=(x, active))})
        self.decode_hops += 1
        run.frames += 1
        n_active = int(np.asarray(active).sum())
        self.slot_steps += n_active
        self.batched_frames += n_active
        if n_active > 1:
            self.batches += 1
        return outputs[sink].tensors[0]

    def on_reconfig(self):
        """Stage hot-swapped under the chain: parked caches and slot rows
        belong to the OLD epoch — drop the parked ones and bump the epoch
        fence so the coordinator replays this stage before trusting it."""
        super().on_reconfig()
        self._parked.clear()
        self.epoch += 1
        self.endpoint.spec["serve_epoch"] = self.epoch

    def stats(self) -> Dict[str, int]:
        base = super().stats()
        base.update({
            "stage_prefills": self.prefills,
            "stage_replay_steps": self.replay_steps,
            "decode_hops": self.decode_hops,
            "slot_steps": self.slot_steps,
            "parked_caches": len(self._parked),
        })
        return base


class StagedStreamingBatcher(StreamingQueryBatcher):
    """The §8 chain coordinator: the streaming request lifecycle of
    StreamingQueryBatcher, with the model split across N
    ``model_serve_stage`` pipelines discovered over the broker.

    It is wired on STAGE 0's endpoint (the client-facing ``query/<op>``
    topic) and owns the slot table; stage 0 serves inline through its own
    run's ``compiled_serve_tick``, stages 1..N-1 are reached as
    among-device hops: a request pushed onto ``query/<op>/s<k>``'s
    best-ranked endpoint, served by that stage's StageQueryBatcher, the
    answer popped off the coordinator's response channel — the exact
    mechanism ``tensor_query_client.apply`` uses, so broker ranking,
    leases, win-back, and the §6 reconfig lifecycle all apply per stage.

    Admission runs a PREFILL CHAIN: stage-0 host prefill parks its b=1
    cache coordinator-side, each downstream stage prefills the upstream
    boundary activations and parks its own slice, the last stage answers
    the first token.  Decode runs one hop per stage per tick over the
    whole slot table.  The coordinator RETAINS every stream's per-stage
    boundary-activation history (prefill acts + one step per completed
    hop) — the feedstock for the per-stage replay rule:

    **Cache trust:** stage k's slot caches are trusted only while
    (endpoint identity, serve_epoch) are unchanged since the last
    successful hop.  On mismatch — death, lease expiry, failover to a
    standby, win-back, or a §6 swap — the coordinator rebuilds ONLY stage
    k: per live stream, replay the retained activations through the
    stage's prefill/replay verbs (bitwise by construction: identical
    traced programs on identical inputs), then the next hop's admit list
    writes the parked caches into their slot rows.  Other stages are
    untouched; no generation restarts; zero tokens drop.

    A hop that fails MID-TICK stalls the tick: stages < k already
    advanced this step, so the chain must resume FROM k — the pending-hop
    record keeps the in-flight boundary activations and the next flush
    re-dispatches after re-securing the stage.  Conservation holds per
    stage: ``hops_dispatched[k] == hops_completed[k] + hops_failed[k]``
    every flush, and the §7 token law holds at the coordinator."""

    def __init__(self, *args, broker=None, **kwargs):
        super().__init__(*args, **kwargs)
        self.broker = broker
        from .query import TensorQueryClient
        self._hop_cid = next(TensorQueryClient._ids)
        self._hops: Dict[int, Any] = {}         # stage -> Binding
        self._trust: Dict[int, Optional[Tuple]] = {}
        self._readmit: Dict[int, Dict[int, int]] = {}  # stage->{slot: sid}
        self._pending_hop: Optional[Dict] = None
        self._stalled: List[Dict] = []          # admission chains to retry
        self._sids = itertools.count(1)
        self.hops_dispatched: Dict[int, int] = {}
        self.hops_completed: Dict[int, int] = {}
        self.hops_failed: Dict[int, int] = {}
        self.stage_replays: Dict[int, int] = {}
        self.stage_replay_steps: Dict[int, int] = {}
        #: delivery policy for the among-device hops (DESIGN.md §10),
        #: installed by the runtime alongside the batcher guards.  None
        #: keeps the pre-§10 single-shot hop, bit for bit.
        self.delivery: Optional[netfault.DeliveryPolicy] = None
        self._hop_seq = 0
        self.hop_retransmits = 0
        self.hop_dups = 0
        self.hop_corrupt = 0
        self.hop_push_drops = 0

    @property
    def n_stages(self) -> int:
        return self._serve_elem().n_stages

    def _has_decode_work(self) -> bool:
        return bool(self._slots or self._waiting or self._stalled
                    or self._pending_hop)

    # -- stage discovery & trust ----------------------------------------------
    def _stage_binding(self, k: int):
        b = self._hops.get(k)
        if b is None:
            op = self.endpoint.operation
            b = self._hops[k] = self.broker.subscribe(
                f"query/{op}/s{k}",
                prefer={"codec": "none", "stage": k})
        return b

    def _stage_endpoint(self, k: int):
        from .broker import BrokerError
        try:
            binding = self._stage_binding(k)
            ep = binding.endpoint
            if not ep.alive:
                binding._rebind()
                ep = binding.endpoint
        except BrokerError:
            return None
        return ep if ep.alive else None

    def _ensure_stage(self, k: int):
        """Resolve stage k's endpoint and make its caches trustworthy:
        any change of (endpoint identity, serve_epoch) since the last hop
        triggers the stage-local replay before the stage is used again."""
        ep = self._stage_endpoint(k)
        if ep is None:
            return None
        key = (ep.endpoint_id, ep.spec.get("serve_epoch", 0))
        if self._trust.get(k) != key:
            if not self._replay_stage(k, ep):
                return None
            self._trust[k] = key
        return ep

    def _replay_stage(self, k: int, ep) -> bool:
        """Rebuild ONLY stage k's slice of every live stream's state from
        the retained boundary activations (DESIGN.md §8 replay rule)."""
        recs = [self._slots[s] for s in sorted(self._slots)] + \
            [r for r in self._waiting if r.get("sid") is not None]
        self.stage_replays[k] = self.stage_replays.get(k, 0) + 1
        for rec in recs:
            acts = rec["acts"][k]
            if self._raw_hop(ep, (acts[0],),
                             {"hop": "prefill", "sid": rec["sid"]}) is None:
                return False
            for step in acts[1:]:
                if self._raw_hop(ep, (step,),
                                 {"hop": "replay",
                                  "sid": rec["sid"]}) is None:
                    return False
                self.stage_replay_steps[k] = \
                    self.stage_replay_steps.get(k, 0) + 1
        # slotted streams' rows on the new stage are garbage until their
        # freshly parked caches are written into them at the next decode hop
        rd = self._readmit.setdefault(k, {})
        for slot, rec in self._slots.items():
            rd[slot] = rec["sid"]
        return True

    # -- the hop itself --------------------------------------------------------
    def _raw_hop(self, ep, tensors, meta) -> Optional[StreamBuffer]:
        """One request → inline serve → answer round-trip against a
        RESOLVED stage endpoint (the tensor_query_client mechanism, with
        the coordinator as the client).

        With a delivery policy the hop becomes at-least-once: the request
        carries a delivery id + CRC, and up to ``hop_retries`` synchronous
        retransmits reuse the SAME id — the stage guard dedups replays and
        re-fires the committed answer bitwise, so a duplicated or replayed
        hop can never double-advance a slot (§10).  Hops can't wait a
        tick (the chain holds the slot), hence the inline loop rather
        than the scheduler's backoff clock."""
        buf = StreamBuffer(tensors=tuple(tensors), meta=dict(meta))
        payload, nbytes = comp.encode(buf, "none")
        hmeta = {**payload.meta, "client_id": self._hop_cid,
                 "codec": "none"}
        delivery = self.delivery
        dseq = None
        crc = None
        if delivery is not None:
            self._hop_seq += 1
            dseq = (self._hop_cid, self._hop_seq)
            hmeta["dseq"] = dseq
            hmeta["crc"] = crc = netfault.checksum(payload)
        payload = payload.with_(meta=hmeta)
        if crc is not None:
            netfault.memoize_crc(payload, crc)
        attempts = max(1, delivery.hop_retries) if delivery is not None \
            else 1
        for attempt in range(attempts):
            if attempt:
                self.hop_retransmits += 1
            if not ep.requests.push(payload, nbytes):
                self.hop_push_drops += 1
            runner = ep.spec.get("inline_runner")
            if runner is None or not ep.alive:
                return None
            runner()
            ch = ep.client_channel(self._hop_cid)
            while True:
                raw = ch.pop()
                if raw is None:
                    break
                rmeta = raw.meta or {}
                if delivery is not None:
                    crc = rmeta.get("crc")
                    if crc is not None and \
                            netfault.checksum(raw) != int(crc):
                        self.hop_corrupt += 1
                        netfault.note(ch, "rejected_corrupt")
                        continue
                    rds = rmeta.get("dseq")
                    if rds is not None and rds != dseq:
                        # late duplicate of an EARLIER hop's answer —
                        # that hop already consumed one copy; this one
                        # dedups here, never advances anything
                        self.hop_dups += 1
                        netfault.note(ch, "deduped")
                        continue
                    netfault.note(ch, "accepted")
                return comp.decode(raw, "none")
        return None

    def _hop(self, k: int, tensors, meta) -> Optional[StreamBuffer]:
        ep = self._ensure_stage(k)
        self.hops_dispatched[k] = self.hops_dispatched.get(k, 0) + 1
        ans = None if ep is None else self._raw_hop(ep, tensors, meta)
        if ans is None:
            self.hops_failed[k] = self.hops_failed.get(k, 0) + 1
            self._trust[k] = None       # whatever happened, re-secure first
        else:
            self.hops_completed[k] = self.hops_completed.get(k, 0) + 1
        return ans

    # -- admission (prefill chain) ---------------------------------------------
    def _admit(self) -> int:
        finished = 0
        elem = self._serve_elem()
        params = self.run.params.get(elem.name, {})
        if self._replay:
            # stage-0 hot-swap replay (inherited §6 semantics): the whole
            # chain re-prefills these streams on the new epoch
            replays, self._replay = self._replay, []
            for rec in replays:
                for key in ("cache0", "sid", "acts", "chain_next",
                            "chain_x"):
                    rec.pop(key, None)
                finished += self._start_stream(rec, elem, params)
        if self._stalled:
            stalled, self._stalled = self._stalled, []
            for rec in stalled:
                finished += self._resume_chain(rec)
        adm = self.admission
        while self.endpoint.alive:
            self._ingest()
            adm.expire()
            recs = adm.take(1)
            if not recs:
                break
            arec = recs[0]
            clean, routing = self._decode(arec.raw)
            gen = int(clean.meta.get("gen", 1))
            rec = {"routing": routing, "tokens": [],
                   "prompt": clean.tensors[0], "gen": gen, "remaining": 0,
                   "adm": arec, "trace": tracing.request(arec.arrived_ns)}
            self.streams_started += 1
            self._track(rec)
            finished += self._start_stream(rec, elem, params)
        return finished

    def _start_stream(self, rec: Dict, elem, params) -> int:
        """Stage-0 prefill (parked coordinator-side) + downstream prefill
        chain.  The stage-0 boundary activations are retained as acts[1]'s
        seed; stage 0's own replay feedstock is ``rec["prompt"]``."""
        out, cache0 = elem.host_stage_prefill(params, rec["prompt"])
        self.prefills += 1
        rec["tokens"] = []
        rec["cache0"] = cache0
        rec["sid"] = next(self._sids)
        rec["acts"] = {k: [] for k in range(1, self.n_stages)}
        rec["chain_next"] = 1
        rec["chain_x"] = np.asarray(out)
        return self._resume_chain(rec)

    def _resume_chain(self, rec: Dict) -> int:
        k = rec["chain_next"]
        x = rec["chain_x"]
        while k < self.n_stages:
            rec["acts"][k] = [x]    # assign, not append: retries overwrite
            ans = self._hop(k, (x,), {"hop": "prefill", "sid": rec["sid"]})
            if ans is None:
                rec["chain_next"], rec["chain_x"] = k, x
                self._stalled.append(rec)
                return 0
            x = np.asarray(ans.tensors[0])
            k += 1
        del rec["chain_next"], rec["chain_x"]
        rec["tokens"] = [int(np.asarray(x).reshape(()))]
        self.tokens_generated += 1
        rec["remaining"] = max(0, rec["gen"] - 1)
        if rec["remaining"] <= 0:
            self._finish(rec)
            return 1
        self._waiting.append(rec)
        return 0

    # -- the per-tick decode chain ---------------------------------------------
    def _decode_tick(self) -> int:
        if self._pending_hop is not None:
            # a stage died mid-tick: stages < k already advanced this
            # step — resume the SAME step from stage k, never re-run it
            return self._run_chain()
        run = self.run
        elem = self._serve_elem()
        free = sorted(s for s in range(elem.slots) if s not in self._slots)
        rows = []
        while free and self._waiting:
            rec = self._waiting.pop(0)
            slot = free.pop(0)
            rows.append((slot, rec["cache0"]))
            rec["cache0"] = None    # stage 0's slice lives in plan state now
            self._slots[slot] = rec
            for k in range(1, self.n_stages):
                self._readmit.setdefault(k, {})[slot] = rec["sid"]
        if not self._slots:
            return 0
        s = elem.slots
        active = np.zeros((s,), np.bool_)
        tok = np.zeros((s,), np.int32)
        for slot, rec in self._slots.items():
            active[slot] = True
            tok[slot] = rec["tokens"][-1]
        plan = run.pipe.plan
        src = plan.query_sources[0].name
        sink = plan.query_sinks[0].name
        serve = plan.compiled_serve_tick(run.state)
        if rows:
            run.state = {**run.state, elem.name: elem.write_rows(
                run.state[elem.name], rows)}
        outputs, run.state = serve(run.params, run.state,
                                   {src: StreamBuffer(tensors=(tok, active))})
        y = np.asarray(jax.device_get(outputs[sink].tensors[0]))
        self.decode_ticks += 1
        run.frames += 1
        n_active = int(active.sum())
        self.batched_frames += n_active
        if n_active > 1:
            self.batches += 1
        self._pending_hop = {"k": 1, "x": y, "active": active}
        return self._run_chain()

    def _run_chain(self) -> int:
        ph = self._pending_hop
        x, active = ph["x"], ph["active"]
        k = ph["k"]
        live = tuple(sorted(rec["sid"] for rec in self._iter_recs()
                            if rec.get("sid") is not None))
        while k < self.n_stages:
            # secure the stage BEFORE assembling the admit list: a trust
            # break replays into _readmit[k], and those freshly parked
            # caches must be written on THIS hop — assembling first would
            # ship an empty admit and decode the standby's zero rows
            self._ensure_stage(k)
            rd = self._readmit.get(k, {})
            admit = tuple((int(slot), int(sid))
                          for slot, sid in sorted(rd.items())
                          if active[slot])
            ans = self._hop(k, (x, active),
                            {"hop": "decode", "admit": admit, "live": live})
            if ans is None:
                ph["k"], ph["x"] = k, x
                return 0
            # x is now part of stage k's committed history — retain it as
            # replay feedstock (AFTER the hop: an in-flight step must not
            # be replayed into a cache it never reached)
            for slot, rec in self._slots.items():
                rec["acts"][k].append(x[slot:slot + 1])
            self._readmit[k] = {}
            x = np.asarray(ans.tensors[0])
            k += 1
        self._pending_hop = None
        done = 0
        for slot in sorted(self._slots):
            rec = self._slots[slot]
            rec["tokens"].append(int(x[slot]))
            self.tokens_generated += 1
            rec["remaining"] -= 1
            if rec["remaining"] <= 0:
                self._finish(rec)
                del self._slots[slot]
                for rd in self._readmit.values():
                    rd.pop(slot, None)
                done += 1
        return done

    def _iter_recs(self):
        yield from self._slots.values()
        yield from self._waiting
        yield from self._stalled

    # -- lifecycle edges --------------------------------------------------------
    def on_reconfig(self):
        """Stage 0's pipeline was hot-swapped: inherited whole-stream
        replay (stage 0's slice re-initialized at commit) plus chain
        bookkeeping reset — stalled admissions rejoin the replay queue and
        downstream stages simply see fresh stream ids (their stale parked
        caches prune via the next hop's live list)."""
        stalled, self._stalled = self._stalled, []
        super().on_reconfig()
        for rec in stalled:
            self.replays += 1
            rec["tokens"] = []
            self._replay.append(rec)
        self._pending_hop = None
        self._readmit = {}

    def _abort_streams(self):
        super()._abort_streams()
        self._stalled.clear()
        self._pending_hop = None
        self._readmit = {}
        self._trust = {}

    def stats(self) -> Dict[str, int]:
        base = super().stats()
        base.update({
            "hops_dispatched": sum(self.hops_dispatched.values()),
            "hops_completed": sum(self.hops_completed.values()),
            "hops_failed": sum(self.hops_failed.values()),
            "stage_replays": sum(self.stage_replays.values()),
            "stage_replay_steps": sum(self.stage_replay_steps.values()),
            "hop_retransmits": self.hop_retransmits,
            "hop_dups": self.hop_dups,
            "hop_corrupt": self.hop_corrupt,
            "hop_push_drops": self.hop_push_drops,
        })
        return base

    def stage_ledger(self, k: int) -> Dict[str, int]:
        """Per-stage hop conservation record (pinned per stage by the
        staged soak): every dispatched hop is completed or failed."""
        return {"dispatched": self.hops_dispatched.get(k, 0),
                "completed": self.hops_completed.get(k, 0),
                "failed": self.hops_failed.get(k, 0),
                "replays": self.stage_replays.get(k, 0),
                "replay_steps": self.stage_replay_steps.get(k, 0)}
