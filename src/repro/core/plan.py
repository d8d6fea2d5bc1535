"""Compiled pipeline execution engine.

``ExecutionPlan`` turns a realized :class:`~repro.core.pipeline.Pipeline`
into a *plan*: the topo order and link wiring are flattened once, at
``realize()`` time, into a static slot-indexed schedule, so stepping a frame
no longer re-sorts links or rebuilds dicts per step (the host-side dispatch
cost NNStreamer avoids by compiling the graph once — arXiv 2101.06371).

Three execution tiers, all bitwise-identical to the seed interpreter:

* ``plan.run(params, state, inputs)`` — one frame through the static
  schedule; pure and jittable.
* ``plan.compiled_step()`` — a jitted executable, cached in a process-wide
  registry keyed by the plan's **topology fingerprint** (element configs +
  link wiring + negotiated caps).  Reconnecting a structurally identical
  pipeline after failover reuses the executable and never retraces; per
  fingerprint, XLA's own jit cache covers the (input shapes/dtypes) axis.
* ``plan.step_n(params, state, inputs, n)`` — an N-frame **burst**: one
  ``lax.scan`` dispatch runs the whole DAG N times over stacked
  :class:`StreamBuffer` frames, amortizing Python/jit dispatch to ~1/N per
  frame.  The runtime scheduler uses this to drain queued Channel frames.

Host-impure elements (mqtt sources/sinks) cannot be traced; ``hoist_io=True``
runs the plan in *hoisted* mode: host-driven sources must be injected through
``inputs`` (the scheduler pulls & decodes at host level) and host sinks
capture their input frame into the outputs dict instead of pushing, so the
scheduler can replay the captured frames through the real (impure)
``apply`` after the burst returns.

Donation: compiled executables donate the ``state`` argument when requested
(``donate=True``) or automatically on gpu/tpu backends (``donate=None``) —
state buffers are overwritten in place across frames.  Donation stays off on
CPU where XLA does not implement it (it would only emit warnings).

Mesh sharding (DESIGN.md §4): ``step_n`` and ``serve_batch`` accept a jax
``Mesh``.  When the stacked frame axis divides the mesh's data-axis extent
and the pipeline threads **no cross-frame state** (the state pytree has no
leaves), the burst is laid out along the data axes with ``shard_map``: each
device scans its own contiguous slice of the frame axis with the exact
per-frame program the single-device scan runs, params/state replicated
(``in_specs=P()``), so the answers are bitwise those of single-device
serving.  Stateful plans, indivisible batch sizes, and 1-device meshes fall
back to the single-device scan — sharding never changes semantics, only
where frames execute.  Compiled executables are cached per (fingerprint,
mesh identity): reconnecting after failover with the same mesh never
retraces.

Fused wire path (DESIGN.md §5): ``serve_batch_wire`` serves a batch of
WIRE-form requests — per-request decode, stacked scan, and per-frame
re-encode of the answers all inside ONE jit (``compiled_serve_batch(codec=
...)``), with the codec as a static trace parameter.  The executable-cache
key carries the codec fingerprint, so codec-fused and plain executables
never collide.  ``run_deferred_compiled`` is the client-side counterpart:
pipelines whose only impure elements are their query clients run each
deferred SEGMENT (start → first client, client → client, client → end) as
one jitted dispatch instead of an interpreted per-element walk — bitwise
the interpreted deferral, minus the per-element dispatch overhead that made
batched e2e ticks slower than sequential ones.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .buffers import StreamBuffer
from .element import Element, PipelineContext

__all__ = ["ExecutionPlan", "PendingQuery", "PlanOp",
           "clear_executable_cache", "executable_cache_info"]


class PlanOp:
    """One scheduled element: static wiring resolved to value slots."""

    __slots__ = ("elem", "name", "in_slots", "out_slots", "injectable",
                 "is_sink", "is_host_sink", "is_query_src", "is_query_sink",
                 "is_query_client")

    def __init__(self, elem: Element, in_slots: Tuple[int, ...],
                 out_slots: Tuple[int, ...], injectable: bool,
                 is_sink: bool, is_host_sink: bool):
        self.elem = elem
        self.name = elem.name
        self.in_slots = in_slots
        self.out_slots = out_slots
        self.injectable = injectable
        self.is_sink = is_sink
        self.is_host_sink = is_host_sink
        self.is_query_src = getattr(elem, "is_query_source", False)
        self.is_query_sink = getattr(elem, "is_query_sink", False)
        self.is_query_client = getattr(elem, "is_query_client", False)


# Process-wide executable registry: fingerprint -> (owning plan, jitted fns).
# Two plans with equal fingerprints are behaviorally identical (the
# fingerprint covers element class, static config, wiring and negotiated
# caps), so the first plan's jitted functions serve all of them.
#
# The jitted fns close over the owning plan's element graph, pinning it
# alive; to keep a long-running process that churns through many distinct
# topologies bounded, the registry is LRU-capped — evicting a fingerprint
# only costs a retrace if that topology ever comes back.
_EXEC_CACHE: "OrderedDict[Any, Dict[str, Any]]" = OrderedDict()
_EXEC_CACHE_MAX = 128


def clear_executable_cache():
    _EXEC_CACHE.clear()


def executable_cache_info() -> Dict[str, int]:
    return {"fingerprints": len(_EXEC_CACHE),
            "executables": sum(len(e["fns"]) for e in _EXEC_CACHE.values())}


class ExecutionPlan:
    """Static schedule + executable cache for one realized pipeline."""

    def __init__(self, pipeline):
        from .elements import AppSink, AppSrc  # cycle-free: elements<-element

        order: List[Element] = pipeline._order
        links = pipeline.links
        # slot assignment: every (producer, src_pad) that any link consumes
        slot_of: Dict[Tuple[str, int], int] = {}
        for l in links:
            key = (l.src.name, l.src_pad)
            if key not in slot_of:
                slot_of[key] = len(slot_of)
        self.n_slots = len(slot_of)

        in_links: Dict[str, list] = {e.name: [] for e in order}
        for l in links:
            in_links[l.dst.name].append(l)

        ops: List[PlanOp] = []
        for elem in order:
            lk = sorted(in_links[elem.name], key=lambda l: l.dst_pad)
            in_slots = tuple(slot_of[(l.src.name, l.src_pad)] for l in lk)
            max_pad = max((l.src_pad for l in links if l.src is elem),
                          default=-1)
            out_slots = tuple(slot_of.get((elem.name, p), -1)
                              for p in range(max_pad + 1))
            injectable = isinstance(elem, AppSrc) or \
                getattr(elem, "is_host_source", False)
            ops.append(PlanOp(elem, in_slots, out_slots,
                              injectable=injectable,
                              is_sink=isinstance(elem, AppSink),
                              is_host_sink=getattr(elem, "is_host_sink",
                                                   False)))
        self.ops = ops
        self.host_sources = [op.elem for op in ops
                             if getattr(op.elem, "is_host_source", False)]
        self.host_sinks = [op.elem for op in ops if op.is_host_sink]
        impure = [op.elem for op in ops
                  if getattr(op.elem, "host_impure", False)]
        #: no host-impure elements at all — safe to jit as-is
        self.pure = not impure
        #: every impure element is a hoistable source or terminal sink, so
        #: the DAG between them is traceable and scan-batched bursts apply
        self.burstable = all(
            getattr(e, "is_host_source", False) or
            getattr(e, "is_host_sink", False) for e in impure)
        #: every graph source is host-driven: a burst replays only queued
        #: frames.  A self-driven source (testsrc camera) mixed in would be
        #: fast-forwarded by a burst — fabricating future frames — so the
        #: scheduler must keep such pipelines on the tick cadence.
        self.all_sources_host_driven = bool(self.host_sources) and all(
            getattr(op.elem, "is_host_source", False)
            for op in ops if not op.in_slots)
        # -- query-protocol topology flags (see core/batching.py) -------------
        #: serversrc/serversink pairs of a query *server* pipeline
        self.query_sources = [op.elem for op in ops if op.is_query_src]
        self.query_sinks = [op.elem for op in ops if op.is_query_sink]
        #: pipeline contains tensor_query_client elements — the runtime
        #: scheduler can run it deferred (pause at each client, gather the
        #: request into a server-side micro-batch, resume with the answer)
        self.has_query_clients = any(op.is_query_client for op in ops)
        #: server pipeline whose impure elements are exactly one injectable
        #: serversrc plus capturable serversinks: N decoded requests can be
        #: stacked and served in ONE hoisted `step_n` scan dispatch.  Anything
        #: else (extra impure elements, multiple serversrcs, non-serversrc
        #: graph sources) keeps the sequential one-request-at-a-time path.
        self.query_batchable = (
            len(self.query_sources) == 1 and bool(self.query_sinks)
            and all(getattr(e, "is_query_source", False)
                    or getattr(e, "is_query_sink", False) for e in impure)
            and all(op.is_query_src for op in ops if not op.in_slots))
        #: query-batchable server whose serve element runs a streaming
        #: (autoregressive) workload: decode state is PLAN STATE carried
        #: across ticks, so the dispatch is one stateful `serve_tick` per
        #: runtime tick (continuous batching over state slots) instead of
        #: the stateless stack-scan-split over independent frames
        self.stream_serving = self.query_batchable and any(
            getattr(op.elem, "is_stream_serve", False) for op in ops)
        #: stream-serving pipeline that is ONE STAGE of an among-device
        #: pipeline-parallel chain (DESIGN.md §8): the serve element owns a
        #: contiguous layer slice plus that slice of the slot-stacked
        #: decode cache; (stage, n_stages) is the hop signature — part of
        #: the multi-hop serve fingerprint, so two stages of one chain (or
        #: the same stage of two chains of different depth) never share a
        #: serve_tick executable even when their cache structures agree
        stage_elems = [op.elem for op in ops
                       if getattr(op.elem, "is_stage_serve", False)]
        self.stage_serving = self.stream_serving and bool(stage_elems)
        self.serve_stage = ((stage_elems[0].stage, stage_elems[0].n_stages)
                            if self.stage_serving else None)
        #: op indices of the query clients, in schedule order (the deferred
        #: walk's pause points — static, because topology is static)
        self.client_idxs = tuple(i for i, op in enumerate(ops)
                                 if op.is_query_client)
        #: every impure element is a query client: the segments BETWEEN
        #: pause points are pure and each can run as one jitted dispatch
        #: (run_deferred_compiled) instead of an interpreted walk
        self.deferred_compilable = bool(self.client_idxs) and all(
            getattr(e, "is_query_client", False) for e in impure)
        self.fingerprint = self._fingerprint(order, links)

    @staticmethod
    def _fingerprint(order: List[Element], links) -> Tuple:
        elems = tuple(e.plan_signature() for e in order)
        wiring = tuple((l.src.name, l.src_pad, l.dst.name, l.dst_pad)
                       for l in links)
        return (elems, wiring)

    # -- single-frame execution ------------------------------------------------
    def _exec_ops(self, params: dict, ctx: PipelineContext, vals: List[Any],
                  outputs: Dict[str, StreamBuffer],
                  inputs: Dict[str, StreamBuffer], start: int,
                  hoist_io: bool, hoist_queries: bool, defer_queries: bool
                  ) -> Optional[Tuple[int, StreamBuffer]]:
        """Walk ``ops[start:]`` mutating ``vals``/``outputs``/``ctx``.

        Returns ``None`` when the schedule completes, or ``(op_idx, request)``
        when ``defer_queries=True`` and a query client is reached — the
        caller ships ``request`` to a server batch and later resumes from
        ``op_idx`` with the answer (see :class:`PendingQuery`).
        """
        for idx in range(start, len(self.ops)):
            op = self.ops[idx]
            ins = [vals[s] for s in op.in_slots]
            injectable = op.injectable or (hoist_queries and op.is_query_src)
            if injectable and op.name in inputs:
                ins = [inputs[op.name]]
                if getattr(op.elem, "is_host_source", False) or \
                        (hoist_queries and op.is_query_src):
                    # host-driven source (mqttsrc) or hoisted serversrc: its
                    # apply would pull from the channel; the injected,
                    # already-decoded frame IS the pull — emit it directly
                    if op.out_slots and op.out_slots[0] >= 0:
                        vals[op.out_slots[0]] = ins[0]
                    continue
            elif hoist_io and getattr(op.elem, "is_host_source", False):
                raise ValueError(
                    f"{op.name}: hoisted execution requires an injected "
                    f"input frame for every host-driven source")
            elif hoist_queries and op.is_query_src:
                raise ValueError(
                    f"{op.name}: hoisted query serving requires an injected "
                    f"request frame for every serversrc")
            if (hoist_io and op.is_host_sink) or \
                    (hoist_queries and op.is_query_sink):
                # capture instead of the impure push; the caller replays the
                # captured frame through the element's real apply afterwards
                outputs[op.name] = ins[0]
                continue
            if defer_queries and op.is_query_client:
                return idx, ins[0]
            outs = op.elem.apply(params.get(op.name, {}), ins, ctx)
            for i, o in enumerate(outs):
                if i < len(op.out_slots) and op.out_slots[i] >= 0:
                    vals[op.out_slots[i]] = o
            if op.is_sink and outs:
                outputs[op.name] = outs[0]
        return None

    def run(self, params: dict, state: dict,
            inputs: Optional[Dict[str, StreamBuffer]] = None,
            hoist_io: bool = False, hoist_queries: bool = False
            ) -> Tuple[Dict[str, StreamBuffer], dict]:
        """One frame through the static schedule.  Pure (jittable) when the
        pipeline is pure or hoisted (``hoist_io`` with all host sources
        injected; ``hoist_queries`` with the serversrc request injected).
        Semantics match the seed interpreter bitwise."""
        inputs = inputs or {}
        ctx = PipelineContext(state)
        vals: List[Any] = [None] * self.n_slots
        outputs: Dict[str, StreamBuffer] = {}
        self._exec_ops(params, ctx, vals, outputs, inputs, 0,
                       hoist_io, hoist_queries, defer_queries=False)
        return outputs, ctx.next_state

    def run_deferred(self, params: dict, state: dict,
                     inputs: Optional[Dict[str, StreamBuffer]] = None):
        """Start one frame, pausing at the first un-answered query client.

        Returns ``(outputs, next_state)`` when the pipeline has no query
        client on this frame's path, or a :class:`PendingQuery` whose
        ``request`` is the buffer the client was about to send.  The caller
        performs the send/serve/receive at host level (the runtime
        scheduler's queue-gather-flush) and calls ``resume(answer)``.
        Interpreted host-level execution only — never jit this path."""
        inputs = inputs or {}
        ctx = PipelineContext(state)
        vals: List[Any] = [None] * self.n_slots
        outputs: Dict[str, StreamBuffer] = {}
        res = self._exec_ops(params, ctx, vals, outputs, inputs, 0,
                             hoist_io=False, hoist_queries=False,
                             defer_queries=True)
        if res is None:
            return outputs, ctx.next_state
        return PendingQuery(self, params, inputs, ctx, vals, outputs, *res)

    # -- burst execution -------------------------------------------------------
    @staticmethod
    def shardable_batch(n: int, state: dict, mesh) -> bool:
        """True when an ``n``-frame burst can be laid out along ``mesh``'s
        data axes without changing semantics: more than one data-axis device,
        a frame axis that tiles them evenly, and NO cross-frame state (a
        state pytree with leaves must thread through the scan in FIFO order —
        splitting it across devices would change what frame ``i`` sees).
        The decision is trace-static (shapes + pytree structure only), so the
        host-side caller and the jitted executable always agree on it."""
        if mesh is None or n <= 0:
            return False
        if jax.tree_util.tree_leaves(state):
            return False
        from ..launch.mesh import data_axis_size
        dsize = data_axis_size(mesh)
        return dsize > 1 and n % dsize == 0

    def _step_n_sharded(self, params: dict, state: dict, inputs, mesh,
                        hoist_io: bool, hoist_queries: bool
                        ) -> Tuple[Dict[str, StreamBuffer], dict]:
        """Among-device burst: shard the stacked frame axis along the mesh's
        data axes; every device runs the single-device scan program over its
        own contiguous frame slice (params/state replicated), so frame ``i``
        is bitwise what the single-device scan produces.  Only called when
        :meth:`shardable_batch` holds — state has no leaves, hence no carry
        crosses the shard boundary."""
        from jax.sharding import PartitionSpec as P
        from ..launch.mesh import batch_spec, data_axis_size
        dspec = P(batch_spec(mesh))
        n_local = (jax.tree_util.tree_leaves(inputs)[0].shape[0]
                   // data_axis_size(mesh))

        def local_scan(p, s, local):
            if n_local == 1:
                # one frame per device: run the DAG directly — a length-1
                # lax.scan drags while-loop/dynamic-slice machinery into
                # every partition for nothing (measured ~2x the dispatch)
                frame = jax.tree_util.tree_map(lambda l: l[0], local)
                outs, _ = self.run(p, s, frame, hoist_io=hoist_io,
                                   hoist_queries=hoist_queries)
                return jax.tree_util.tree_map(lambda l: l[None], outs)

            def body(carry, x):
                outs, nxt = self.run(p, carry, x, hoist_io=hoist_io,
                                     hoist_queries=hoist_queries)
                return nxt, outs
            _, outs = lax.scan(body, s, local)
            return outs

        outs = jax.shard_map(local_scan, mesh=mesh,
                             in_specs=(P(), P(), dspec),
                             out_specs=dspec)(params, state, inputs)
        # no state leaves: the scan carry is pure structure, returned as-is
        return outs, dict(state)

    def step_n(self, params: dict, state: dict,
               inputs: Optional[Dict[str, StreamBuffer]] = None,
               n: Optional[int] = None, hoist_io: bool = False,
               hoist_queries: bool = False, mesh=None
               ) -> Tuple[Dict[str, StreamBuffer], dict]:
        """Run an N-frame burst with a single ``lax.scan`` dispatch.

        ``inputs`` maps source names to *stacked* StreamBuffers (leading axis
        N, see :func:`repro.core.buffers.stack_buffers`); self-driven
        pipelines pass ``n`` instead.  Returns (stacked outputs, final
        state) — frame ``i`` of the outputs equals what ``run`` would have
        produced on the ``i``-th sequential call.

        With ``mesh``, hoisted bursts whose frame axis tiles the mesh's data
        axes and whose state pytree is leafless run sharded
        (:meth:`_step_n_sharded`); anything else falls back to the
        single-device scan unchanged.
        """
        if inputs is None and n is None:
            raise ValueError("step_n needs stacked `inputs` or a length `n`")
        if mesh is not None and inputs is not None:
            leaves = jax.tree_util.tree_leaves(inputs)
            nn = int(leaves[0].shape[0]) if leaves else 0
            if self.shardable_batch(nn, state, mesh):
                return self._step_n_sharded(params, state, inputs, mesh,
                                            hoist_io, hoist_queries)

        def body(carry, x):
            outs, nxt = self.run(params, carry, x, hoist_io=hoist_io,
                                 hoist_queries=hoist_queries)
            return nxt, outs

        final_state, outs = lax.scan(body, state, inputs, length=n)
        return outs, final_state

    def serve_batch(self, params: dict, state: dict, frames: Tuple,
                    mesh=None) -> Tuple[Tuple, dict]:
        """Serve N query requests as one traced unit: stack the per-frame
        input dicts, scan the hoisted DAG, and split the outputs back into
        per-frame pytrees — all INSIDE the trace, so a compiled batch costs
        one host dispatch total (eager stack/unstack would pay one dispatch
        per leaf per frame, which is the overhead batching exists to kill).

        ``frames`` is a tuple of ``{source_name: StreamBuffer}`` dicts with
        identical pytree structure.  Returns (tuple of per-frame outputs,
        final state); frame ``i`` equals the ``i``-th sequential hoisted
        ``run``.

        With ``mesh``, batches satisfying :meth:`shardable_batch` serve
        sharded along the mesh's data axes (one frame slice per device);
        everything else — including every stateful plan — keeps the
        single-device scan, so batch composition and placement never change
        any client's numerics."""
        n = len(frames)
        if n == 1:  # never shardable: 1 frame cannot tile >1 devices
            outs, final = self.run(params, state, frames[0],
                                   hoist_io=True, hoist_queries=True)
            return (outs,), final
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *frames)
        outs, final = self.step_n(params, state, stacked,
                                  hoist_io=True, hoist_queries=True,
                                  mesh=mesh)
        per = tuple(jax.tree_util.tree_map(lambda l, _i=i: l[_i], outs)
                    for i in range(n))
        return per, final

    def serve_batch_wire(self, params: dict, state: dict, wire_frames: Tuple,
                         codec: str) -> Tuple[Tuple, dict]:
        """Codec-fused :meth:`serve_batch`: the whole wire path of a batch —
        per-request decode, stacked scan, per-frame re-encode of the query
        answers — in one traced unit (DESIGN.md §5).

        ``wire_frames`` is a tuple of ``{serversrc_name: wire StreamBuffer}``
        dicts with identical pytree structure and one shared static
        ``codec`` (the batcher groups by codec exactly like it groups by
        structure).  Returns ``((stacked_wire_answers, stacked_app_outs,
        dropped), final_state)``:

        * ``stacked_wire_answers`` — ``{sink_name: wire StreamBuffer}`` with
          a leading frame axis; frame ``i`` of every payload is bitwise
          what the eager path (decode → serve → ``encode``) produces;
        * ``stacked_app_outs`` — non-query-sink outputs, stacked;
        * ``dropped`` — ``{sink_name: int32 [tensors, frames]}`` deferred
          sparse truncation counts, PER SINK (empty unless the codec is
          sparse): the caller syncs ONCE per flush and stamps each sink's
          own ``meta["sparse_dropped"]`` / codec stats host-side — the
          per-buffer loss signal the eager serversink encode produces,
          without its one sync per tensor.

        Answers stay stacked at the jit boundary (the PR-4 lesson: per-frame
        outputs cost a dispatch per leaf per frame; the host fetches the
        stack once and splits as numpy)."""
        from . import compression as comp
        n = len(wire_frames)
        src = self.query_sources[0].name
        stacked_wire = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[f[src] for f in wire_frames])
        dense = comp.decode_stacked(stacked_wire, codec)
        if n == 1:
            # one frame: run the DAG directly (a length-1 scan drags
            # while-loop machinery into the trace for nothing — the same
            # choice serve_batch makes)
            frame = jax.tree_util.tree_map(lambda l: l[0], dense)
            outs, final = self.run(params, state, {src: frame},
                                   hoist_io=True, hoist_queries=True)
            outs = jax.tree_util.tree_map(lambda l: l[None], outs)
        else:
            outs, final = self.step_n(params, state, {src: dense},
                                      hoist_io=True, hoist_queries=True)
        sink_names = {e.name for e in self.query_sinks}
        wire_outs: Dict[str, StreamBuffer] = {}
        app_outs: Dict[str, StreamBuffer] = {}
        dropped: Dict[str, Any] = {}
        for name, buf in outs.items():
            if name in sink_names:
                w, drp = comp.encode_stacked(buf, codec)
                wire_outs[name] = w
                if drp is not None:
                    dropped[name] = drp
            else:
                app_outs[name] = buf
        return (wire_outs, app_outs, dropped), final

    # -- compiled executables --------------------------------------------------
    def _cache(self) -> Dict[str, Any]:
        ent = _EXEC_CACHE.get(self.fingerprint)
        if ent is None:
            ent = {"fns": {}}
            _EXEC_CACHE[self.fingerprint] = ent
            while len(_EXEC_CACHE) > _EXEC_CACHE_MAX:
                _EXEC_CACHE.popitem(last=False)
        else:
            _EXEC_CACHE.move_to_end(self.fingerprint)
        return ent

    @staticmethod
    def _resolve_donate(donate: Optional[bool]) -> bool:
        if donate is None:
            return jax.default_backend() in ("gpu", "tpu")
        return bool(donate)

    def compiled_step(self, donate: Optional[bool] = None) -> Callable:
        """Jitted single-frame step ``(params, state, inputs=None) ->
        (outputs, next_state)``, shared across all plans with this
        fingerprint."""
        donate = self._resolve_donate(donate)
        fns = self._cache()["fns"]
        key = ("step", donate)
        if key not in fns:
            fns[key] = jax.jit(self.run,
                               donate_argnums=(1,) if donate else ())
        return fns[key]

    @staticmethod
    def _mesh_key(mesh):
        from ..launch.mesh import mesh_fingerprint
        return mesh_fingerprint(mesh)

    def compiled_step_n(self, hoist_io: bool = False,
                        hoist_queries: bool = False,
                        donate: Optional[bool] = None, mesh=None) -> Callable:
        """Jitted burst step ``(params, state, inputs=None, n=None) ->
        (stacked outputs, final state)``.  ``n``, ``hoist_io``,
        ``hoist_queries`` and ``mesh`` are static; each distinct burst
        length (= query batch size in hoisted-query serving) traces once and
        is cached thereafter in the fingerprint-keyed registry.  The cache
        key carries the mesh identity (axes, shape, device assignment), so a
        mesh-sharded executable is never confused with the single-device one
        and reconnecting with the same mesh never retraces."""
        donate = self._resolve_donate(donate)
        fns = self._cache()["fns"]
        key = ("step_n", hoist_io, hoist_queries, donate, self._mesh_key(mesh))
        if key not in fns:
            def step_n(params, state, inputs=None, n=None,
                       _self=self, _hoist=hoist_io, _hoistq=hoist_queries,
                       _mesh=mesh):
                return _self.step_n(params, state, inputs, n=n,
                                    hoist_io=_hoist, hoist_queries=_hoistq,
                                    mesh=_mesh)
            fns[key] = jax.jit(step_n, static_argnames=("n",),
                               donate_argnums=(1,) if donate else ())
        return fns[key]

    def compiled_serve_batch(self, donate: Optional[bool] = None,
                             mesh=None, codec: Optional[str] = None
                             ) -> Callable:
        """Jitted :meth:`serve_batch` ``(params, state, frames_tuple) ->
        (per-frame outputs tuple, final state)``.  The batch size lives in
        the input pytree structure, so each distinct size traces once per
        fingerprint and is cached thereafter (the QueryBatcher caps sizes
        at ``max_batch``, keeping the trace set tiny).  ``mesh`` extends the
        cache key exactly like :meth:`compiled_step_n`.

        ``codec`` (static) selects the codec-FUSED executable instead: a
        jitted :meth:`serve_batch_wire` ``(params, state, wire_frames) ->
        ((stacked wire answers, stacked app outs, dropped), final)``.  The
        cache key carries the codec fingerprint, so codec-fused and plain
        executables never collide — and neither do two codecs (quant8 and
        sparse trace different wire pytrees).  Codec fusion composes with
        single-device serving only; a group the mesh may take serves
        dense (host-decoded frames; the batcher decides per group).

        The mesh executable moves the stack/split to the HOST (numpy, zero
        XLA dispatches) and keeps the jit boundary stacked-and-sharded:
        per-frame outputs at an SPMD boundary would each pay a cross-device
        gather (measured ~10x the whole serve), whereas one sharded stacked
        output costs a single device_get.  Host-split answers are therefore
        numpy — bitwise the same frames.  Groups the mesh cannot take
        (:meth:`shardable_batch` fails) fall through to the single-device
        executable inside the same callable."""
        donate = self._resolve_donate(donate)
        fns = self._cache()["fns"]
        key = ("serve_batch", donate, self._mesh_key(mesh), codec)
        if key in fns:
            return fns[key]
        if codec is not None:
            if mesh is not None:
                raise ValueError("codec-fused serving is single-device; "
                                 "mesh groups serve dense frames")
            def serve_wire(params, state, frames, _self=self, _codec=codec):
                return _self.serve_batch_wire(params, state, frames, _codec)
            fns[key] = jax.jit(serve_wire,
                               donate_argnums=(1,) if donate else ())
            return fns[key]
        if mesh is None:
            def serve_batch(params, state, frames, _self=self):
                return _self.serve_batch(params, state, frames)
            fns[key] = jax.jit(serve_batch,
                               donate_argnums=(1,) if donate else ())
            return fns[key]

        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..launch.mesh import batch_spec
        single = self.compiled_serve_batch(donate=donate, mesh=None)
        step = self.compiled_step_n(hoist_io=True, hoist_queries=True,
                                    donate=donate, mesh=mesh)
        frame_sharding = NamedSharding(mesh, P(batch_spec(mesh)))

        def serve_sharded(params, state, frames, _self=self):
            n = len(frames)
            if not _self.shardable_batch(n, state, mesh):
                return single(params, state, frames)
            import numpy as np
            host = jax.device_get(frames)
            stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *host)
            stacked = jax.device_put(
                stacked, jax.tree_util.tree_map(lambda _: frame_sharding,
                                                stacked))
            outs, final = step(params, state, stacked)
            outs = jax.device_get(outs)
            per = tuple(jax.tree_util.tree_map(lambda l, _i=i: l[_i], outs)
                        for i in range(n))
            return per, final

        fns[key] = serve_sharded
        return fns[key]

    # -- stateful streaming serve ----------------------------------------------
    def _serve_tick_fn(self, donate: bool, state_key) -> Callable:
        """Executable behind :meth:`compiled_serve_tick`, addressable by its
        full cache key so reconfigure warming can replicate it (see
        ``reconfig._warm``)."""
        fns = self._cache()["fns"]
        key = ("serve_tick", donate, self.serve_stage, state_key)
        if key not in fns:
            @jax.named_scope("lm_serve_tick")
            def serve_tick(params, state, inputs, _self=self):
                return _self.run(params, state, inputs,
                                 hoist_io=True, hoist_queries=True)
            fns[key] = jax.jit(serve_tick,
                               donate_argnums=(1,) if donate else ())
        return fns[key]

    def compiled_serve_tick(self, state: dict,
                            donate: Optional[bool] = None) -> Callable:
        """Jitted stateful decode tick ``(params, state, inputs) ->
        (outputs, next_state)`` for a ``stream_serving`` plan.

        Unlike :meth:`compiled_serve_batch` — which stacks N independent
        stateless frames — the batch here lives INSIDE the plan state (slot
        axis of the KV/SSM cache plus an active-slot mask), so requests join
        and leave mid-generation without changing the traced program.  The
        cache key therefore carries a distinct fingerprint axis: the state
        pytree's :func:`structure_key` (treedef + leaf shapes/dtypes, which
        covers both the cache layout and the active-slot mask).  Two serve
        configurations with different slot counts or cache structures never
        collide; re-dispatching the same structure never retraces."""
        from .buffers import structure_key
        return self._serve_tick_fn(self._resolve_donate(donate),
                                   structure_key(state))

    # -- compiled deferred segments --------------------------------------------
    def _next_client(self, after: int) -> Optional[int]:
        for i in self.client_idxs:
            if i > after:
                return i
        return None

    def _live_slots(self, pause_idx: int) -> Tuple[int, ...]:
        """Value slots that must survive a pause at ``pause_idx``: written
        by an op before the pause AND read by an op after it.  Static —
        the schedule is topology-fixed — so segment jits carry exactly the
        live values and nothing else."""
        written = {s for op in self.ops[:pause_idx]
                   for s in op.out_slots if s >= 0}
        read = {s for op in self.ops[pause_idx + 1:] for s in op.in_slots}
        return tuple(sorted(written & read))

    def _deferred_segment(self, start: Optional[int]) -> Callable:
        """Pure segment of the deferred walk as one traceable function:
        ``start=None`` runs op 0 → the first query client; ``start=j``
        injects the answer for the client at op ``j`` and runs to the next
        client or the end.  Where the segment stops is static (topology),
        so the caller knows the return shape without looking:

        * pauses again → ``(request, live_vals, outputs, next_state)``
        * completes    → ``(outputs, next_state)``
        """
        def seg(params, state, next_state, live_vals, answer, inputs):
            ctx = PipelineContext(state)
            ctx.next_state = dict(next_state)
            vals: List[Any] = [None] * self.n_slots
            outputs: Dict[str, StreamBuffer] = {}
            if start is None:
                begin = 0
            else:
                for s, v in zip(self._live_slots(start), live_vals):
                    vals[s] = v
                op = self.ops[start]
                if op.out_slots and op.out_slots[0] >= 0:
                    vals[op.out_slots[0]] = answer
                if op.is_sink:
                    outputs[op.name] = answer
                begin = start + 1
            res = self._exec_ops(params, ctx, vals, outputs, inputs, begin,
                                 hoist_io=False, hoist_queries=False,
                                 defer_queries=True)
            if res is None:
                return outputs, ctx.next_state
            idx, request = res
            live = tuple(vals[s] for s in self._live_slots(idx))
            return request, live, outputs, ctx.next_state
        return seg

    def compiled_deferred_segment(self, start: Optional[int]) -> Callable:
        """Jitted :meth:`_deferred_segment`, cached in the fingerprint-keyed
        registry (failover reconnects of a structurally identical client
        pipeline never retrace its segments)."""
        fns = self._cache()["fns"]
        key = ("defer_seg", -1 if start is None else start)
        if key not in fns:
            fns[key] = jax.jit(self._deferred_segment(start))
        return fns[key]

    def run_deferred_compiled(self, params: dict, state: dict,
                              inputs: Optional[Dict[str, StreamBuffer]] = None):
        """Compiled counterpart of :meth:`run_deferred` for plans whose only
        impure elements are query clients (:attr:`deferred_compilable`):
        the walk to the first client is ONE jitted dispatch instead of an
        interpreted per-element walk — bitwise the same frame, minus the
        eager dispatch overhead per element.  Returns a compiled-mode
        :class:`PendingQuery` (its ``resume`` runs jitted segments too)."""
        inputs = inputs or {}
        fn = self.compiled_deferred_segment(None)
        request, live, outputs, next_state = fn(params, state, state,
                                                (), None, inputs)
        return PendingQuery.compiled(self, params, inputs, state, next_state,
                                     live, outputs, self.client_idxs[0],
                                     request)


class PendingQuery:
    """A frame paused mid-schedule at a query client, awaiting its answer.

    Produced by :meth:`ExecutionPlan.run_deferred`; ``request`` is the
    StreamBuffer the client was about to ship.  After the host sends the
    request and the (batched) server answer arrives, ``resume(answer)``
    continues the walk — returning ``(outputs, next_state)`` on completion
    or ``self`` again if a later query client pauses the frame once more.

    The request buffer is retained until the answer is in hand, which is
    what makes serving **fault-tolerant**: ``endpoint`` records where the
    scheduler actually shipped the request, and if that server dies before
    answering, the scheduler re-dispatches the very same ``request`` to the
    next-ranked survivor (``redispatches`` counts the hops) or parks the
    frame until one registers — see DESIGN.md §3.

    Two execution modes, bitwise-identical: the interpreted mode carries the
    live walk (``ctx``/``vals``) and resumes element by element; the
    COMPILED mode (``run_deferred_compiled``) carries only the live slot
    values plus the state pytrees, and ``resume`` runs the next pure
    segment as one jitted dispatch.
    """

    __slots__ = ("plan", "params", "inputs", "ctx", "vals", "outputs",
                 "op_idx", "request", "endpoint", "redispatches",
                 "state", "next_state", "live", "is_compiled",
                 "dseq", "retries", "next_retry")

    def __init__(self, plan: ExecutionPlan, params: dict, inputs: dict,
                 ctx: PipelineContext, vals: List[Any],
                 outputs: Dict[str, StreamBuffer], op_idx: int,
                 request: StreamBuffer):
        self.plan = plan
        self.params = params
        self.inputs = inputs
        self.ctx = ctx
        self.vals = vals
        self.outputs = outputs
        self.op_idx = op_idx
        self.request = request
        #: endpoint the in-flight request was dispatched to (scheduler-owned)
        self.endpoint = None
        #: failover hops this frame survived (scheduler-owned)
        self.redispatches = 0
        #: delivery id + retransmit clock (scheduler-owned, DESIGN.md §10).
        #: ``dseq`` is minted ONCE per logical request and reused verbatim
        #: by every retransmit and failover re-dispatch — idempotence by
        #: dedup rests on the id surviving the frame's whole lifetime.
        self.dseq = None
        self.retries = 0
        self.next_retry = 0
        # compiled-mode fields (PendingQuery.compiled)
        self.state = None
        self.next_state = None
        self.live = ()
        self.is_compiled = False

    @classmethod
    def compiled(cls, plan: ExecutionPlan, params: dict, inputs: dict,
                 state: dict, next_state: dict, live: Tuple,
                 outputs: Dict[str, StreamBuffer], op_idx: int,
                 request: StreamBuffer) -> "PendingQuery":
        pq = cls(plan, params, inputs, None, [], outputs, op_idx, request)
        pq.state = state
        pq.next_state = next_state
        pq.live = live
        pq.is_compiled = True
        return pq

    @property
    def client(self):
        """The tensor_query_client element this frame is paused at."""
        return self.plan.ops[self.op_idx].elem

    def resume(self, answer: StreamBuffer):
        """Inject the server's answer as the paused client's output and run
        the rest of the schedule."""
        if self.is_compiled:
            return self._resume_compiled(answer)
        op = self.plan.ops[self.op_idx]
        if op.out_slots and op.out_slots[0] >= 0:
            self.vals[op.out_slots[0]] = answer
        if op.is_sink:
            self.outputs[op.name] = answer
        res = self.plan._exec_ops(self.params, self.ctx, self.vals,
                                  self.outputs, self.inputs,
                                  self.op_idx + 1, hoist_io=False,
                                  hoist_queries=False, defer_queries=True)
        if res is None:
            return self.outputs, self.ctx.next_state
        self.op_idx, self.request = res
        self.endpoint = None  # the next client's request is not yet in flight
        return self

    def _resume_compiled(self, answer: StreamBuffer):
        """One jitted dispatch for the segment after the paused client."""
        plan = self.plan
        fn = plan.compiled_deferred_segment(self.op_idx)
        nxt = plan._next_client(self.op_idx)
        res = fn(self.params, self.state, self.next_state, self.live,
                 answer, self.inputs)
        if nxt is None:
            outputs, final = res
            return {**self.outputs, **outputs}, final
        request, live, outputs, next_state = res
        self.op_idx = nxt
        self.request = request
        self.live = live
        self.outputs = {**self.outputs, **outputs}
        self.next_state = next_state
        self.endpoint = None
        return self
