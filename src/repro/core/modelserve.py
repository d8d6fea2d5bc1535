"""Model serving elements — a real autoregressive LM behind the query fabric.

``model_serve`` puts an actual ``models/`` network (transformer, rGLRU
hybrid, ...) behind ``tensor_query_serversrc ! model_serve !
tensor_query_serversink``.  Decode state is PLAN STATE: a slot-stacked
KV-cache / recurrent-state pytree plus an active-slot mask, carried across
ticks through the pipeline state dict.  Continuous batching happens INSIDE
one jitted decode dispatch — requests join (slot allocation: the
prefilled cache written into its slot row on the device, its lanes merged
in under the admit mask) and leave (slots freed when ``remaining`` hits
zero) mid-generation without retracing, because the traced programs only
see fixed slot-axis shapes (DESIGN.md §7).

Parity-by-construction: the decode tick runs each slot as an independent
``b=1`` ``lm_decode`` via ``jax.vmap`` over the slot axis — the same traced
program a per-request sequential decode runs, each slot an independent row
of every batched matmul — and commits state at the row: each layer writes
``where(active, new row, old row)`` at the slot's position (a recurrent
state, whole) and ``pos`` advances by ``active``, so an inactive slot's
cache comes back bitwise unchanged and continuous-batched output is
bitwise the sequential output regardless of join/leave order (pinned in
tests/test_model_serving.py and tests/test_slot_commit.py).  A model
with routed experts is the one exception to slot independence inside
the tick: its expert layers (``models/moe.py`` ``moe_serve``) take the
vmapped slot batch as one group of tokens, so each held expert runs once
per tick for the slots routed to it; each token's own rows are still
computed alone.

The host half (prefill, admit lanes, row writes) lives on the element too:
the StreamingQueryBatcher calls ``host_prefill`` when a request arrives,
``build_admit``/``empty_admit`` each tick and ``write_rows`` on a tick with
joiners, and reads the (token, emitted, finished) lanes the dispatch
captured at the serversink.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import tracing
from .buffers import StreamBuffer
from .element import Element, PipelineContext, register_element
from .formats import Caps

__all__ = ["ModelServeElement", "ModelServeStageElement", "TokenPromptSrc",
           "SERVE_MODELS", "register_serve_model"]

# Preset registry: ``model_serve model=<key>`` resolves through here, so
# pipeline descriptions stay gst-launch strings.  Values are zero-arg
# callables returning a ModelConfig (lazy: configs import only on use).
SERVE_MODELS: Dict[str, Callable] = {}


def register_serve_model(key: str, cfg_fn: Callable):
    SERVE_MODELS[key] = cfg_fn
    return cfg_fn


def _default_presets():
    """Tier-1 CPU presets: a small dense transformer (flash-attention on the
    serve path) and one recurrent (rGLRU hybrid) so the stateful-plan
    contract covers both KV-cache and SSM-style state."""
    if "stablelm-smoke-flash" not in SERVE_MODELS:
        def _stablelm():
            import dataclasses
            from ..configs import stablelm_1_6b
            return dataclasses.replace(stablelm_1_6b.config().smoke(),
                                       use_flash_attn=True)
        SERVE_MODELS["stablelm-smoke-flash"] = _stablelm
    if "recurrentgemma-smoke" not in SERVE_MODELS:
        def _rglru():
            from ..configs import recurrentgemma_9b
            return recurrentgemma_9b.config().smoke()
        SERVE_MODELS["recurrentgemma-smoke"] = _rglru


@register_element("model_serve")
class ModelServeElement(Element):
    """Autoregressive decode as plan state with continuous batching.

    Props (gst-launch strings, coerced like TestSrc):
      * ``model``   — SERVE_MODELS preset key
      * ``slots``   — decode-batch capacity S (the slot axis of every state
                      leaf; requests beyond S wait in the batcher's FIFO)
      * ``max_seq`` — KV-cache length (prompt length + generation budget
                      must fit)

    State (pytree, per slot):
      ``cache[S, ...]``   — slot-stacked b=1 decode caches
      ``active[S]``       — bool mask, THE fingerprint-relevant lane
      ``token[S]``        — last emitted token (next decode input)
      ``remaining[S]``    — decode steps left before the slot frees

    Input frame (injected by the batcher at the hoisted serversrc):
      ``(admit_mask[S], admit_tok[S], admit_rem[S])`` on a tick with joins
      (their caches are already in their slot rows: ``write_rows``), or
      the structurally tiny ``(mask,)`` + ``meta={"empty": True}`` on a
      no-join tick (static aux — its own cached trace)
    Output frame (captured at the serversink):
      ``(token[S], emitted[S], finished[S])``, and for a model with experts
      ``load[n_moe_layers, E_held]``: the tick's tokens routed to each
      expert this chip holds, per expert layer (``note_routes`` counts it)
    """

    #: streaming serve workload: ExecutionPlan routes this pipeline through
    #: the stateful ``compiled_serve_tick`` path, the scheduler wires a
    #: StreamingQueryBatcher instead of the stateless stack-scan batcher
    is_stream_serve = True

    def __init__(self, name=None, model="stablelm-smoke-flash", slots=8,
                 max_seq=64, **props):
        super().__init__(name=name, **props)
        self.model = str(props.get("model", model))
        self.slots = int(props.get("slots", slots))
        self.max_seq = int(props.get("max_seq", max_seq))
        self._cfg = None
        self._prefill_jit = None
        self._commit_bytes: Optional[int] = None
        #: jitted row writes by donation (``row_writer``)
        self._row_writers: Dict[bool, Callable] = {}
        #: routed-expert counters of a model with experts (``note_routes``)
        self.moe_counters: Dict[str, int] = {}

    # -- config / cache templates (host-side, cached) -------------------------
    @property
    def cfg(self):
        if self._cfg is None:
            _default_presets()
            try:
                self._cfg = SERVE_MODELS[self.model]()
            except KeyError as e:
                raise KeyError(
                    f"model_serve model={self.model!r} not registered; "
                    f"known: {sorted(SERVE_MODELS)}") from e
        return self._cfg

    def _cache_template(self):
        """Zero b=1 decode cache: the per-slot state an admitted request's
        prefilled cache must structurally match."""
        from ..models import transformer
        return transformer.cache_init(self.cfg, 1, self.max_seq)

    def negotiate(self, in_caps):
        return [Caps(media="other/tensors")]

    # -- params / state -------------------------------------------------------
    def init_params(self, rng) -> dict:
        from ..models import transformer
        return transformer.init_params(rng, self.cfg)

    def init_state(self) -> dict:
        s = self.slots
        cache = jax.tree_util.tree_map(
            lambda l: jnp.zeros((s,) + tuple(jnp.shape(l)), l.dtype),
            self._cache_template())
        return {"cache": cache,
                "active": jnp.zeros((s,), jnp.bool_),
                "token": jnp.zeros((s,), jnp.int32),
                "remaining": jnp.zeros((s,), jnp.int32)}

    # -- the jitted decode tick ----------------------------------------------
    def apply(self, params, inputs: List[StreamBuffer],
              ctx: PipelineContext = None) -> List[StreamBuffer]:
        from ..models import transformer
        cfg = self.cfg
        st = ctx.get_state(self.name)
        admit = inputs[0].tensors

        # 1. admit: merge the joiners' lanes under the admit mask.  Their
        #    prefilled caches are already in their slot rows (``write_rows``
        #    ran before this dispatch).  A no-join tick carries the
        #    STRUCTURALLY tiny empty bundle (mask only — ``meta["empty"]``
        #    is static aux), so the steady-state decode tick skips the
        #    merge.
        cache = st["cache"]
        if inputs[0].meta.get("empty"):
            token = st["token"]
            remaining, active = st["remaining"], st["active"]
        else:
            with jax.named_scope("lm_admit_merge"):
                admit_mask, admit_tok, admit_rem = admit
                token = jnp.where(admit_mask, admit_tok, st["token"])
                remaining = jnp.where(admit_mask, admit_rem,
                                      st["remaining"])
                active = st["active"] | admit_mask

        # 2. decode tick: each slot is an independent b=1 lm_decode
        #    (``step_slots``).  A model with experts routes the whole slot
        #    batch as one group (``moe_serve``'s vmap rule) and counts the
        #    held experts' load.
        def step(c, tok, act):
            if cfg.n_experts:
                logits, new_c, routes = transformer.lm_decode(
                    params, cfg, tok[None], c, with_routes=True, commit=act)
                load = self._held_load(routes) * act.astype(jnp.int32)
            else:
                logits, new_c = transformer.lm_decode(params, cfg, tok[None],
                                                      c, commit=act)
                load = None
            new_tok = jnp.argmax(logits[0], axis=-1).astype(jnp.int32)
            return new_c, jnp.where(act, new_tok, tok), load

        cache, token, load = self.step_slots(step, cache, token, active)

        # 3. retire: a slot leaves the batch the tick its budget hits zero
        emitted = active
        rem_after = remaining - active.astype(jnp.int32)
        finished = active & (rem_after <= 0)
        ctx.set_state(self.name, {
            "cache": cache,
            "active": active & ~finished,
            "token": token,
            "remaining": jnp.maximum(rem_after, 0),
        })
        lanes = (token, emitted, finished)
        if load is not None:
            lanes += (jnp.sum(load, axis=0),)
        return [inputs[0].with_(tensors=lanes, meta={})]

    @staticmethod
    def step_slots(step, cache, x, active):
        """The slot step both serve elements' ticks run: ``step(c, x, act)
        -> (new_c, *outs)`` on each slot as an independent b=1 program — the
        same traced program sequential per-request decode runs — vmapped
        over the slot axis, so the S slots' matmuls fuse into batched
        contractions (the continuous-batching throughput lever) while each
        slot's values stay the per-request values (slot rows are independent
        rows of every batched matmul — bitwise parity is pinned in
        tests/test_model_serving.py).  ``step`` commits its own cache under
        ``act`` (``lm_decode``/``stage_decode``'s ``commit``): each layer
        selects the one row it writes, or its recurrent state, and ``pos``
        advances by ``act``, so an inactive slot keeps its cache bit for
        bit and the table is never selected whole.  Returns ``(cache,
        *outs)`` stacked over slots."""
        return jax.vmap(step)(cache, x, active)

    @property
    def commit_bytes(self) -> int:
        """Bytes one tick's cache commit writes over the slot table, from
        static shapes: ``slots`` x one position row of every attention
        leaf, plus each recurrent leaf and ``pos`` whole
        (``transformer.commit_bytes``)."""
        from ..models import transformer
        if self._commit_bytes is None:
            one = transformer.commit_bytes(
                self.cfg, jax.eval_shape(self._cache_template),
                self._first_layer())
            self._commit_bytes = self.slots * one
        return self._commit_bytes

    def _first_layer(self) -> int:
        """Global index of the first layer this element's cache holds."""
        return 0

    # -- routed experts -------------------------------------------------------
    def _held_load(self, routes):
        """routes [n_moe, ..., k] (global expert ids) -> tokens routed to
        each held expert, [n_moe, E_held] int32."""
        held = self.cfg.n_experts_held
        hit = jax.nn.one_hot(routes.reshape(routes.shape[0], -1), held,
                             dtype=jnp.int32)
        return jnp.sum(hit, axis=1)

    def note_routes(self, load, tokens: int, tick: bool) -> Dict[str, int]:
        """Add one prefill's or decode tick's routing to ``moe_counters``:
        ``moe_routed_held`` (assignments to held experts), ``moe_routed_total``
        (all assignments: tokens x top_k x expert layers) and, over decode
        ticks, ``moe_load_max`` (the most tokens one held expert took in one
        layer of one tick).  Returns this call's three numbers."""
        load = np.asarray(load)
        mine = {"moe_routed_held": int(load.sum()),
                "moe_routed_total": tokens * self.cfg.top_k * load.shape[0],
                "moe_load_max": int(load.max()) if load.size else 0}
        c = self.moe_counters
        for key in ("moe_routed_held", "moe_routed_total"):
            c[key] = c.get(key, 0) + mine[key]
        c.setdefault("moe_load_max", 0)
        if tick:
            c["moe_load_max"] = max(c["moe_load_max"], mine["moe_load_max"])
        return mine

    # -- host half (StreamingQueryBatcher calls) ------------------------------
    def active_slots(self, state) -> int:
        """Occupied decode slots right now — the serve-capacity half of the
        broker's scaling signal (DESIGN.md §9: a streaming server's load is
        its queue depth PLUS the streams already holding slots across
        ticks).  Reads the plan-state active mask; cheap enough for the
        per-tick heartbeat.

        Slot admission is where tenant priority acts (the batcher's waiting
        pool orders by the admission record's ``(priority, deadline,
        arrival)`` key); once a stream holds a slot it is NEVER evicted
        before its ``finished`` lane fires — preemption happens only at
        generation boundaries, so a slot's cache lineage stays intact."""
        st = state.get(self.name, {})
        active = st.get("active")
        if active is None:
            return 0
        import numpy as _np
        return int(_np.asarray(jax.device_get(active)).sum())

    def host_prefill(self, params, prompt):
        """Prefill one request: prompt int32[L] -> (first token int, b=1
        decode cache).  Jitted per prompt length (element-local cache, NOT
        the plan exec cache — the retrace set is per-length and bounded by
        the workload, not the topology)."""
        from ..models import transformer
        if self._prefill_jit is None:
            cfg, max_seq = self.cfg, self.max_seq

            @jax.named_scope("lm_prefill")
            def prefill(p, toks):
                if cfg.n_experts:
                    logits, cache, routes = transformer.lm_prefill(
                        p, cfg, toks[None], max_seq, with_routes=True)
                    load = self._held_load(routes)
                else:
                    logits, cache = transformer.lm_prefill(p, cfg, toks[None],
                                                           max_seq)
                    load = None
                tok = jnp.argmax(logits[0], axis=-1).astype(jnp.int32)
                return tok, cache, load
            self._prefill_jit = jax.jit(prefill)
        with tracing.span("lm.prefill") as sp:
            sp.set(n=len(prompt))
            tok, cache, load = self._prefill_jit(
                params, jnp.asarray(prompt, jnp.int32))
            if load is not None:
                tok, load = jax.device_get((tok, load))
                sp.set(**self.note_routes(load, len(prompt), tick=False))
            return int(tok), cache

    def empty_admit(self) -> StreamBuffer:
        """No-join tick: a structurally TINY bundle (mask only, flagged by
        static meta) — the steady-state decode tick must not ship a zero
        slot-stacked cache across the host edge just to say 'nobody
        joined'.

        A FRESH buffer (fresh meta dict) every call: ``apply`` hands
        ``inputs[0].with_(...)`` downstream and the serversink routing
        idiom re-attaches meta, so one cached buffer shared across every
        no-join tick of every stage would let any consumer's meta mutation
        corrupt all later ticks.  The mask ndarray is shared but
        write-protected — aliasing it is safe, writing it raises."""
        if getattr(self, "_empty_mask", None) is None:
            mask = np.zeros((self.slots,), np.bool_)
            mask.flags.writeable = False
            self._empty_mask = mask
        return StreamBuffer(tensors=(self._empty_mask,),
                            meta={"empty": True})

    def build_admit(self, admits) -> StreamBuffer:
        """Assemble the admit lanes for one tick.  ``admits`` is a list of
        ``(slot, first_token, remaining, b1_cache)``; the bundle carries the
        joiners' mask, first tokens and budgets over the slot axis (zero
        outside the mask).  Their caches go into the slot table through
        ``write_rows``, never through the bundle."""
        if not admits:
            return self.empty_admit()
        with tracing.span("lm.admit.build") as sp:
            sp.set(n=len(admits))
            s = self.slots
            mask = np.zeros((s,), np.bool_)
            tok = np.zeros((s,), np.int32)
            rem = np.zeros((s,), np.int32)
            for slot, t, r, _ in admits:
                mask[slot] = True
                tok[slot] = t
                rem[slot] = r
        return StreamBuffer(tensors=(mask, tok, rem), meta={})

    def write_rows(self, state: dict, rows) -> dict:
        """Write each joiner's prefilled b=1 cache into its slot row of the
        slot table, on the device; returns the element state with the rows
        written.  ``rows`` is a list of ``(slot, b1_cache)``.  A
        host-resident (numpy) cache is shipped by the call."""
        write = self.row_writer()
        cache = state["cache"]
        for slot, row in rows:
            cache = write(cache, np.int32(slot), row)
        return {**state, "cache": cache}

    def row_writer(self, donate: Optional[bool] = None) -> Callable:
        """The jitted row write behind ``write_rows``: ``(table, slot,
        b1_cache) -> table``, one ``dynamic_update_index_in_dim`` per cache
        leaf with the slot a traced int32 scalar, so one executable serves
        every joiner and every joiner count, whatever the cache pytree.
        The table is donated where the plan donates its state
        (``donate=None``), so the write happens in place and moves one
        row."""
        from .plan import ExecutionPlan
        donate = ExecutionPlan._resolve_donate(donate)
        fn = self._row_writers.get(donate)
        if fn is None:
            def write(table, slot, row):
                leaves, treedef = jax.tree_util.tree_flatten(table)
                return jax.tree_util.tree_unflatten(treedef, [
                    jax.lax.dynamic_update_index_in_dim(t, r.astype(t.dtype),
                                                        slot, 0)
                    for t, r in zip(leaves, jax.tree_util.tree_leaves(row))])
            fn = self._row_writers[donate] = jax.jit(
                write, donate_argnums=(0,) if donate else ())
        return fn


@register_element("model_serve_stage")
class ModelServeStageElement(ModelServeElement):
    """One pipeline-parallel stage of a model behind the query fabric
    (DESIGN.md §8): layers ``[stage*R/N, (stage+1)*R/N)`` of the preset
    plus that slice of the slot-stacked decode cache as plan state.  The
    first stage embeds tokens, the last norms + unembeds; per-slot
    boundary activations hop stage → stage over the pub/sub + query
    fabric, driven by the StagedStreamingBatcher on stage 0.

    State is the stage cache ONLY — the coordinator owns the slot table
    (active/token/remaining lanes) and ships ``active`` as a tensor each
    hop, so downstream stages are pure cache-holders whose stale rows are
    inert until re-admitted.

    Input frame (a hop bundle assembled host-side):
      ``(x_in[S,...], active[S])`` on every tick.  ``x_in`` is ``token[S]
      int32`` on stage 0, acts ``[S, 1, d]`` after.  Joiners' parked b=1
      caches are already in their slot rows (``write_rows`` ran before the
      hop), so one hop program serves join and steady ticks alike.
    Output frame: next-stage acts ``[S, 1, d]`` (zeroed where inactive),
      or ``token[S] int32`` from the last stage.
    """

    #: stage pipelines get hop-serving batchers, not the client-facing
    #: streaming lifecycle (scheduler._wire dispatches on this + stage)
    is_stage_serve = True

    def __init__(self, name=None, model="stablelm-smoke-flash", slots=8,
                 max_seq=64, stage=0, n_stages=1, **props):
        super().__init__(name=name, model=model, slots=slots,
                         max_seq=max_seq, **props)
        self.stage = int(props.get("stage", stage))
        self.n_stages = int(props.get("n_stages", n_stages))

    @property
    def is_first(self) -> bool:
        return self.stage == 0

    @property
    def is_last(self) -> bool:
        return self.stage == self.n_stages - 1

    def _cache_template(self):
        from ..models import transformer
        return transformer.stage_cache_init(self.cfg, self.stage,
                                            self.n_stages, 1, self.max_seq)

    def _first_layer(self) -> int:
        from ..models import transformer
        return transformer.stage_bounds(self.cfg, self.stage,
                                        self.n_stages)[0]

    # -- params / state -------------------------------------------------------
    def init_params(self, rng) -> dict:
        """Init the FULL model from ``rng`` then slice this stage's share.
        Every stage pipeline puts its model element at the same position
        (``ssrc ! stage ! ssink``), so Pipeline.init hands each stage the
        SAME sub-rng the monolithic server's model element gets — the full
        trees are identical and the slices compose back to the monolithic
        params exactly (the staged-vs-single bitwise pin rests on this)."""
        from ..models import transformer
        full = transformer.init_params(rng, self.cfg)
        return transformer.stage_params(full, self.cfg, self.stage,
                                        self.n_stages)

    def init_state(self) -> dict:
        return {"cache": super().init_state()["cache"]}

    # -- the jitted stage hop -------------------------------------------------
    def apply(self, params, inputs: List[StreamBuffer],
              ctx: PipelineContext = None) -> List[StreamBuffer]:
        from ..models import transformer
        cfg = self.cfg
        x_in, active = inputs[0].tensors
        stage, n_stages = self.stage, self.n_stages

        def step(c, x, act):
            out, new_c = transformer.stage_decode(params, cfg, stage,
                                                  n_stages, x[None], c,
                                                  commit=act)
            if stage == n_stages - 1:
                y = jnp.argmax(out[0], axis=-1).astype(jnp.int32)
                return new_c, jnp.where(act, y, 0)
            return new_c, jnp.where(act, out[0], jnp.zeros_like(out[0]))

        cache, y = self.step_slots(step, ctx.get_state(self.name)["cache"],
                                   x_in, active)
        ctx.set_state(self.name, {"cache": cache})
        return [inputs[0].with_(tensors=(y,), meta={})]

    # -- host half (stage-local prefill/replay) -------------------------------
    def host_stage_prefill(self, params, x):
        """Stage-local prefill: tokens int32[L] (stage 0) or boundary acts
        float[1, L, d] -> (boundary out, b=1 stage cache).  Jitted per
        input shape (element-local cache, workload-bounded like
        ``host_prefill``).  The last stage argmaxes inside the jit — the
        same program position the monolithic ``host_prefill`` uses."""
        from ..models import transformer
        if getattr(self, "_stage_prefill_jits", None) is None:
            self._stage_prefill_jits = {}
        x = np.asarray(x)
        key = (x.shape, str(x.dtype))
        fn = self._stage_prefill_jits.get(key)
        if fn is None:
            cfg, max_seq = self.cfg, self.max_seq
            stage, n_stages = self.stage, self.n_stages

            def prefill(p, xx):
                if stage == 0:
                    xx = xx[None]       # [L] tokens -> [1, L]
                out, cache = transformer.stage_prefill(p, cfg, stage,
                                                       n_stages, xx, max_seq)
                if stage == n_stages - 1:
                    out = jnp.argmax(out[0], axis=-1).astype(jnp.int32)
                return out, cache
            fn = self._stage_prefill_jits[key] = jax.jit(prefill)
        out, cache = fn(params, jnp.asarray(x))
        return np.asarray(out), cache

    def host_stage_decode(self, params, x, cache):
        """One b=1 decode step through this stage against a parked cache —
        the stage-local REPLAY primitive (DESIGN.md §8): re-running the
        retained boundary activations through this rebuilds a dead stage's
        cache without touching any other stage, bitwise by construction
        (identical traced program on identical inputs)."""
        from ..models import transformer
        if getattr(self, "_stage_decode_jit", None) is None:
            cfg = self.cfg
            stage, n_stages = self.stage, self.n_stages

            def decode(p, xx, c):
                if stage == 0:
                    xx = xx.reshape((1,)).astype(jnp.int32)
                out, new_c = transformer.stage_decode(p, cfg, stage,
                                                      n_stages, xx, c)
                if stage == n_stages - 1:
                    out = jnp.argmax(out[0], axis=-1).astype(jnp.int32)
                return out, new_c
            self._stage_decode_jit = jax.jit(decode)
        out, cache = self._stage_decode_jit(params, jnp.asarray(x), cache)
        return np.asarray(out), cache

    def host_stage_decode_idempotent(self, params, x, cache, hop_id=None):
        """``host_stage_decode`` with at-most-once effect per ``hop_id``
        (the hop's §10 delivery id): a replayed hop whose id was already
        served returns the memoized (out, cache) instead of advancing the
        parked cache a second time.  This is the stage element's backstop
        BENEATH the batcher's dedup window — a duplicate that slips past
        an evicted window still cannot double-step generation state.
        ``hop_id=None`` (delivery off) is plain ``host_stage_decode``."""
        if hop_id is None:
            return self.host_stage_decode(params, x, cache)
        if getattr(self, "_hop_memo", None) is None:
            from collections import OrderedDict
            self._hop_memo = OrderedDict()
        hit = self._hop_memo.get(hop_id)
        if hit is not None:
            return hit
        out = self.host_stage_decode(params, x, cache)
        self._hop_memo[hop_id] = out
        while len(self._hop_memo) > 64:
            self._hop_memo.popitem(last=False)
        return out


@register_element("token_prompt_src")
class TokenPromptSrc(Element):
    """Deterministic streaming-workload source: emits one prompt request per
    frame, cycling through ``prompts`` ("1,2,3;4,5" — ';'-separated prompt
    lists) and ``gens`` ("6;4" — total tokens to generate per request),
    tagging ``gen`` into meta for the streaming server.  The frame counter
    lives in pipeline state (TestSrc idiom) so soak workloads replay
    deterministically.

    Host-impure on purpose: per-frame ``gen`` meta and prompt-list cycling
    are host decisions (meta is static pytree aux — a compiled deferred
    segment would bake one gen per trace), so client pipelines carrying
    this source keep the interpreted deferral path."""

    host_impure = True
    n_sink_pads = 0

    def __init__(self, name=None, prompts="1,2,3", gens="4", **props):
        super().__init__(name=name, **props)
        self.prompts = str(props.get("prompts", prompts))
        self.gens = str(props.get("gens", gens))
        self._prompt_list = [
            tuple(int(t) for t in p.split(",") if t)
            for p in self.prompts.split(";") if p]
        self._gen_list = [int(g) for g in self.gens.split(";") if g]

    def negotiate(self, in_caps):
        return [Caps(media="other/tensors")]

    def init_state(self):
        return {"frame": jnp.int32(0)}

    def apply(self, params, inputs, ctx: PipelineContext = None):
        i = int(ctx.get_state(self.name)["frame"])
        prompt = self._prompt_list[i % len(self._prompt_list)]
        gen = self._gen_list[i % len(self._gen_list)]
        ctx.set_state(self.name, {"frame": jnp.int32(i + 1)})
        return [StreamBuffer(tensors=(jnp.asarray(prompt, jnp.int32),),
                             meta={"gen": gen})]
