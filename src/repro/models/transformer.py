"""Decoder-only LM assembled from per-layer mixer kinds (G/L/R/S) and
dense-or-MoE MLPs, with three entry points sharing one parameter tree:

* train   — full-sequence teacher forcing (no cache)
* prefill — full sequence, returns logits + a decode cache
* decode  — one token against the cache (serve_step)

Two parameter layouts:

* list layout   — params["layers"] = [per-layer dict] (tests, small models)
* stacked layout — params["prefix"/"stack"/"tail"]: the repeating
  layer-pattern unit is stacked over repeats and executed with lax.scan
  (+ per-unit remat).  This is what the production launcher lowers: an
  80-layer model compiles as one scanned unit, not 80 inlined blocks.

``layer_plan`` splits layers into (prefix | R repeats of the pattern unit |
tail) so heterogeneous patterns (gemma3 LLLLLG, deepseek first-dense,
recurrentgemma RRL) scan their homogeneous core.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from . import layers as L
from . import mla as MLA
from . import moe as MOE
from . import rglru as RG
from . import ssm as SSM
from .sharding import shard


# ---------------------------------------------------------------------------
# layer plan (scan grouping)
# ---------------------------------------------------------------------------

def layer_plan(cfg: ModelConfig) -> Tuple[List[int], int, int, List[int]]:
    """-> (prefix_layers, period, repeats, tail_layers).

    Layers [0, first_dense) are structurally unique (dense MLP before MoE) —
    unrolled.  The middle is R repeats of the pattern unit (all same
    structure per unit position).  A remainder tail is unrolled."""
    p = len(cfg.layer_pattern)
    start = cfg.first_dense
    n = cfg.n_layers
    repeats = max(0, (n - start) // p)
    tail_start = start + repeats * p
    return list(range(start)), p, repeats, list(range(tail_start, n))


def kind_at(cfg: ModelConfig, layer: int) -> str:
    return cfg.kind(layer)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def block_init(rng, cfg: ModelConfig, layer: int) -> Dict:
    kind = cfg.kind(layer)
    k1, k2 = jax.random.split(rng, 2)
    p: Dict = {"norm1": L.norm_init(cfg.d_model, cfg)}
    if kind in ("G", "L"):
        p["attn"] = MLA.mla_init(k1, cfg) if cfg.mla else L.attn_init(k1, cfg)
    elif kind == "R":
        p["rec"] = RG.rglru_init(k1, cfg)
    elif kind == "S":
        p["ssm"] = SSM.ssm_init(k1, cfg)
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    if kind == "S":
        return p  # mamba2 blocks have no separate MLP
    p["norm2"] = L.norm_init(cfg.d_model, cfg)
    if cfg.is_moe_layer(layer):
        p["moe"] = MOE.moe_init(k2, cfg)
    else:
        p["mlp"] = L.mlp_init(k2, cfg)
    return p


def init_params(rng, cfg: ModelConfig) -> Dict:
    ks = jax.random.split(rng, cfg.n_layers + 2)
    return {
        "embed": L.embed_init(ks[0], cfg),
        "layers": [block_init(ks[i + 1], cfg, i) for i in range(cfg.n_layers)],
        "final_norm": L.norm_init(cfg.d_model, cfg),
    }


def stack_params(cfg: ModelConfig, params: Dict) -> Dict:
    """list layout -> stacked layout (pure tree ops, works on
    ShapeDtypeStructs under eval_shape too)."""
    prefix, period, repeats, tail = layer_plan(cfg)
    layers = params["layers"]
    stack = []
    for j in range(period):
        unit = [layers[len(prefix) + r * period + j] for r in range(repeats)]
        stack.append(jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *unit)
                     if repeats else None)
    return {
        "embed": params["embed"],
        "final_norm": params["final_norm"],
        "prefix": [layers[i] for i in prefix],
        "stack": stack,
        "tail": [layers[i] for i in tail],
        **({"vis_norm": params["vis_norm"]} if "vis_norm" in params else {}),
    }


def init_params_stacked(rng, cfg: ModelConfig) -> Dict:
    return stack_params(cfg, init_params(rng, cfg))


# ---------------------------------------------------------------------------
# block apply (kind-based)
# ---------------------------------------------------------------------------

def _mixer_train(p, cfg: ModelConfig, kind: str, x):
    if kind in ("G", "L"):
        window = cfg.window if kind == "L" else None
        if cfg.mla:
            return MLA.mla_train(p["attn"], cfg, x)
        return L.attn_train(p["attn"], cfg, x, window)
    if kind == "R":
        return RG.rglru_train(p["rec"], cfg, x)
    if kind == "S":
        return SSM.ssm_train(p["ssm"], cfg, x)
    raise ValueError(kind)


def _mlp_part(p, cfg: ModelConfig, x, serve: bool = False
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """-> (x + mlp(norm(x)), aux).  aux is the router's balance loss in
    training; on the serve path (``serve``: dropless experts) it is the
    experts each token was routed to, [B, S, k]."""
    h = L.apply_norm(p["norm2"], x, cfg)
    if "moe" in p:
        y, aux = (MOE.moe_serve if serve else MOE.apply_moe)(p["moe"], cfg, h)
    else:
        y, aux = L.apply_mlp(p["mlp"], cfg, h), jnp.float32(0)
    return x + y, aux


def block_train(p, cfg: ModelConfig, kind: str, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    h = L.apply_norm(p["norm1"], x, cfg)
    x = x + _mixer_train(p, cfg, kind, h)
    if kind == "S":
        return x, jnp.float32(0)
    return _mlp_part(p, cfg, x)


def block_prefill(p, cfg: ModelConfig, kind: str, x, max_seq: int
                  ) -> Tuple[jnp.ndarray, Dict, jnp.ndarray]:
    """Prefill forward that also emits this layer's decode cache; the
    third value is ``_mlp_part``'s serve-path aux."""
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    h = L.apply_norm(p["norm1"], x, cfg)
    if kind in ("G", "L"):
        window = cfg.window if kind == "L" else None
        if cfg.mla:
            y = MLA.mla_train(p["attn"], cfg, h)
            c_kv, k_rope = MLA._latent(p["attn"], cfg, h, pos)
            cache = MLA.mla_cache_init(cfg, b, max_seq)
            cache["c_kv"] = jax.lax.dynamic_update_slice(
                cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, 0, 0))
            cache["k_rope"] = jax.lax.dynamic_update_slice(
                cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), (0, 0, 0))
        else:
            y = L.attn_train(p["attn"], cfg, h, window)
            _, k, v = L._qkv(p["attn"], cfg, h, pos)
            cache = L.attn_cache_init(cfg, b, max_seq, window)
            size = cache["k"].shape[1]
            if size >= s:
                cache["k"] = jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0))
                cache["v"] = jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0))
            else:  # ring buffer: keep the last `size`, rotated into slot order
                shift = (-(s % size)) % size
                cache["k"] = jnp.roll(k[:, -size:], shift, axis=1)
                cache["v"] = jnp.roll(v[:, -size:], shift, axis=1)
    elif kind == "R":
        y = RG.rglru_train(p["rec"], cfg, h)
        cache = _rglru_prefill_cache(p["rec"], cfg, h)
    elif kind == "S":
        y, cache = SSM.ssm_prefill(p["ssm"], cfg, h)
    else:
        raise ValueError(kind)
    x = x + y
    if kind == "S":
        return x, cache, jnp.float32(0)
    x, aux = _mlp_part(p, cfg, x, serve=True)
    return x, cache, aux


def block_decode(p, cfg: ModelConfig, kind: str, x, cache, pos,
                 with_routes: bool = False, commit=None):
    """One token through one layer; ``with_routes`` adds the experts it was
    routed to ([B, 1, k], None for a layer without experts).  ``commit`` (a
    traced bool scalar) commits the layer's cache writes only where it
    holds: one position row of an attention cache, a recurrent state
    whole."""
    h = L.apply_norm(p["norm1"], x, cfg)
    if kind in ("G", "L"):
        window = cfg.window if kind == "L" else None
        if cfg.mla:
            y, new_cache = MLA.mla_decode(p["attn"], cfg, h, cache, pos,
                                          commit)
        else:
            y, new_cache = L.attn_decode(p["attn"], cfg, h, cache, pos, window,
                                         commit)
    elif kind == "R":
        y, new_cache = RG.rglru_decode(p["rec"], cfg, h, cache, commit)
    elif kind == "S":
        y, new_cache = SSM.ssm_decode(p["ssm"], cfg, h, cache, commit)
    else:
        raise ValueError(kind)
    x = x + y
    routes = None
    if kind != "S":
        x, aux = _mlp_part(p, cfg, x, serve=True)
        routes = aux if "moe" in p else None
    return (x, new_cache, routes) if with_routes else (x, new_cache)


# ---------------------------------------------------------------------------
# cache init
# ---------------------------------------------------------------------------

def layer_cache_init(cfg: ModelConfig, layer: int, batch: int, max_seq: int) -> Dict:
    kind = cfg.kind(layer)
    if kind in ("G", "L"):
        if cfg.mla:
            return MLA.mla_cache_init(cfg, batch, max_seq)
        window = cfg.window if kind == "L" else None
        return L.attn_cache_init(cfg, batch, max_seq, window)
    if kind == "R":
        return RG.rglru_cache_init(cfg, batch)
    if kind == "S":
        return SSM.ssm_cache_init(cfg, batch)
    raise ValueError(kind)


def commit_bytes(cfg: ModelConfig, cache, first_layer: int = 0) -> int:
    """Bytes one decode step commits into ``cache`` (a ``cache_init`` or
    ``stage_cache_init`` tree of arrays or shape structs whose first layer
    is global layer ``first_layer``), from static shapes: one position row
    of each attention leaf, each recurrent leaf whole, and ``pos``."""
    def nbytes(leaf, rows=1):
        return math.prod(leaf.shape) // rows * jnp.dtype(leaf.dtype).itemsize
    total = nbytes(cache["pos"])
    for i, layer in enumerate(cache["layers"]):
        positional = cfg.kind(first_layer + i) in ("G", "L")
        for leaf in jax.tree_util.tree_leaves(layer):
            total += nbytes(leaf, leaf.shape[1] if positional else 1)
    return total


def _advance(pos, commit):
    """The position after a step: one on, where ``commit`` holds."""
    return pos + 1 if commit is None else pos + commit.astype(pos.dtype)


def cache_init(cfg: ModelConfig, batch: int, max_seq: int) -> Dict:
    return {
        "pos": jnp.int32(0),
        "layers": [layer_cache_init(cfg, i, batch, max_seq)
                   for i in range(cfg.n_layers)],
    }


def cache_init_stacked(cfg: ModelConfig, batch: int, max_seq: int) -> Dict:
    prefix, period, repeats, tail = layer_plan(cfg)
    caches = [layer_cache_init(cfg, i, batch, max_seq) for i in range(cfg.n_layers)]
    stack = []
    for j in range(period):
        unit = [caches[len(prefix) + r * period + j] for r in range(repeats)]
        stack.append(jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *unit)
                     if repeats else None)
    return {
        "pos": jnp.int32(0),
        "prefix": [caches[i] for i in prefix],
        "groups": stack,
        "tail": [caches[i] for i in tail],
    }


# ---------------------------------------------------------------------------
# list-layout entry points
# ---------------------------------------------------------------------------

def backbone_train(params, cfg: ModelConfig, x,
                   remat: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    aux_total = jnp.float32(0)
    for i, p in enumerate(params["layers"]):
        fn = jax.checkpoint(block_train, prevent_cse=False,
                            static_argnums=(1, 2)) if remat else block_train
        x, aux = fn(p, cfg, cfg.kind(i), x)
        aux_total = aux_total + aux
    return L.apply_norm(params["final_norm"], x, cfg), aux_total


def lm_train(params, cfg: ModelConfig, tokens,
             remat: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    x = L.embed(params["embed"], cfg, tokens)
    h, aux = backbone_train(params, cfg, x, remat)
    return L.unembed(params["embed"], cfg, h), aux


def lm_decode(params, cfg: ModelConfig, token, cache,
              with_routes: bool = False, commit=None):
    """-> (logits [B, V], cache); ``with_routes`` (a model with expert
    layers) adds the experts each token was routed to in each expert
    layer, [n_moe_layers, B, k].  ``commit`` (a traced bool scalar) commits
    the step's cache writes only where it holds: each layer's own rows or
    state (``block_decode``), and ``pos`` advances by it; an uncommitted
    step returns its cache bitwise unchanged."""
    pos = cache["pos"]
    x = L.embed(params["embed"], cfg, token[:, None])
    new_layers, routes = [], []
    for i, p in enumerate(params["layers"]):
        x, c, r = block_decode(p, cfg, cfg.kind(i), x, cache["layers"][i],
                               pos, with_routes=True, commit=commit)
        new_layers.append(c)
        if with_routes and r is not None:
            routes.append(r[:, 0])
    h = L.apply_norm(params["final_norm"], x, cfg)
    logits = L.unembed(params["embed"], cfg, h)[:, 0]
    new_cache = {"pos": _advance(pos, commit), "layers": new_layers}
    if with_routes:
        return logits, new_cache, jnp.stack(routes)
    return logits, new_cache


def lm_prefill(params, cfg: ModelConfig, tokens, max_seq: Optional[int] = None,
               with_routes: bool = False):
    """-> (last-position logits [B, V], cache); ``with_routes`` adds the
    experts each position was routed to, [n_moe_layers, B, S, k]."""
    x = L.embed(params["embed"], cfg, tokens)
    return lm_prefill_embedded(params, cfg, x, max_seq or tokens.shape[1],
                               with_routes)


def lm_prefill_embedded(params, cfg: ModelConfig, x, max_seq: int,
                        with_routes: bool = False):
    caches: List[Dict] = []
    routes = []
    for i, p in enumerate(params["layers"]):
        x, cache, aux = block_prefill(p, cfg, cfg.kind(i), x, max_seq)
        caches.append(cache)
        if with_routes and "moe" in p:
            routes.append(aux)
    hfin = L.apply_norm(params["final_norm"], x, cfg)
    logits = L.unembed(params["embed"], cfg, hfin[:, -1:])[:, 0]
    cache = {"pos": jnp.int32(x.shape[1]), "layers": caches}
    if with_routes:
        return logits, cache, jnp.stack(routes)
    return logits, cache


# ---------------------------------------------------------------------------
# pipeline-stage entry points (among-device hops, DESIGN.md §8)
#
# A stage is a contiguous slice [lo, hi) of the layer stack running as its
# own pipeline on its own device: stage 0 embeds, the last stage norms and
# unembeds, middle stages map activations to activations.  Layer kinds and
# cache shapes are indexed by GLOBAL layer number, so an N-stage chain runs
# layer-for-layer the identical traced blocks ``lm_decode``/``lm_prefill``
# run — chaining the stages reproduces the monolithic model bitwise (pinned
# in tests/test_pp_staged_serving.py).
# ---------------------------------------------------------------------------

def stage_bounds(cfg: ModelConfig, stage: int, n_stages: int
                 ) -> Tuple[int, int]:
    """Global layer range [lo, hi) owned by ``stage`` of ``n_stages``."""
    if not 0 <= stage < n_stages:
        raise ValueError(f"stage {stage} not in [0, {n_stages})")
    if cfg.n_layers % n_stages:
        raise ValueError(f"n_layers={cfg.n_layers} not divisible by "
                         f"n_stages={n_stages}")
    r = cfg.n_layers // n_stages
    return stage * r, (stage + 1) * r


def stage_params(params: Dict, cfg: ModelConfig, stage: int, n_stages: int
                 ) -> Dict:
    """Slice a full list-layout param tree down to one stage's share.
    ``embed`` rides on the first stage (token embedding) AND the last
    (unembed reads ``params["embed"]`` — tied or ``head``)."""
    lo, hi = stage_bounds(cfg, stage, n_stages)
    out: Dict = {"layers": params["layers"][lo:hi]}
    if stage == 0 or stage == n_stages - 1:
        out["embed"] = params["embed"]
    if stage == n_stages - 1:
        out["final_norm"] = params["final_norm"]
    return out


def stage_cache_init(cfg: ModelConfig, stage: int, n_stages: int, batch: int,
                     max_seq: int) -> Dict:
    """Zero decode cache covering only this stage's layers (its slice of
    the monolithic ``cache_init`` tree, same per-layer shapes)."""
    lo, hi = stage_bounds(cfg, stage, n_stages)
    return {"pos": jnp.int32(0),
            "layers": [layer_cache_init(cfg, i, batch, max_seq)
                       for i in range(lo, hi)]}


def stage_prefill(params, cfg: ModelConfig, stage: int, n_stages: int, x,
                  max_seq: int) -> Tuple[jnp.ndarray, Dict]:
    """Prefill one stage: tokens ``[b, L]`` in for stage 0, activations
    ``[b, L, d]`` for later stages; out is the boundary activations (or
    final-position logits ``[b, vocab]`` on the last stage) plus this
    stage's decode cache."""
    lo, hi = stage_bounds(cfg, stage, n_stages)
    if stage == 0:
        x = L.embed(params["embed"], cfg, x)
    caches: List[Dict] = []
    for j in range(hi - lo):
        x, cache, _ = block_prefill(params["layers"][j], cfg, cfg.kind(lo + j),
                                    x, max_seq)
        caches.append(cache)
    out = x
    if stage == n_stages - 1:
        hfin = L.apply_norm(params["final_norm"], x, cfg)
        out = L.unembed(params["embed"], cfg, hfin[:, -1:])[:, 0]
    return out, {"pos": jnp.int32(x.shape[1]), "layers": caches}


def stage_decode(params, cfg: ModelConfig, stage: int, n_stages: int, x,
                 cache, commit=None) -> Tuple[jnp.ndarray, Dict]:
    """One decode step through one stage: token ``[b]`` in for stage 0,
    activations ``[b, 1, d]`` for later stages; out is activations
    ``[b, 1, d]`` (or logits ``[b, vocab]`` on the last stage) plus the
    advanced stage cache, committed under ``commit`` as in
    ``lm_decode``."""
    lo, hi = stage_bounds(cfg, stage, n_stages)
    pos = cache["pos"]
    if stage == 0:
        x = L.embed(params["embed"], cfg, x[:, None])
    new_layers = []
    for j in range(hi - lo):
        x, c = block_decode(params["layers"][j], cfg, cfg.kind(lo + j), x,
                            cache["layers"][j], pos, commit=commit)
        new_layers.append(c)
    out = x
    if stage == n_stages - 1:
        h = L.apply_norm(params["final_norm"], x, cfg)
        out = L.unembed(params["embed"], cfg, h)[:, 0]
    return out, {"pos": _advance(pos, commit), "layers": new_layers}


def _rglru_prefill_cache(p, cfg: ModelConfig, x) -> Dict:
    u = x @ p["w_rec"]
    u_conv, conv_state = RG._conv4(u, p["conv"])
    a, bx = RG._gates(p, u_conv)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, bx), axis=1)
    return {"h": h[:, -1], "conv": conv_state}


def _ssm_prefill_cache(p, cfg: ModelConfig, x) -> Dict:
    b, s, d = x.shape
    d_inner, hh, hd, n = SSM._dims(cfg)
    proj = x @ p["w_in"]
    z, xbc, dt_raw = SSM._split_proj(cfg, proj)
    xbc_c, conv_state = SSM._causal_conv(xbc, p["conv"])
    xs, B, C = jnp.split(xbc_c, [d_inner, d_inner + n], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    xh = xs.reshape(b, s, hh, hd)
    _, h_final, _ = SSM._ssd_scan(cfg, p, xh, B, C, dt, None)
    return {"h": h_final, "conv": conv_state}


# ---------------------------------------------------------------------------
# stacked-layout entry points (lax.scan over pattern repeats, remat per unit)
# ---------------------------------------------------------------------------

def _unit_kinds(cfg: ModelConfig) -> List[str]:
    prefix, period, _, _ = layer_plan(cfg)
    return [cfg.kind(len(prefix) + j) for j in range(period)]


def backbone_train_stacked(params, cfg: ModelConfig, x,
                           remat: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    prefix, period, repeats, tail = layer_plan(cfg)
    kinds = _unit_kinds(cfg)
    aux_total = jnp.float32(0)
    for i, p in zip(range(len(prefix)), params["prefix"]):
        x, aux = block_train(p, cfg, cfg.kind(i), x)
        aux_total = aux_total + aux

    def unit(carry, unit_params):
        x, aux = carry
        for j in range(period):
            x, a = block_train(unit_params[j], cfg, kinds[j], x)
            aux = aux + a
        return (x, aux), None

    if repeats:
        body = jax.checkpoint(unit, prevent_cse=False) if remat else unit
        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total),
                                         tuple(params["stack"]))
    for i, p in zip(tail, params["tail"]):
        x, aux = block_train(p, cfg, cfg.kind(i), x)
        aux_total = aux_total + aux
    return L.apply_norm(params["final_norm"], x, cfg), aux_total


def lm_train_stacked(params, cfg: ModelConfig, tokens, remat: bool = True):
    x = L.embed(params["embed"], cfg, tokens)
    h, aux = backbone_train_stacked(params, cfg, x, remat)
    return L.unembed(params["embed"], cfg, h), aux


def lm_prefill_stacked(params, cfg: ModelConfig, tokens, max_seq: int,
                       x=None) -> Tuple[jnp.ndarray, Dict]:
    prefix, period, repeats, tail = layer_plan(cfg)
    kinds = _unit_kinds(cfg)
    if x is None:
        x = L.embed(params["embed"], cfg, tokens)
    pre_caches = []
    for i, p in zip(range(len(prefix)), params["prefix"]):
        x, c, _ = block_prefill(p, cfg, cfg.kind(i), x, max_seq)
        pre_caches.append(c)

    def unit(x, unit_params):
        caches = []
        for j in range(period):
            x, c, _ = block_prefill(unit_params[j], cfg, kinds[j], x, max_seq)
            caches.append(c)
        return x, tuple(caches)

    groups = [None] * period
    if repeats:
        body = jax.checkpoint(unit, prevent_cse=False)
        x, stacked = jax.lax.scan(body, x, tuple(params["stack"]))
        groups = list(stacked)
    tail_caches = []
    for i, p in zip(tail, params["tail"]):
        x, c, _ = block_prefill(p, cfg, cfg.kind(i), x, max_seq)
        tail_caches.append(c)
    hfin = L.apply_norm(params["final_norm"], x, cfg)
    logits = L.unembed(params["embed"], cfg, hfin[:, -1:])[:, 0]
    return logits, {"pos": jnp.int32(x.shape[1]), "prefix": pre_caches,
                    "groups": groups, "tail": tail_caches}


def lm_decode_stacked(params, cfg: ModelConfig, token, cache
                      ) -> Tuple[jnp.ndarray, Dict]:
    prefix, period, repeats, tail = layer_plan(cfg)
    kinds = _unit_kinds(cfg)
    pos = cache["pos"]
    x = L.embed(params["embed"], cfg, token[:, None])
    new_prefix = []
    for i, p, c in zip(range(len(prefix)), params["prefix"], cache["prefix"]):
        x, nc = block_decode(p, cfg, cfg.kind(i), x, c, pos)
        new_prefix.append(nc)

    def unit(x, scanned):
        unit_params, unit_cache = scanned
        new_caches = []
        for j in range(period):
            x, nc = block_decode(unit_params[j], cfg, kinds[j], x,
                                 unit_cache[j], pos)
            new_caches.append(nc)
        return x, tuple(new_caches)

    new_groups = [None] * period
    if repeats:
        x, stacked = jax.lax.scan(unit, x,
                                  (tuple(params["stack"]), tuple(cache["groups"])))
        new_groups = list(stacked)
    new_tail = []
    for i, p, c in zip(tail, params["tail"], cache["tail"]):
        x, nc = block_decode(p, cfg, cfg.kind(i), x, c, pos)
        new_tail.append(nc)
    h = L.apply_norm(params["final_norm"], x, cfg)
    logits = L.unembed(params["embed"], cfg, h)[:, 0]
    return logits, {"pos": pos + 1, "prefix": new_prefix,
                    "groups": new_groups, "tail": new_tail}
