"""Mixture-of-Experts layer: router + expert dispatch + optional shared
experts (DeepSeek-V2), on three paths that share one router (``route``).

* ``moe_serve`` — prefill and decode: dropless.  Every token reaches each of
  its top-k experts that this chip holds (``experts_held`` of ``n_experts``,
  a contiguous share starting at ``first``); the router still scores all
  ``n_experts``, and absent experts' parts are left out, as on one chip of an
  expert-parallel deployment.  The held experts run as one grouped dispatch:
  assignments sorted by expert, cut into blocks of rows of one expert each,
  and a loop over the blocks that exist, so the work follows the routed
  assignments rather than tokens x held experts.  Under ``jax.vmap`` (the
  serve tick's per-slot map) a ``custom_vmap`` rule treats the whole slot
  batch as one group of tokens.
* ``_apply_moe_dense`` — training on one device: capacity-bounded sort +
  gather (each expert takes its first ``capacity_factor`` share of tokens);
  gathers are bandwidth, not FLOPs, so HLO_FLOPs stays close to 6·N_active·D.
* ``_apply_moe_shard_map`` — the production mesh: expert-parallel over the
  ``model`` axis, capacity-bounded like the dense path.

Routing (``route``): softmax scores in float32; with ``n_group > 1`` the
group-limited greedy selection (a group scores its best expert, the best
``topk_group`` groups stay, the top-k is taken among their experts); the
top-k weights renormalised (``norm_topk_prob``) or else scaled by
``routed_scaling_factor``.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from .config import ModelConfig
from .layers import dense_init, _act
from .sharding import shard


def moe_init(rng, cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    f = cfg.d_ff_expert or cfg.d_ff
    e = cfg.n_experts_held
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 5)
    p = {
        "router": dense_init(ks[0], d, cfg.n_experts, jnp.float32, scale=0.02),
        "w_up": (jax.random.normal(ks[1], (e, d, f)) * d ** -0.5).astype(dt),
        "w_gate": (jax.random.normal(ks[2], (e, d, f)) * d ** -0.5).astype(dt),
        "w_down": (jax.random.normal(ks[3], (e, f, d)) * f ** -0.5).astype(dt),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_up": dense_init(k1, d, fs, dt),
            "w_gate": dense_init(k2, d, fs, dt),
            "w_down": dense_init(k3, fs, d, dt),
        }
    return p


def route(router: jnp.ndarray, cfg: ModelConfig, xt: jnp.ndarray
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """xt [T, d] -> (weights [T, k] f32, experts [T, k] int32, probs [T, E]).
    Experts are global ids in ``[0, n_experts)``."""
    t = xt.shape[0]
    e, k = cfg.n_experts, cfg.top_k
    logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)   # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    scores = probs
    if cfg.n_group > 1 and cfg.topk_group < cfg.n_group:
        g = cfg.n_group
        best = jnp.max(probs.reshape(t, g, e // g), axis=-1)        # [T, G]
        _, top_groups = jax.lax.top_k(best, cfg.topk_group)
        keep = jnp.any(top_groups[:, :, None] == jnp.arange(g), axis=1)
        scores = jnp.where(jnp.repeat(keep, e // g, axis=1), probs, 0.0)
    topw, topi = jax.lax.top_k(scores, k)                           # [T, k]
    if cfg.norm_topk_prob:
        topw = topw / jnp.sum(topw, -1, keepdims=True)
    else:
        topw = topw * cfg.routed_scaling_factor
    return topw, topi.astype(jnp.int32), probs


def block_rows(cfg: ModelConfig, tokens: int) -> int:
    """Rows of one block of the grouped dispatch: a multiple of 8 near the
    mean assignments per expert (8 at decode, ~40 for a 1k prefill)."""
    mean = -(-tokens * cfg.top_k // max(cfg.n_experts, 1))
    return max(8, min(256, -(-mean // 8) * 8))


def _held_experts(p: Dict, cfg: ModelConfig, xt, topw, topi, first: int):
    """Routed part of the held experts ``[first, first + E_held)``: every
    assignment to them computed once (dropless), float32 [T, d]."""
    t, d = xt.shape
    k = cfg.top_k
    e_held = p["w_up"].shape[0]
    n = t * k
    bm = block_rows(cfg, t)
    local = topi.reshape(n) - first
    key = jnp.where((local >= 0) & (local < e_held), local, e_held)
    order = jnp.argsort(key, stable=True)           # assignments by expert
    counts = jnp.bincount(key, length=e_held + 1)[:e_held]
    row_start = jnp.cumsum(counts) - counts
    blocks = (counts + bm - 1) // bm                # blocks of each expert
    blk_end = jnp.cumsum(blocks)
    weights = topw.reshape(n)

    def block(b, acc):
        e = jnp.sum(blk_end <= b)                   # the block's expert
        r = (b - (blk_end[e] - blocks[e])) * bm + jnp.arange(bm)
        valid = r < counts[e]
        a = order[jnp.clip(row_start[e] + r, 0, n - 1)]
        tok = a // k
        xb = jnp.take(xt, tok, axis=0)
        h = _act(cfg, xb @ p["w_gate"][e]) * (xb @ p["w_up"][e])
        yb = (h @ p["w_down"][e]).astype(jnp.float32)
        yb = yb * jnp.where(valid, weights[a], 0.0)[:, None]
        return acc.at[tok].add(yb)
    return jax.lax.fori_loop(0, blk_end[-1], block,
                             jnp.zeros((t, d), jnp.float32))


def _shared_experts(p: Dict, cfg: ModelConfig, xt):
    sp = p["shared"]
    h = _act(cfg, xt @ sp["w_gate"]) * (xt @ sp["w_up"])
    return (h @ sp["w_down"]).astype(jnp.float32)


def moe_tokens(p: Dict, cfg: ModelConfig, xt: jnp.ndarray, first: int = 0,
               shared: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The serving layer on one group of tokens: xt [T, d] -> (y [T, d],
    routed experts [T, k]).  ``shared=False`` leaves the shared experts out
    (a share's routed part alone)."""
    with jax.named_scope("moe_route"):
        topw, topi, _ = route(p["router"], cfg, xt)
    with jax.named_scope("moe_experts"):
        y = _held_experts(p, cfg, xt, topw, topi, first)
    if shared and "shared" in p:
        y = y + _shared_experts(p, cfg, xt)
    return y.astype(xt.dtype), topi


@functools.lru_cache(maxsize=None)
def _serve_fn(cfg: ModelConfig, first: int):
    def plain(p, x):
        y, topi = moe_tokens(p, cfg, x.reshape(-1, x.shape[-1]), first)
        return y.reshape(x.shape), topi.reshape(x.shape[:-1] + (cfg.top_k,))

    fn = custom_vmap(plain)

    @fn.def_vmap
    def _grouped(axis_size, in_batched, p, x):
        p_batched, x_batched = in_batched
        if x_batched and not any(jax.tree_util.tree_leaves(p_batched)):
            # one set of weights over the batch: the batch's tokens are one
            # group, so each held expert runs once for all of them
            return plain(p, x), (True, True)
        axes = (jax.tree_util.tree_map(lambda b: 0 if b else None,
                                       p_batched), 0 if x_batched else None)
        return jax.vmap(plain, in_axes=axes, axis_size=axis_size)(p, x), \
            (True, True)
    return fn


def moe_serve(p: Dict, cfg: ModelConfig, x: jnp.ndarray, first: int = 0
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Prefill and decode: x [B, S, d] -> (y [B, S, d], routed experts
    [B, S, k]).  On a production mesh this is ``apply_moe``'s expert-parallel
    path (capacity-bounded), which returns no routes."""
    from .sharding import current_rules
    mesh = current_rules().get("__mesh__")
    if mesh is not None and "model" in getattr(mesh, "axis_names", ()):
        return apply_moe(p, cfg, x)
    return _serve_fn(cfg, first)(p, x)


def apply_moe(p: Dict, cfg: ModelConfig, x: jnp.ndarray
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, S, d] -> (y [B, S, d], aux_loss scalar).

    On a production mesh (rules carry "__mesh__") this takes the shard_map
    expert-parallel path; otherwise the plain single-device path below."""
    from .sharding import current_rules
    rules = current_rules()
    mesh = rules.get("__mesh__")
    if mesh is not None and "model" in getattr(mesh, "axis_names", ()):
        return _apply_moe_shard_map(p, cfg, x, mesh)
    return _apply_moe_dense(p, cfg, x)


def _apply_moe_dense(p: Dict, cfg: ModelConfig, x: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    if cfg.n_experts_held != e:
        raise NotImplementedError("training computes every expert; "
                                  "experts_held is a serving share")
    xt = x.reshape(t, d)

    topw, topi, probs = route(p["router"], cfg, xt)             # [T, k]

    # ---- load-balance aux (Switch): E * Σ_e fraction_e * prob_e ----
    onehot_count = jnp.sum(jax.nn.one_hot(topi, e, dtype=jnp.float32), axis=1)
    frac = jnp.mean(onehot_count, axis=0)                       # [E]
    pmean = jnp.mean(probs, axis=0)                             # [E]
    aux = e * jnp.sum(frac / k * pmean) * cfg.router_aux_weight

    # ---- sort+gather dispatch ----
    cap = max(1, int(t * k / e * cfg.capacity_factor))
    flat_e = topi.reshape(-1)                                    # [T*k]
    order = jnp.argsort(flat_e)                                  # [T*k]
    counts = jnp.bincount(flat_e, length=e)                      # [E]
    offsets = jnp.cumsum(counts) - counts                        # [E]
    slot_pos = offsets[:, None] + jnp.arange(cap)[None, :]       # [E, C]
    valid = jnp.arange(cap)[None, :] < counts[:, None]           # [E, C]
    slot = jnp.take(order, jnp.clip(slot_pos, 0, t * k - 1), axis=0)  # [E, C]
    tok = slot // k                                              # [E, C]

    xe = jnp.take(xt, tok, axis=0) * valid[..., None].astype(xt.dtype)  # [E,C,d]
    xe = shard(xe, "experts", None, None)
    gate = _act(cfg, jnp.einsum("ecd,edf->ecf", xe, p["w_gate"]))
    up = jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    ye = jnp.einsum("ecf,efd->ecd", gate * up, p["w_down"])      # [E,C,d]
    ye = shard(ye, "experts", None, None)

    w_slot = jnp.take(topw.reshape(-1), slot) * valid.astype(jnp.float32)  # [E,C]
    contrib = (ye.astype(jnp.float32) * w_slot[..., None]).reshape(e * cap, d)
    y = jnp.zeros((t, d), jnp.float32).at[tok.reshape(-1)].add(contrib)

    if "shared" in p:
        sp = p["shared"]
        up_s = xt @ sp["w_up"]
        gate_s = _act(cfg, xt @ sp["w_gate"])
        y = y + ((gate_s * up_s) @ sp["w_down"]).astype(jnp.float32)

    y = y.astype(x.dtype).reshape(b, s, d)
    return shard(y, "batch", "seq", None), aux


# ---------------------------------------------------------------------------
# shard_map expert-parallel path (production mesh)
# ---------------------------------------------------------------------------
#
# GSPMD cannot shard the data-dependent sort+gather dispatch (global token
# indices over a batch-sharded array force full replication: measured 80×
# FLOP and 40× collective blow-ups).  The TPU-native design keeps tokens
# SHARD-LOCAL and moves no tokens at all:
#
#   * every model shard holds the full local token set (activations are
#     replicated over `model`, sharded over `data` — standard TP layout);
#   * expert weights are sharded over `model`: whole experts when
#     E % model == 0 (expert parallelism: deepseek 160/16), else the expert
#     hidden dim f (intra-expert TP: mixtral 8 experts on 16 shards);
#   * each shard gathers ITS experts' tokens locally, computes, and
#     scatter-adds a partial output; one psum over `model` combines both
#     expert partitions and f-partials — the same collective shape as a
#     row-parallel dense MLP ([T_loc, d] per layer).

def _moe_specs(cfg: ModelConfig, mesh, batch: int):
    from jax.sharding import PartitionSpec as P
    import numpy as np
    ep = (cfg.n_experts % mesh.shape["model"] == 0) and not cfg.moe_force_tp
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dsize = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    if batch % max(dsize, 1):
        dp = ()  # batch=1 decode: tokens replicated over data
    bspec = dp if len(dp) > 1 else (dp[0] if dp else None)
    if ep:
        w_up = w_gate = P("model", None, None)
        w_down = P("model", None, None)
    else:
        w_up = w_gate = P(None, None, "model")
        w_down = P(None, "model", None)
    return ep, bspec, (w_up, w_gate, w_down)


def _apply_moe_shard_map(p: Dict, cfg: ModelConfig, x: jnp.ndarray, mesh
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    from jax.sharding import PartitionSpec as P
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    ep, bspec, (s_up, s_gate, s_down) = _moe_specs(cfg, mesh, b)
    # aux varies over the data axes (different tokens) and is already
    # invariant over model (x is model-replicated) — pmean the former only
    dp_axes = bspec if isinstance(bspec, tuple) else ((bspec,) if bspec else ())

    def body(xl, router, w_gate, w_up, w_down):
        bl, sl, _ = xl.shape
        t = bl * sl
        xt = xl.reshape(t, d)
        topw, topi, probs = route(router, cfg, xt)

        onehot_count = jnp.sum(jax.nn.one_hot(topi, e, dtype=jnp.float32), 1)
        frac = jnp.mean(onehot_count, axis=0)
        pmean = jnp.mean(probs, axis=0)
        aux = e * jnp.sum(frac / k * pmean) * cfg.router_aux_weight
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes)

        e_loc = w_up.shape[0]
        first = jax.lax.axis_index("model") * e_loc if e_loc < e else 0
        cap = max(1, int(t * k / e * cfg.capacity_factor))

        flat_e = topi.reshape(-1)                              # [T_loc*k]
        order = jnp.argsort(flat_e)
        counts = jnp.bincount(flat_e, length=e)
        offsets = jnp.cumsum(counts) - counts
        cnt_l = jax.lax.dynamic_slice(counts, (first,), (e_loc,))
        off_l = jax.lax.dynamic_slice(offsets, (first,), (e_loc,))
        slot_pos = off_l[:, None] + jnp.arange(cap)[None, :]
        valid = jnp.arange(cap)[None, :] < cnt_l[:, None]
        slot = jnp.take(order, jnp.clip(slot_pos, 0, t * k - 1), axis=0)
        tok = slot // k                                        # [E_loc, C]

        xe = jnp.take(xt, tok, axis=0) * valid[..., None].astype(xt.dtype)
        gate = _act(cfg, jnp.einsum("ecd,edf->ecf", xe, w_gate))
        up = jnp.einsum("ecd,edf->ecf", xe, w_up)
        ye = jnp.einsum("ecf,efd->ecd", gate * up, w_down)     # [E_loc,C,d]

        w_slot = jnp.take(topw.reshape(-1), slot) * valid.astype(jnp.float32)
        acc = jnp.bfloat16 if cfg.moe_psum_bf16 else jnp.float32
        contrib = (ye.astype(acc) * w_slot[..., None].astype(acc)).reshape(-1, d)
        y = jnp.zeros((t, d), acc).at[tok.reshape(-1)].add(contrib)
        y = jax.lax.psum(y, "model")                           # combine partials
        return y.astype(xl.dtype).reshape(bl, sl, d), aux

    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, None), P(), s_gate, s_up, s_down),
        out_specs=(P(bspec, None, None), P()),
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if "shared" in p:
        sp = p["shared"]
        xt = x.reshape(-1, d)
        up_s = xt @ sp["w_up"]
        gate_s = _act(cfg, xt @ sp["w_gate"])
        y = y + ((gate_s * up_s) @ sp["w_down"]).reshape(b, s, d)

    return shard(y, "batch", "seq", None), aux
