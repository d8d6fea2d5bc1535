"""Mixture-of-Experts layer: top-k router + capacity-bounded gather dispatch
+ optional shared experts (DeepSeek-V2) — expert-parallel over the `model`
mesh axis.

Dispatch avoids the GShard one-hot einsum (whose FLOPs, T·E·C·d, would dwarf
the expert FLOPs at 160 experts) in favour of sort+gather: tokens are
argsorted by expert id, each expert gathers its first C tokens, computes the
gated FF, and results scatter-add back weighted by router probs.  Gathers
are bandwidth, not FLOPs, so HLO_FLOPs stays close to 6·N_active·D.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import dense_init, _act
from .sharding import shard


def moe_init(rng, cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    f = cfg.d_ff_expert or cfg.d_ff
    e = cfg.n_experts
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 5)
    p = {
        "router": dense_init(ks[0], d, e, jnp.float32, scale=0.02),
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
    xt = x.reshape(t, d)

    logits = (xt.astype(jnp.float32) @ p["router"])            # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)                        # [T, k]
    topw = topw / jnp.sum(topw, -1, keepdims=True)

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
        logits = xt.astype(jnp.float32) @ router              # [T_loc, E]
        probs = jax.nn.softmax(logits, axis=-1)
        topw, topi = jax.lax.top_k(probs, k)
        topw = topw / jnp.sum(topw, -1, keepdims=True)

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
