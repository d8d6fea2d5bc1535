"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

KV is compressed into a rank-``kv_lora_rank`` latent c_kv plus a shared
RoPE key k_rope; queries optionally go through a q_lora bottleneck.  The
decode path uses the *absorbed* form: W_uk is folded into the query so
attention runs directly against the cached latent — the cache holds only
[S, kv_lora + rope_dim] per token (the paper's 93% KV-cache cut), not
per-head keys/values.

Train/prefill uses the naive (materialized) form, which is einsum-friendlier
for long sequences; decode uses absorption.  Both share the same params.

With ``yarn_factor > 1`` the rope dims use YaRN (arXiv:2309.00071) as
DeepSeek-V2's published code does: frequencies interpolated between the
plain and the ``factor``-stretched ones over a ramp set by ``beta_fast`` /
``beta_slow`` at ``original_max_position_embeddings``, and the softmax
scale multiplied by ``mscale_all_dim``'s YaRN mscale squared.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .layers import (apply_norm, apply_rope, commit_row, dense_init,
                     norm_init)
from .sharding import shard


def mla_init(rng, cfg: ModelConfig) -> Dict:
    d, h = cfg.d_model, cfg.n_heads
    r_kv, r_q = cfg.kv_lora_rank, cfg.q_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 8)
    p = {
        # KV path: x -> [c_kv | k_rope]
        "w_dkv": dense_init(ks[0], d, r_kv + dr, dt),
        "norm_kv": norm_init(r_kv, cfg),
        "w_uk": (jax.random.normal(ks[1], (r_kv, h, dn)) * r_kv ** -0.5).astype(dt),
        "w_uv": (jax.random.normal(ks[2], (r_kv, h, dv)) * r_kv ** -0.5).astype(dt),
        "wo": dense_init(ks[3], h * dv, d, dt),
    }
    if r_q:
        p["w_dq"] = dense_init(ks[4], d, r_q, dt)
        p["norm_q"] = norm_init(r_q, cfg)
        p["w_uq"] = (jax.random.normal(ks[5], (r_q, h, dn + dr)) * r_q ** -0.5).astype(dt)
    else:
        p["w_q"] = (jax.random.normal(ks[5], (d, h, dn + dr)) * d ** -0.5).astype(dt)
    return p


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(cfg: ModelConfig) -> Optional[np.ndarray]:
    """YaRN-interpolated inverse frequencies of the ``qk_rope_dim`` rope
    dims (float32, as the published code computes them); None without
    YaRN (plain ``rope_theta`` frequencies)."""
    if cfg.yarn_factor <= 1:
        return None
    dim, base = cfg.qk_rope_dim, cfg.rope_theta

    def corr_dim(rotations):
        return dim * math.log(cfg.yarn_original_max_pos
                              / (rotations * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / np.float32(high - low), 0, 1)
    extra_mask = (1.0 - ramp).astype(np.float32)
    pos_freqs = np.float32(base) ** (np.arange(0, dim, 2, dtype=np.float32)
                                     / np.float32(dim))
    extra = (1.0 / pos_freqs).astype(np.float32)
    inter = (1.0 / (np.float32(cfg.yarn_factor) * pos_freqs)).astype(np.float32)
    return (inter * (1 - extra_mask) + extra * extra_mask).astype(np.float32)


def _rope(cfg: ModelConfig, x: jnp.ndarray, positions) -> jnp.ndarray:
    inv = rope_inv_freq(cfg)
    if inv is None:
        return apply_rope(x, positions, 1.0, cfg.rope_theta)
    m = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale) / yarn_mscale(
        cfg.yarn_factor, cfg.yarn_mscale_all_dim)
    return apply_rope(x, positions, 1.0, cfg.rope_theta,
                      inv_freq=jnp.asarray(inv), mscale=m)


def softmax_scale(cfg: ModelConfig) -> float:
    """1/sqrt(q head dim), times YaRN's ``mscale_all_dim`` mscale squared."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.yarn_factor > 1 and cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def _queries(p: Dict, cfg: ModelConfig, x: jnp.ndarray, positions):
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    if "w_dq" in p:
        cq = apply_norm(p["norm_q"], x @ p["w_dq"], cfg)
        q = jnp.einsum("bsr,rhd->bshd", cq, p["w_uq"])
    else:
        q = jnp.einsum("bsd,dhe->bshe", x, p["w_q"])
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = _rope(cfg, q_rope, positions)
    return q_nope, q_rope


def _latent(p: Dict, cfg: ModelConfig, x: jnp.ndarray, positions):
    r_kv, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
    dkv = x @ p["w_dkv"]                                        # [B,S,r+dr]
    c_kv = apply_norm(p["norm_kv"], dkv[..., :r_kv], cfg)       # [B,S,r]
    k_rope = dkv[..., None, r_kv:]                              # [B,S,1,dr]
    k_rope = _rope(cfg, k_rope, positions)[:, :, 0]
    return c_kv, k_rope                                         # [B,S,r], [B,S,dr]


def mla_train(p: Dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Naive (materialized) form for train/prefill."""
    b, s, _ = x.shape
    h, dv = cfg.n_heads, cfg.v_head_dim
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    q_nope, q_rope = _queries(p, cfg, x, pos)
    c_kv, k_rope = _latent(p, cfg, x, pos)
    k_nope = jnp.einsum("btr,rhd->bthd", c_kv, p["w_uk"])        # [B,S,h,dn]
    v = jnp.einsum("btr,rhd->bthd", c_kv, p["w_uv"])             # [B,S,h,dv]
    q_nope = shard(q_nope, "batch", "seq", "heads", None)
    k_nope = shard(k_nope, "batch", "seq", "heads", None)
    v = shard(v, "batch", "seq", "heads", None)
    scale = softmax_scale(cfg)
    acc = jnp.float32 if cfg.attn_f32_logits else jnp.bfloat16
    if cfg.mla_fused_qk:
        # §Perf: one QK dot over concat features — the naive two-einsum form
        # writes+reads the S×S tensor twice more (dot #2 + transpose + add)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)       # [B,S,h,dn+dr]
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (*k_rope.shape[:2], h, cfg.qk_rope_dim))],
            axis=-1)
        logits = jnp.einsum("bshd,bthd->bhst",
                            (q_full * scale).astype(acc), k_full.astype(acc))
    else:
        logits = (jnp.einsum("bshd,bthd->bhst", q_nope.astype(acc),
                             k_nope.astype(acc))
                  + jnp.einsum("bshd,btd->bhst", q_rope.astype(acc),
                               k_rope.astype(acc))) * jnp.asarray(scale, acc)
    if cfg.attn_additive_mask:
        # §Perf: additive causal bias, no [B,h,S,S] bool broadcast + select
        bias = jnp.where(pos[0][:, None] >= pos[0][None, :], 0.0, -1e30)
        logits = logits + bias[None, None, :, :].astype(logits.dtype)
    else:
        mask = pos[:, :, None] >= pos[:, None, :]
        neg = jnp.asarray(-1e30 if cfg.attn_f32_logits else -3e38, acc)
        logits = jnp.where(mask[:, None, :, :], logits, neg)
    w = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(acc) \
        if not cfg.attn_f32_logits else jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", w, v.astype(acc)).astype(x.dtype)
    y = out.reshape(b, s, h * dv) @ p["wo"]
    return shard(y, "batch", "seq", None)


def mla_cache_init(cfg: ModelConfig, batch: int, max_seq: int) -> Dict:
    dt = jnp.dtype(cfg.dtype)
    return {
        "c_kv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dt),
        "k_rope": jnp.zeros((batch, max_seq, cfg.qk_rope_dim), dt),
    }


def mla_decode(p: Dict, cfg: ModelConfig, x: jnp.ndarray, cache: Dict,
               pos: jnp.ndarray, commit=None) -> Tuple[jnp.ndarray, Dict]:
    """Absorbed-form decode: attention runs in the latent space against the
    compressed cache.  ``commit`` (a traced bool scalar) commits the new
    latent row only where it holds (``layers.commit_row``)."""
    b = x.shape[0]
    h, dv = cfg.n_heads, cfg.v_head_dim
    positions = jnp.broadcast_to(pos[None, None], (b, 1)).astype(jnp.int32)
    q_nope, q_rope = _queries(p, cfg, x, positions)              # [B,1,h,*]
    c1, kr1 = _latent(p, cfg, x, positions)                      # [B,1,r],[B,1,dr]
    ck = commit_row(cache["c_kv"], c1, pos, commit)
    cr = commit_row(cache["k_rope"], kr1, pos, commit)
    ck = shard(ck, "batch", "kv_seq", None)
    cr = shard(cr, "batch", "kv_seq", None)
    # absorb W_uk into q: q_lat[b,h,r] = Σ_d q_nope[b,h,d] · W_uk[r,h,d]
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, p["w_uk"])[:, 0]    # [B,h,r]
    scale = softmax_scale(cfg)
    with jax.named_scope("mla_decode_attn"):
        logits = (jnp.einsum("bhr,btr->bht", q_lat.astype(jnp.float32),
                             ck.astype(jnp.float32))
                  + jnp.einsum("bhd,btd->bht",
                               q_rope[:, 0].astype(jnp.float32),
                               cr.astype(jnp.float32))) * scale
        size = ck.shape[1]
        valid = jnp.arange(size, dtype=jnp.int32) <= pos
        logits = jnp.where(valid[None, None, :], logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1)                      # [B,h,S]
        lat = jnp.einsum("bht,btr->bhr", w, ck.astype(jnp.float32))  # [B,h,r]
    out = jnp.einsum("bhr,rhd->bhd", lat.astype(x.dtype), p["w_uv"])  # [B,h,dv]
    y = out.reshape(b, 1, h * dv) @ p["wo"]
    return shard(y, "batch", None, None), {"c_kv": ck, "k_rope": cr}
