"""Shared neural-net layers (pure JAX): norms, RoPE, GQA attention with
global/sliding-window masking and ring-buffer KV caches, gated MLPs.

Conventions:
* params are nested dicts of arrays; init functions take an rng and return
  the dict.  Compute dtype follows cfg.dtype; norms/softmax accumulate f32.
* activations are tagged with logical axes via models.sharding.shard —
  no-ops on CPU, PartitionSpecs on the production mesh.
* decode caches: global layers keep [B, S_max, kv, hd]; local (sliding
  window) layers keep a ring buffer [B, W, kv, hd] — this is what makes
  window archs viable at 500k context.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .sharding import shard

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(rng, d_in: int, d_out: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else d_in ** -0.5
    return (jax.random.normal(rng, (d_in, d_out)) * scale).astype(dtype)


def norm_init(d: int, cfg: ModelConfig) -> Dict:
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(p: Dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]
    else:
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6)
        y = y * p["scale"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, rope_frac: float, theta: float) -> jnp.ndarray:
    rot = int(head_dim * rope_frac) // 2 * 2
    return 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, rope_frac: float,
               theta: float, inv_freq: Optional[jnp.ndarray] = None,
               mscale: float = 1.0) -> jnp.ndarray:
    """x: [B, S, H, hd]; positions: [B, S] (absolute).  Rotates the leading
    rope_frac fraction of hd (partial rotary, stablelm-style).  ``inv_freq``
    replaces the plain ``theta`` frequencies (YaRN), ``mscale`` scales the
    rotation's cos and sin."""
    hd = x.shape[-1]
    rot = int(hd * rope_frac) // 2 * 2
    if rot == 0:
        return x
    freqs = rope_freqs(hd, rope_frac, theta) if inv_freq is None \
        else inv_freq                                              # [rot/2]
    ang = positions[..., None].astype(jnp.float32) * freqs         # [B,S,rot/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    yr = jnp.stack([y1, y2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([yr, xp], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA / MQA, global or sliding-window)
# ---------------------------------------------------------------------------


def attn_init(rng, cfg: ModelConfig) -> Dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], d, h * hd, dt),
        "wk": dense_init(ks[1], d, kv * hd, dt),
        "wv": dense_init(ks[2], d, kv * hd, dt),
        "wo": dense_init(ks[3], h * hd, d, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dt)
        p["bk"] = jnp.zeros((kv * hd,), dt)
        p["bv"] = jnp.zeros((kv * hd,), dt)
    return p


def _qkv(p: Dict, cfg: ModelConfig, x: jnp.ndarray, positions: jnp.ndarray):
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = x @ p["wq"] + (p["bq"] if "bq" in p else 0)
    k = x @ p["wk"] + (p["bk"] if "bk" in p else 0)
    v = x @ p["wv"] + (p["bv"] if "bv" in p else 0)
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kv, hd)
    v = v.reshape(b, s, kv, hd)
    q = apply_rope(q, positions, cfg.rope_frac, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_frac, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, mask, softcap: Optional[float], n_heads: int, n_kv: int,
          f32_logits: bool = True, additive_mask=None):
    """q: [B,Sq,H,hd]; k,v: [B,Sk,KV,hd]; mask: [B?,Sq,Sk] bool or None.

    f32_logits=False is the §Perf bf16-softmax variant: halves the bytes of
    the S×S score tensors (the memory-roofline hot spot at train_4k).
    additive_mask: [Sq,Sk] float bias — §Perf alternative to the boolean
    select (no [B,h,Sq,Sk] bool broadcast + select_n passes)."""
    b, sq, h, hd = q.shape
    groups = h // n_kv
    acc = jnp.float32 if f32_logits else jnp.bfloat16
    qg = q.reshape(b, sq, n_kv, groups, hd)
    logits = jnp.einsum("bsngh,btnh->bngst", qg.astype(acc) * hd ** -0.5,
                        k.astype(acc))
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    if additive_mask is not None:
        logits = logits + additive_mask[None, None, None].astype(logits.dtype)
    elif mask is not None:
        neg = jnp.asarray(-1e30 if f32_logits else -3e38, logits.dtype)
        logits = jnp.where(mask[:, None, None, :, :], logits, neg)
    w = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(acc) \
        if not f32_logits else jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bngst,btnh->bsngh", w, v.astype(acc))
    return out.reshape(b, sq, h, hd).astype(q.dtype)


def causal_mask(sq: int, sk: int, positions_q, positions_k, window: Optional[int]):
    """mask[b, i, j] = may q-position i attend to k-position j."""
    m = positions_q[:, :, None] >= positions_k[:, None, :]
    if window is not None:
        m &= positions_q[:, :, None] - positions_k[:, None, :] < window
    return m


def attn_train(p: Dict, cfg: ModelConfig, x: jnp.ndarray,
               window: Optional[int]) -> jnp.ndarray:
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
    q, k, v = _qkv(p, cfg, x, pos)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)
    if cfg.use_flash_attn and window is None and not cfg.logit_softcap:
        # Pallas flash attention (kernels/flash_attn.py): no S×S HBM tensor.
        from ..kernels.flash_attn import flash_attention, flash_blocks
        from ..kernels.ops import use_interpret
        h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
        sp, blk = flash_blocks(s)
        pad = ((0, 0), (0, sp - s), (0, 0))
        q2 = jnp.pad(q.transpose(0, 2, 1, 3).reshape(b * h, s, hd), pad)
        k2 = jnp.pad(k.transpose(0, 2, 1, 3).reshape(b * kvh, s, hd), pad)
        v2 = jnp.pad(v.transpose(0, 2, 1, 3).reshape(b * kvh, s, hd), pad)
        o2 = flash_attention(q2, k2, v2, causal=True, bq=blk, bk=blk,
                             kv_groups=h // kvh, interpret=use_interpret())
        out = o2[:, :s].reshape(b, h, s, hd).transpose(0, 2, 1, 3)
        y = out.reshape(b, s, -1) @ p["wo"]
        return shard(y, "batch", "seq", None)
    if cfg.attn_additive_mask:
        idx = jnp.arange(s, dtype=jnp.int32)
        ok = idx[:, None] >= idx[None, :]
        if window is not None:
            ok &= idx[:, None] - idx[None, :] < window
        bias = jnp.where(ok, 0.0, -1e30)
        out = _sdpa(q, k, v, None, cfg.logit_softcap, cfg.n_heads,
                    cfg.n_kv_heads, f32_logits=cfg.attn_f32_logits,
                    additive_mask=bias)
    else:
        mask = causal_mask(s, s, pos, pos, window)
        out = _sdpa(q, k, v, mask, cfg.logit_softcap, cfg.n_heads,
                    cfg.n_kv_heads, f32_logits=cfg.attn_f32_logits)
    y = out.reshape(b, s, -1) @ p["wo"]
    return shard(y, "batch", "seq", None)


def attn_cache_init(cfg: ModelConfig, batch: int, max_seq: int,
                    window: Optional[int]) -> Dict:
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    size = min(window, max_seq) if window is not None else max_seq
    if cfg.kv_cache_quant:
        # §Perf: int8 KV + per-(token, kv-head) f32 scales — halves the
        # dominant cache-read bytes of long-context decode on TPU
        return {
            "k": jnp.zeros((batch, size, kv, hd), jnp.int8),
            "v": jnp.zeros((batch, size, kv, hd), jnp.int8),
            "k_s": jnp.ones((batch, size, kv, 1), jnp.float32),
            "v_s": jnp.ones((batch, size, kv, 1), jnp.float32),
        }
    dt = jnp.dtype(cfg.dtype)
    return {
        "k": jnp.zeros((batch, size, kv, hd), dt),
        "v": jnp.zeros((batch, size, kv, hd), dt),
    }


def _quant_kv(x: jnp.ndarray):
    """x: [B,1,kv,hd] -> (int8, f32 scale [B,1,kv,1])."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.round(x.astype(jnp.float32) / scale).astype(jnp.int8)
    return q, scale


def _row_at(cache: jnp.ndarray, at) -> jnp.ndarray:
    """The position row ``cache[:, at:at+1]``, bit for bit, read as a masked
    integer sum over the position axis.  A vmapped ``dynamic_slice`` would
    be a gather, and where the TPU compiler lays the cache out with
    positions minor (a 64-wide head) it transposes every cache leaf to
    gather one row; a masked sum streams the cache in its own layout."""
    bits = jnp.dtype(f"int{8 * cache.dtype.itemsize}")
    hit = (jnp.arange(cache.shape[1]) == at).reshape(
        (1, -1) + (1,) * (cache.ndim - 2))
    word = jax.lax.bitcast_convert_type(cache, bits)
    row = jnp.sum(jnp.where(hit, word, 0), axis=1, keepdims=True, dtype=bits)
    return jax.lax.bitcast_convert_type(row, cache.dtype)


def commit_row(cache: jnp.ndarray, row: jnp.ndarray, at,
               commit=None) -> jnp.ndarray:
    """Write one position ``row`` ([B, 1, ...]) into ``cache`` ([B, S, ...])
    at position ``at``.  Under a traced bool ``commit`` the row written is
    ``where(commit, row, old row)``: an uncommitted step writes its own old
    row back, so only the row is selected, never the whole cache."""
    start = (0, at) + (0,) * (cache.ndim - 2)
    if commit is not None:
        # the row dynamic_update_slice writes: it clamps ``at`` into the cache
        at = jnp.clip(at, 0, cache.shape[1] - 1)
        row = jnp.where(commit, row, _row_at(cache, at))
    return jax.lax.dynamic_update_slice(cache, row, start)


def commit_state(new: Dict, old: Dict, commit=None) -> Dict:
    """A recurrent state committed whole: ``new`` where the traced bool
    ``commit`` holds, else ``old`` (``commit=None``: ``new``)."""
    if commit is None:
        return new
    return jax.tree_util.tree_map(lambda n, o: jnp.where(commit, n, o),
                                  new, old)


def attn_decode(p: Dict, cfg: ModelConfig, x: jnp.ndarray, cache: Dict,
                pos: jnp.ndarray, window: Optional[int],
                commit=None) -> Tuple[jnp.ndarray, Dict]:
    """One-token decode. x: [B,1,d]; pos: scalar int32 (current position);
    cache k/v: [B, S_cache, kv, hd] (ring buffer iff window).  ``commit``
    (a traced bool scalar) commits the new position row only where it
    holds (``commit_row``)."""
    b = x.shape[0]
    positions = jnp.broadcast_to(pos[None, None], (b, 1)).astype(jnp.int32)
    q, k1, v1 = _qkv(p, cfg, x, positions)
    size = cache["k"].shape[1]
    slot = (pos % size).astype(jnp.int32) if window is not None else pos
    if cfg.kv_cache_quant:
        k1q, k1s = _quant_kv(k1)
        v1q, v1s = _quant_kv(v1)
        rows = {"k": k1q, "v": v1q, "k_s": k1s, "v_s": v1s}
        new_cache = {name: commit_row(cache[name], row, slot, commit)
                     for name, row in rows.items()}
        dt = jnp.dtype(cfg.dtype)
        ck = (new_cache["k"].astype(jnp.float32) * new_cache["k_s"]).astype(dt)
        cv = (new_cache["v"].astype(jnp.float32) * new_cache["v_s"]).astype(dt)
    else:
        new_cache = None
        ck = commit_row(cache["k"], k1, slot, commit)
        cv = commit_row(cache["v"], v1, slot, commit)
    ck = shard(ck, "batch", "kv_seq", "kv_heads", None)
    cv = shard(cv, "batch", "kv_seq", "kv_heads", None)
    # key positions: absolute position of each cache slot
    idx = jnp.arange(size, dtype=jnp.int32)
    if window is not None:
        # ring: slot i holds position p where p % size == i and p <= pos
        kpos = pos - ((pos - idx) % size)
    else:
        kpos = idx
    valid = (kpos <= pos) & (kpos >= 0)
    if window is not None:
        valid &= pos - kpos < window
    # one piece for every configuration: the [B, H, S_cache] score row is
    # no larger than the cache it reads whenever H / KV <= hd, so a
    # blockwise (flash) sweep buys nothing at decode
    mask = jnp.broadcast_to(valid[None, None, :], (b, 1, size))
    out = _sdpa(q, ck, cv, mask, cfg.logit_softcap, cfg.n_heads,
                cfg.n_kv_heads, f32_logits=cfg.attn_f32_logits)
    y = out.reshape(b, 1, -1) @ p["wo"]
    return shard(y, "batch", None, None), \
        (new_cache if new_cache is not None else {"k": ck, "v": cv})


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_init(rng, cfg: ModelConfig, d_ff: Optional[int] = None,
             d_model: Optional[int] = None) -> Dict:
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 3)
    p = {"w_up": dense_init(ks[0], d, f, dt),
         "w_down": dense_init(ks[1], f, d, dt)}
    if cfg.mlp_glu:
        p["w_gate"] = dense_init(ks[2], d, f, dt)
    return p


def _act(cfg: ModelConfig, x):
    return jax.nn.silu(x) if cfg.act == "silu" else jax.nn.gelu(x)


def apply_mlp(p: Dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    up = x @ p["w_up"]
    up = shard(up, "batch", "seq", "ff")
    if "w_gate" in p:
        gate = shard(x @ p["w_gate"], "batch", "seq", "ff")
        h = _act(cfg, gate) * up
    else:
        h = _act(cfg, up)
    y = h @ p["w_down"]
    return shard(y, "batch", "seq", None)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def embed_init(rng, cfg: ModelConfig) -> Dict:
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 2)
    p = {"tok": (jax.random.normal(ks[0], (cfg.vocab, cfg.d_model)) * 0.02).astype(dt)}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(ks[1], cfg.d_model, cfg.vocab, dt)
    return p


def embed(p: Dict, cfg: ModelConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    x = jnp.take(p["tok"], tokens, axis=0)
    return shard(x, "batch", "seq", None)


def unembed(p: Dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    w = p["tok"].T if cfg.tie_embeddings else p["head"]
    logits = x @ w
    return shard(logits, "batch", "seq", "vocab")
