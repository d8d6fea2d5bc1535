"""RG-LRU recurrent block (RecurrentGemma / Griffin, arXiv:2402.19427).

Griffin recurrent block: two branches from the residual stream —
  gate branch : linear -> GeLU
  rec branch  : linear -> causal conv1d(4) -> RG-LRU
merged multiplicatively, then projected out.

RG-LRU (real-gated linear recurrent unit):
  r_t = σ(W_r x_t)         recurrence gate
  i_t = σ(W_i x_t)         input gate
  a_t = a^(c·r_t)          with a = σ(Λ) learnable, c = 8
  h_t = a_t ⊙ h_{t-1} + √(1 - a_t²) ⊙ (i_t ⊙ x_t)

The recurrence is a first-order linear scan — implemented with
jax.lax.associative_scan over the sequence (TPU-friendly log-depth), and as
a single fused update at decode.  State is [B, lru_width]: O(1) in sequence
length (long_500k viable).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import commit_state, dense_init
from .sharding import shard

_C = 8.0
_MAX_SQRT = 1e-6


def rglru_init(rng, cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    w = cfg.lru_width or d
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 6)
    # Λ init so that a = σ(Λ)^c spans ~(0.9, 0.999) as in the paper
    lam = jnp.log(jnp.expm1(jnp.linspace(2.0, 6.0, w))).astype(jnp.float32)
    return {
        "w_gate": dense_init(ks[0], d, w, dt),      # GeLU branch
        "w_rec": dense_init(ks[1], d, w, dt),       # recurrent branch in
        "conv": (jax.random.normal(ks[2], (4, w)) * 0.1).astype(dt),
        "w_r": dense_init(ks[3], w, w, dt),
        "w_i": dense_init(ks[4], w, w, dt),
        "lam": lam,
        "w_out": dense_init(ks[5], w, d, dt),
    }


def _conv4(x: jnp.ndarray, w: jnp.ndarray, prev=None):
    k = w.shape[0]
    pad = prev if prev is not None else jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(k))
    return out, xp[:, -(k - 1):]


def _gates(p: Dict, u: jnp.ndarray):
    """u: [..., w] conv output.  Returns (a, beta·i·u) in f32."""
    r = jax.nn.sigmoid((u @ p["w_r"]).astype(jnp.float32))
    i = jax.nn.sigmoid((u @ p["w_i"]).astype(jnp.float32))
    log_a = -_C * r * jax.nn.softplus(p["lam"])       # log σ(Λ)^(c·r) stable form
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), _MAX_SQRT))
    return a, beta * i * u.astype(jnp.float32)


def rglru_train(p: Dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    b, s, d = x.shape
    gate = jax.nn.gelu(x @ p["w_gate"])
    u = x @ p["w_rec"]
    u, _ = _conv4(u, p["conv"])
    a, bx = _gates(p, u)                               # [B,S,w] each, f32
    a = shard(a, "batch", "seq", "ff")
    bx = shard(bx, "batch", "seq", "ff")

    # h_t = a_t h_{t-1} + bx_t  — associative: (a1,b1)∘(a2,b2)=(a1a2, a2 b1 + b2)
    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, bx), axis=1)
    y = (h.astype(x.dtype) * gate) @ p["w_out"]
    return shard(y, "batch", "seq", None)


def rglru_cache_init(cfg: ModelConfig, batch: int) -> Dict:
    w = cfg.lru_width or cfg.d_model
    return {
        "h": jnp.zeros((batch, w), jnp.float32),
        "conv": jnp.zeros((batch, 3, w), jnp.dtype(cfg.dtype)),
    }


def rglru_decode(p: Dict, cfg: ModelConfig, x: jnp.ndarray, cache: Dict,
                 commit=None) -> Tuple[jnp.ndarray, Dict]:
    """One-token decode; ``commit`` (a traced bool scalar) keeps the old
    state where it does not hold (``layers.commit_state``)."""
    gate = jax.nn.gelu(x @ p["w_gate"])                # [B,1,w]
    u = x @ p["w_rec"]
    u, conv_state = _conv4(u, p["conv"], prev=cache["conv"])
    a, bx = _gates(p, u[:, 0])                         # [B,w]
    h = shard(cache["h"], "batch", "ff") * a + bx
    y = (h[:, None].astype(x.dtype) * gate) @ p["w_out"]
    return shard(y, "batch", None, None), commit_state(
        {"h": h, "conv": conv_state}, cache, commit)
