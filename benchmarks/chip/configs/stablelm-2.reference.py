"""Plain reference of StableLM 2 (stabilityai/stablelm-2-1_6b), in float32.

Follows the published modelling code: pre-LayerNorm decoder blocks with a
sequential residual, multi-head attention with q/k/v biases and no output
bias, rotary embedding on the first ``partial_rotary_factor`` of each
head's dimensions in the rotate-half layout, a SwiGLU MLP, a final
LayerNorm and an untied output head.  No kernel, cache or batching: one
sequence, every position, causal softmax attention in full.

It imports nothing of the system under test.  The benchmark makes the
weights here, in this layout, from the seed; the deployment converts a
copy to the served program's layout as a checkpoint loader would.

``fp8=True`` is the control: every matmul of the model (not attention's
scores) takes its inputs rounded to float8 e4m3, scaled per activation row
and per weight column, and accumulates in float32.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0
HIGHEST = jax.lax.Precision.HIGHEST


def init_weights(cfg: Dict, key) -> Dict:
    """Random weights in the published layout: matrices in ``torch_dtype``
    of standard deviation ``initializer_range``, LayerNorm scales near 1
    and biases near 0 in float32 (random, so that a dropped scale or bias
    shows)."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    std = cfg["initializer_range"]
    dtype = jnp.dtype(cfg["torch_dtype"])
    keys = iter(jax.random.split(key, 3 + 12 * cfg["num_hidden_layers"]))

    def mat(shape):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std
                ).astype(dtype)

    def ln():
        k = next(keys)
        a, b = jax.random.normal(k, (2, d), jnp.float32)
        return {"weight": 1.0 + 0.1 * a, "bias": 0.1 * b}

    layers = []
    for _ in range(cfg["num_hidden_layers"]):
        layers.append({
            "input_layernorm": ln(),
            "q_proj": {"weight": mat((d, d)), "bias": mat((d,))},
            "k_proj": {"weight": mat((d, d)), "bias": mat((d,))},
            "v_proj": {"weight": mat((d, d)), "bias": mat((d,))},
            "o_proj": {"weight": mat((d, d))},
            "post_attention_layernorm": ln(),
            "gate_proj": {"weight": mat((d, f))},
            "up_proj": {"weight": mat((d, f))},
            "down_proj": {"weight": mat((f, d))},
        })
    return {"embed_tokens": mat((v, d)), "layers": layers,
            "norm": ln(), "lm_head": mat((d, v))}


def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fp8: bool):
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _ln(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["weight"] + p["bias"]


def _rotary(x, pos, rot, theta):
    """Rotate-half rotary embedding on the first ``rot`` dims of each head.
    x: [T, H, hd]."""
    half = rot // 2
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]          # [T, half]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    rotated = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    return jnp.concatenate([xr * cos + rotated * sin, xp], -1)


@functools.partial(jax.jit, static_argnames=("cfg_items", "fp8"))
def _layer(x, p, cfg_items, fp8):
    cfg = dict(cfg_items)
    t, d = x.shape
    h = cfg["num_attention_heads"]
    hd = d // h
    rot = int(hd * cfg["partial_rotary_factor"])
    eps = cfg["layer_norm_eps"]
    pos = jnp.arange(t)
    a = _ln(x, p["input_layernorm"], eps)

    def proj(name):
        y = _mm(a, p[name]["weight"], fp8) + p[name]["bias"].astype(jnp.float32)
        return y.reshape(t, h, hd)
    q = _rotary(proj("q_proj"), pos, rot, cfg["rope_theta"])
    k = _rotary(proj("k_proj"), pos, rot, cfg["rope_theta"])
    v = proj("v_proj")
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   precision=HIGHEST) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST).reshape(t, d)
    x = x + _mm(o, p["o_proj"]["weight"], fp8)
    m = _ln(x, p["post_attention_layernorm"], eps)
    g = _mm(m, p["gate_proj"]["weight"], fp8)
    u = _mm(m, p["up_proj"]["weight"], fp8)
    return x + _mm(jax.nn.silu(g) * u, p["down_proj"]["weight"], fp8)


@functools.partial(jax.jit, static_argnames=("cfg_items", "fp8"))
def _head(x, rows, norm, w, cfg_items, fp8):
    cfg = dict(cfg_items)
    return _mm(_ln(x[rows], norm, cfg["layer_norm_eps"]), w, fp8)


def _items(cfg: Dict):
    keys = ("num_attention_heads", "partial_rotary_factor", "layer_norm_eps",
            "rope_theta")
    return tuple((k, cfg[k]) for k in keys)


def logits_at(weights: Dict, cfg: Dict, tokens, rows, fp8: bool = False):
    """float32 logits [len(rows), vocab] of the positions ``rows`` of the
    token sequence ``tokens`` ([T] int32): the prediction of the token that
    follows each such position.  Layer by layer; every product at
    precision ``HIGHEST`` (a float32 matmul on a TPU otherwise rounds its
    inputs to bf16)."""
    items = _items(cfg)
    x = weights["embed_tokens"][tokens].astype(jnp.float32)
    for p in weights["layers"]:
        x = _layer(x, p, items, fp8)
    return _head(x, rows, weights["norm"], weights["lm_head"], items, fp8)
