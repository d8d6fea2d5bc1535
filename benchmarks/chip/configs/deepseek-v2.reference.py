"""Plain reference of DeepSeek-V2 (deepseek-ai/DeepSeek-V2), in float32.

Follows the published modelling code (``modeling_deepseek.py``): RMSNorm
pre-norm decoder blocks with a sequential residual; multi-head latent
attention in its naive form (queries through ``q_a_proj`` /
``q_a_layernorm`` / ``q_b_proj``, keys and values through
``kv_a_proj_with_mqa`` / ``kv_a_layernorm`` / ``kv_b_proj``, one rope key
shared by every head, the rope dims de-interleaved and then rotated in the
rotate-half layout with YaRN frequencies, the softmax scale times YaRN's
``mscale_all_dim`` mscale squared); a dense SwiGLU MLP for the first
``first_k_dense_replace`` layers and after them the MoE block: a float32
softmax gate over every routed expert, group-limited greedy top-k
(``n_group`` groups scored by their best expert, the best ``topk_group``
kept), weights times ``routed_scaling_factor`` and not renormalised, plus
the shared experts; a final RMSNorm and an untied output head.  No kernel,
cache or batching: one sequence, every position, causal softmax attention
in full (in blocks of query rows, so that it fits one chip), and every
routed expert computed for every token it is routed to (no capacity).

The one departure is the expert-parallel share.  The gate routes over all
``n_routed_experts_total`` experts, but only the first ``n_routed_experts``
are held here (one chip of the configuration's deployment); the parts the
other experts would add are left out, as the served program leaves them
out, and that partial result goes on to the next layer.

It imports nothing of the system under test.  The benchmark makes the
weights here, in this layout (matrices stored ``[in, out]``), from the
seed; the deployment converts a copy to the served program's layout as a
checkpoint loader would.

``fp8=True`` is the control: every weight matmul of the model (not the
gate, which the published code runs in float32, and not attention's
scores) takes its inputs rounded to float8 e4m3, scaled per activation row
and per weight column, and accumulates in float32.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

E4M3_MAX = 448.0
HIGHEST = jax.lax.Precision.HIGHEST
#: query rows per block of the attention, and rows per block of the head
Q_BLOCK = 512
HEAD_BLOCK = 256


def init_weights(cfg: Dict, key) -> Dict:
    """Random weights in the published layout: matrices in ``torch_dtype``
    of standard deviation ``initializer_range``, RMSNorm weights near 1 in
    float32 (random, so that a dropped norm shows).  Only the held experts
    of each expert layer exist; the gate has every routed expert's row."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h = cfg["num_attention_heads"]
    r, rq = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held, total = cfg["n_routed_experts"], cfg["n_routed_experts_total"]
    fs = fe * cfg["n_shared_experts"]
    std = cfg["initializer_range"]
    dtype = jnp.dtype(cfg["torch_dtype"])
    keys = iter(jax.random.split(key, 3 + 20 * cfg["num_hidden_layers"]))

    def mat(shape):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std
                ).astype(dtype)

    def norm(n):
        return {"weight": 1.0 + 0.1 * jax.random.normal(next(keys), (n,),
                                                          jnp.float32)}

    layers = []
    for i in range(cfg["num_hidden_layers"]):
        attn = {"q_a_proj": {"weight": mat((d, rq))},
                "q_a_layernorm": norm(rq),
                "q_b_proj": {"weight": mat((rq, h * (dn + dr)))},
                "kv_a_proj_with_mqa": {"weight": mat((d, r + dr))},
                "kv_a_layernorm": norm(r),
                "kv_b_proj": {"weight": mat((r, h * (dn + dv)))},
                "o_proj": {"weight": mat((h * dv, d))}}
        if i < cfg["first_k_dense_replace"]:
            mlp = {"gate_proj": {"weight": mat((d, f))},
                   "up_proj": {"weight": mat((d, f))},
                   "down_proj": {"weight": mat((f, d))}}
        else:
            mlp = {"gate": {"weight": mat((d, total))},
                   "experts": {"gate_proj": {"weight": mat((held, d, fe))},
                               "up_proj": {"weight": mat((held, d, fe))},
                               "down_proj": {"weight": mat((held, fe, d))}},
                   "shared_experts": {"gate_proj": {"weight": mat((d, fs))},
                                      "up_proj": {"weight": mat((d, fs))},
                                      "down_proj": {"weight": mat((fs, d))}}}
        layers.append({"input_layernorm": norm(d), "self_attn": attn,
                       "post_attention_layernorm": norm(d), "mlp": mlp})
    return {"embed_tokens": mat((v, d)), "layers": layers, "norm": norm(d),
            "lm_head": mat((d, v))}


# ---------------------------------------------------------------------------
# YaRN, as the published DeepseekV2YarnRotaryEmbedding computes it
# ---------------------------------------------------------------------------

def yarn_get_mscale(scale: float = 1.0, mscale: float = 1.0) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def _correction_dim(num_rotations, dim, base, max_pos):
    return (dim * math.log(max_pos / (num_rotations * 2 * math.pi))) / (
        2 * math.log(base))


def yarn_inv_freq(cfg: Dict) -> np.ndarray:
    """float32 inverse frequencies of the rope dims."""
    rs = cfg["rope_scaling"]
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]
    ar = np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim)
    freq_extra = (1.0 / np.float32(base) ** ar).astype(np.float32)
    freq_inter = (1.0 / (np.float32(factor) * np.float32(base) ** ar)
                  ).astype(np.float32)
    low = max(math.floor(_correction_dim(rs["beta_fast"], dim, base, orig)), 0)
    high = min(math.ceil(_correction_dim(rs["beta_slow"], dim, base, orig)),
               dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / np.float32(high - low), 0, 1).astype(np.float32)
    mask = (1.0 - ramp).astype(np.float32)
    return (freq_inter * (1 - mask) + freq_extra * mask).astype(np.float32)


def rope_mscale(cfg: Dict) -> float:
    rs = cfg["rope_scaling"]
    return yarn_get_mscale(rs["factor"], rs["mscale"]) / yarn_get_mscale(
        rs["factor"], rs["mscale_all_dim"])


def softmax_scale(cfg: Dict) -> float:
    rs = cfg["rope_scaling"]
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    if rs.get("mscale_all_dim"):
        m = yarn_get_mscale(rs["factor"], rs["mscale_all_dim"])
        scale = scale * m * m
    return scale


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

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


def _rms(x, p, eps):
    var = jnp.mean(x * x, -1, keepdims=True)
    return x / jnp.sqrt(var + eps) * p["weight"]


def _rotate(x, cos, sin):
    """Published apply_rotary_pos_emb: de-interleave the rope dims, then
    rotate-half.  x [T, H, dr]; cos, sin [T, 1, dr]."""
    t, n, d = x.shape
    x = x.reshape(t, n, d // 2, 2).transpose(0, 1, 3, 2).reshape(t, n, d)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _silu_mlp(x, p, fp8):
    g = _mm(x, p["gate_proj"]["weight"], fp8)
    u = _mm(x, p["up_proj"]["weight"], fp8)
    return _mm(jax.nn.silu(g) * u, p["down_proj"]["weight"], fp8)


def _attention(x, p, cfg, fp8):
    t = x.shape[0]
    h = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    q = _mm(_rms(_mm(x, p["q_a_proj"]["weight"], fp8), p["q_a_layernorm"],
                 eps), p["q_b_proj"]["weight"], fp8).reshape(t, h, dn + dr)
    ckv = _mm(x, p["kv_a_proj_with_mqa"]["weight"], fp8)
    kv = _mm(_rms(ckv[:, :r], p["kv_a_layernorm"], eps),
             p["kv_b_proj"]["weight"], fp8).reshape(t, h, dn + dv)
    pos = jnp.arange(t, dtype=jnp.float32)
    freqs = pos[:, None] * jnp.asarray(yarn_inv_freq(cfg))[None, :]
    emb = jnp.concatenate([freqs, freqs], -1)
    cos = (jnp.cos(emb) * rope_mscale(cfg))[:, None, :]
    sin = (jnp.sin(emb) * rope_mscale(cfg))[:, None, :]
    q_pe = _rotate(q[..., dn:], cos, sin)
    k_pe = _rotate(ckv[:, None, r:], cos, sin)
    q = jnp.concatenate([q[..., :dn], q_pe], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (t, h, dr))],
                        -1)
    v = kv[..., dn:]
    scale = softmax_scale(cfg)
    outs = []
    for a in range(0, t, Q_BLOCK):
        b = min(t, a + Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", q[a:b], k, precision=HIGHEST) * scale
        causal = jnp.arange(a, b)[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(causal[None], s, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                               precision=HIGHEST))
    o = jnp.concatenate(outs, 0).reshape(t, h * dv)
    return _mm(o, p["o_proj"]["weight"], fp8)


def gate(x, w, cfg):
    """Published MoEGate, group_limited_greedy: -> (weights [T, k], experts
    [T, k]) over every routed expert."""
    t = x.shape[0]
    e, k = cfg["n_routed_experts_total"], cfg["num_experts_per_tok"]
    g, kg = cfg["n_group"], cfg["topk_group"]
    scores = jax.nn.softmax(jnp.matmul(x, w.astype(jnp.float32),
                                       precision=HIGHEST), -1)
    group_scores = scores.reshape(t, g, e // g).max(-1)
    _, group_idx = jax.lax.top_k(group_scores, kg)
    group_mask = jnp.zeros((t, g)).at[jnp.arange(t)[:, None], group_idx].set(1)
    score_mask = jnp.repeat(group_mask, e // g, axis=1)
    tmp = jnp.where(score_mask > 0, scores, 0.0)
    weight, idx = jax.lax.top_k(tmp, k)
    if k > 1 and cfg["norm_topk_prob"]:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    else:
        weight = weight * cfg["routed_scaling_factor"]
    return weight, idx


def _moe(x, p, cfg, fp8):
    weight, idx = gate(x, p["gate"]["weight"], cfg)
    ex = p["experts"]

    def expert(e, y):                                 # the held share
        w_e = jnp.sum(jnp.where(idx == e, weight, 0.0), -1)   # [T]
        return y + w_e[:, None] * _silu_mlp(
            x, {n: {"weight": ex[n]["weight"][e]} for n in ex}, fp8)
    y = jax.lax.fori_loop(0, cfg["n_routed_experts"], expert,
                          jnp.zeros_like(x))
    return y + _silu_mlp(x, p["shared_experts"], fp8), idx


@functools.partial(jax.jit, static_argnames=("cfg_items", "fp8"))
def _layer(x, p, cfg_items, fp8):
    cfg = dict(cfg_items)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_rms(x, p["input_layernorm"], eps), p["self_attn"],
                       cfg, fp8)
    m = _rms(x, p["post_attention_layernorm"], eps)
    if "gate" in p["mlp"]:
        y, idx = _moe(m, p["mlp"], cfg, fp8)
    else:
        y, idx = _silu_mlp(m, p["mlp"], fp8), None
    return x + y, idx


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, rows, norm, w, eps, fp8):
    return jnp.concatenate([_mm(_rms(x[rows[a:a + HEAD_BLOCK]], norm, eps),
                                w, fp8)
                            for a in range(0, rows.shape[0], HEAD_BLOCK)], 0)


def _items(cfg: Dict):
    keys = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "kv_lora_rank", "rms_norm_eps", "rope_theta",
            "n_routed_experts", "n_routed_experts_total",
            "num_experts_per_tok", "n_group", "topk_group", "norm_topk_prob",
            "routed_scaling_factor")
    return tuple((k, cfg[k]) for k in keys) + (
        ("rope_scaling", tuple(sorted(cfg["rope_scaling"].items()))),)


def _forward(weights: Dict, cfg: Dict, tokens, fp8: bool):
    items = _items(cfg)
    x = weights["embed_tokens"][tokens].astype(jnp.float32)
    routes = []
    for p in weights["layers"]:
        x, idx = _layer(x, p, items, fp8)
        if idx is not None:
            routes.append(idx)
    return x, routes


def logits_at(weights: Dict, cfg: Dict, tokens, rows, fp8: bool = False):
    """float32 logits [len(rows), vocab] of the positions ``rows`` of the
    token sequence ``tokens`` ([T] int32): the prediction of the token that
    follows each such position.  Layer by layer; every product at
    precision ``HIGHEST`` (a float32 matmul on a TPU otherwise rounds its
    inputs to bf16)."""
    x, _ = _forward(weights, cfg, tokens, fp8)
    return _head(x, rows, weights["norm"], weights["lm_head"],
                 cfg["rms_norm_eps"], fp8)


def routes_at(weights: Dict, cfg: Dict, tokens):
    """The experts each position of ``tokens`` is routed to in each expert
    layer: int32 [n_expert_layers, T, k]."""
    _, routes = _forward(weights, cfg, tokens, False)
    return jnp.stack(routes)
