"""Pallas TPU kernel: causal flash attention (online softmax).

The §Roofline analysis shows unfused attention's S×S score tensor is touched
~10× per layer in HLO (dot, mask, sub, exp, div, ...): at 32k context it is
the dominant memory-roofline term for every full-attention arch (deepseek
prefill: 56s of the 56–71s memory term).  Flash attention keeps each
(bq × bk) score block in VMEM and never materializes S×S in HBM:

  grid (batch·heads, q_blocks, kv_blocks)  — kv innermost, sequential;
  scratch (m, l, acc) persists across the kv sweep (online softmax);
  causal masking skips whole blocks above the diagonal.

HBM traffic per layer drops to Q+K+V+O (+negligible scratch), i.e. the
attention term leaves the memory roofline entirely on TPU.  Validated in
interpret mode against the pure-jnp oracle (models.layers._sdpa semantics).

This is the prefill kernel only.  Cached-KV decode (one query row) has no
S×S tensor to avoid: its [H, S_cache] score row is no larger than the KV
cache it reads whenever H / KV <= head_dim, so ``models.layers.attn_decode``
runs one-piece masked attention (``_sdpa``) for flash configurations too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  bq: int, bk: int, causal: bool, scale: float):
    i = pl.program_id(1)          # q block
    j = pl.program_id(2)          # kv block
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = (not causal) or (j * bk <= i * bq + bq - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale          # [bq, dk]
        k = k_ref[0].astype(jnp.float32)                  # [bk, dk]
        v = v_ref[0].astype(jnp.float32)                  # [bk, dv]
        s = q @ k.T                                       # [bq, bk]
        if causal:
            qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_scr[...]                               # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)                            # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)                   # [bq, 1]
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + p @ v
        m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _fin():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_blocks(s: int):
    """-> (padded length, block) for a causal flash call over ``s``
    positions.  Up to 512 one block spans the sequence; beyond, the length
    is padded to a multiple of 128 and the block is the largest of 512,
    256, 128 dividing it (600 -> 640 in blocks of 128, not 1024 in 512s).
    Causal masking makes the padded keys inert for every real query."""
    if s <= 512:
        return s, s
    sp = -(-s // 128) * 128
    return sp, next(b for b in (512, 256, 128) if sp % b == 0)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret",
                                    "kv_groups"))
def flash_attention(q, k, v, *, causal: bool = True, bq: int = 512,
                    bk: int = 512, interpret: bool = True,
                    kv_groups: int = 1):
    """q: [BH, Sq, dk]; k: [BH//kv_groups, Sk, dk]; v: likewise [.., dv]
    -> [BH, Sq, dv].

    GQA: ``kv_groups`` q-heads share one kv head — handled in the BlockSpec
    index map (no broadcast materialization).  Sq/Sk must be multiples of
    bq/bk: pad upstream (:func:`flash_blocks`) — a remainder block would
    read past the arrays and write NaN rows."""
    bh, sq, dk = q.shape
    _, sk, dv = v.shape
    bq = min(bq, sq)
    bk = min(bk, sk)
    if sq % bq or sk % bk:
        raise ValueError(f"flash_attention: sq={sq}, sk={sk} are not "
                         f"multiples of bq={bq}, bk={bk}; pad upstream")
    scale = dk ** -0.5
    grid = (bh, sq // bq, sk // bk)
    g = kv_groups
    return pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, causal=causal,
                          scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dk), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dk), lambda b, i, j: (b // g, j, 0)),
            pl.BlockSpec((1, bk, dv), lambda b, i, j: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max m
            pltpu.VMEM((bq, 1), jnp.float32),     # running denom l
            pltpu.VMEM((bq, dv), jnp.float32),    # output accumulator
        ],
        compiler_params=getattr(pltpu, "CompilerParams",
                                getattr(pltpu, "TPUCompilerParams", None))(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)

