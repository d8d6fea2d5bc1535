"""Pure-jnp oracles for the Pallas stream-codec and attention kernels.

Semantics (shared contract between ref and kernels):

* quantize8: per (BM, BN) tile symmetric int8 quantization.  scale =
  absmax/127 (1.0 for all-zero tiles); q = round(x/scale).
* sparse_enc ("block-COO"): the flat input is split into blocks of B
  elements; each block keeps its first KB nonzeros (|x| > threshold) in
  position order — value and *global* flat index; empty slots hold
  (value=0, index=block_base), which decode treats as a no-op because the
  contribution is zero.  Capacity overflow inside a block drops the tail
  (bounded-capacity framing, like any fixed-size wire format).
* sparse_dec: scatter-add values at indices into a zeroed dense vector.
* attn_ref / attn_decode_ref: FULL-softmax f32 attention matching the
  flash kernels' signatures — the serve-path trust anchor (the flash
  online-softmax results must land within fp32 tolerance of these before
  the kernel sits under model-serving traffic).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUANT_BM, QUANT_BN = 32, 128
SPARSE_B = 512  # elements per sparse block


def _pad2d(x, bm, bn):
    m, n = x.shape
    pm, pn = (-m) % bm, (-n) % bn
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


def quantize8_ref(x: jnp.ndarray):
    """x: [M, N] float -> (q int8 [Mp, Np], scales f32 [Mp/BM, Np/BN])."""
    xp = _pad2d(x.astype(jnp.float32), QUANT_BM, QUANT_BN)
    mp, np_ = xp.shape
    gm, gn = mp // QUANT_BM, np_ // QUANT_BN
    tiles = xp.reshape(gm, QUANT_BM, gn, QUANT_BN).transpose(0, 2, 1, 3)
    amax = jnp.max(jnp.abs(tiles), axis=(2, 3))
    scales = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.round(tiles / scales[:, :, None, None]).astype(jnp.int8)
    q = q.transpose(0, 2, 1, 3).reshape(mp, np_)
    return q, scales


def dequantize8_ref(q: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    mp, np_ = q.shape
    gm, gn = scales.shape
    tiles = q.reshape(gm, QUANT_BM, gn, QUANT_BN).transpose(0, 2, 1, 3)
    x = tiles.astype(jnp.float32) * scales[:, :, None, None]
    return x.transpose(0, 2, 1, 3).reshape(mp, np_)


def _sparse_dims(n: int, cap: int):
    nb = max(1, -(-n // SPARSE_B))
    kb = max(1, cap // nb)
    # sublane-align (8) the per-block capacity: the MXU one-hot matmul pads
    # lanes to 128 internally (VMEM cost only) but the wire format carries
    # the logical kb, so compression ratio follows the requested capacity
    kb = min(SPARSE_B, -(-kb // 8) * 8)
    return nb, kb


def sparse_enc_ref(flat: jnp.ndarray, cap: int, threshold: float = 0.0):
    """flat: [N] -> (values [nb*kb], indices int32 [nb*kb], nnz int32)."""
    n = flat.shape[0]
    nb, kb = _sparse_dims(n, cap)
    xp = jnp.pad(flat, (0, nb * SPARSE_B - n)).reshape(nb, SPARSE_B)
    mask = jnp.abs(xp) > threshold
    rank = jnp.cumsum(mask, axis=1) - 1                       # [nb, B]
    keep = mask & (rank < kb)
    base = (jnp.arange(nb, dtype=jnp.int32) * SPARSE_B)[:, None]
    gidx = base + jnp.arange(SPARSE_B, dtype=jnp.int32)[None, :]
    slot = jnp.where(keep, rank, kb)                          # dropped -> scratch slot
    vals = jnp.zeros((nb, kb + 1), xp.dtype)
    idxs = jnp.zeros((nb, kb + 1), jnp.int32) + base          # empty slot -> base
    row = jnp.arange(nb)[:, None]
    vals = vals.at[row, slot].set(jnp.where(keep, xp, 0.0))
    idxs = idxs.at[row, slot].set(jnp.where(keep, gidx, base))
    nnz = jnp.sum(jnp.minimum(jnp.sum(mask, axis=1), kb)).astype(jnp.int32)
    return vals[:, :kb].reshape(-1), idxs[:, :kb].reshape(-1), nnz


def sparse_dec_ref(values: jnp.ndarray, indices: jnp.ndarray,
                   nnz: jnp.ndarray, n: int) -> jnp.ndarray:
    del nnz  # zero-valued empty slots make the scatter-add a no-op
    total = int(np.prod(values.shape))
    dense = jnp.zeros((max(n, int(indices.max(initial=0)) + 1),), values.dtype) \
        if False else jnp.zeros((n + SPARSE_B,), values.dtype)
    dense = dense.at[indices.reshape(-1)].add(values.reshape(-1))
    return dense[:n]


NEG_INF = -1e30


def attn_ref(q, k, v, *, causal: bool = True, kv_groups: int = 1):
    """Full-softmax reference for ``flash_attention``: q [BH, Sq, dk],
    k/v [BH//kv_groups, Sk, d*] -> [BH, Sq, dv].  Materializes the whole
    [BH, Sq, Sk] score tensor (the thing flash exists to avoid) in f32."""
    bh, sq, dk = q.shape
    if kv_groups > 1:
        k = jnp.repeat(k, kv_groups, axis=0)
        v = jnp.repeat(v, kv_groups, axis=0)
    s = jnp.einsum("hqd,hkd->hqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (dk ** -0.5)
    if causal:
        sk = k.shape[1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p, v.astype(jnp.float32)).astype(q.dtype)


def attn_decode_ref(q, k, v, pos, *, kv_groups: int = 1):
    """Full-softmax reference for the serve path's decode attention
    (``models.layers.attn_decode``, one-piece ``_sdpa`` over the cache):
    q [BH, dk] (one query position), cached k/v [BKV, Sk, d*], ``pos`` the
    last valid cache index -> [BH, dv]."""
    dk = q.shape[-1]
    if kv_groups > 1:
        k = jnp.repeat(k, kv_groups, axis=0)
        v = jnp.repeat(v, kv_groups, axis=0)
    s = jnp.einsum("hd,hkd->hk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (dk ** -0.5)
    sk = k.shape[1]
    s = jnp.where((jnp.arange(sk) <= pos)[None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hk,hkd->hd", p, v.astype(jnp.float32)).astype(q.dtype)
