"""Pallas TPU kernel: per-tile symmetric int8 quantization (stream codec).

The among-device transport compresses activation streams by narrowing
bf16/f32 frames to int8 + per-tile scales (the TPU-native analogue of the
paper's gst-gz/JPEG frame codecs — on TPU, bandwidth is saved by dtype
narrowing, not byte-level entropy coding).

Tiling: (32, 128) blocks — int8 native tile on TPU (sublane 32 × lane 128);
one f32 scale per tile.  The wrapper lays the tiles out as one column
(``_tile_column``); each grid step reads ``TILES_PER_STEP`` tiles into VMEM
and, tile by tile, computes the absmax and writes the quantized tile plus
its scale (a scalar into an SMEM block).  A block of one tile and one
scale would break the TPU's rule that a block's last two dimensions divide
by (8, 128) or span the array.

:func:`quantize8_xla`/:func:`dequantize8_xla` are the bitwise-identical
vectorized XLA statements of the same per-tile contract — the fast path
``ops`` dispatches to off-TPU, where the Pallas interpreter pays a Python
grid loop per (32, 128) tile (pinned by tests/test_wire_path.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import QUANT_BM, QUANT_BN


def _as_tiles(x: jnp.ndarray):
    """[M, N] -> [gm, gn, BM, BN] tile view (M % BM == 0, N % BN == 0)."""
    m, n = x.shape
    gm, gn = m // QUANT_BM, n // QUANT_BN
    return x.reshape(gm, QUANT_BM, gn, QUANT_BN).transpose(0, 2, 1, 3)


def _from_tiles(t: jnp.ndarray):
    gm, gn, bm, bn = t.shape
    return t.transpose(0, 2, 1, 3).reshape(gm * bm, gn * bn)


#: tiles per grid step (the wrapper pads the tile count up to a multiple)
TILES_PER_STEP = 64


def _quant_kernel(x_ref, q_ref, s_ref):
    def tile(r, carry):
        rows = pl.ds(pl.multiple_of(r * QUANT_BM, QUANT_BM), QUANT_BM)
        x = x_ref[rows, :].astype(jnp.float32)
        amax = jnp.max(jnp.abs(x))
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        q_ref[rows, :] = jnp.round(x / scale).astype(jnp.int8)
        s_ref[r, 0] = scale
        return carry
    jax.lax.fori_loop(0, s_ref.shape[0], tile, 0)


def _dequant_kernel(q_ref, s_ref, x_ref):
    def tile(r, carry):
        rows = pl.ds(pl.multiple_of(r * QUANT_BM, QUANT_BM), QUANT_BM)
        x_ref[rows, :] = q_ref[rows, :].astype(jnp.float32) * s_ref[r, 0]
        return carry
    jax.lax.fori_loop(0, s_ref.shape[0], tile, 0)


def _tile_column(x: jnp.ndarray) -> jnp.ndarray:
    """[M, N] -> [T*BM, BN]: every (BM, BN) tile stacked in row-major tile
    order, so one tile column carries the whole array (a no-op view when
    N == BN).  Keeps the kernel's blocks and its per-tile scale blocks
    inside the TPU's (8, 128) block-shape rule for any M and N."""
    gm, gn = x.shape[0] // QUANT_BM, x.shape[1] // QUANT_BN
    return _as_tiles(x).reshape(gm * gn * QUANT_BM, QUANT_BN)


def _steps(n_tiles: int):
    """-> (tiles per grid step, padded tile count)."""
    r = min(TILES_PER_STEP, n_tiles)
    return r, -(-n_tiles // r) * r


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize8_pallas(x: jnp.ndarray, *, interpret: bool = True):
    """x: [M, N] (M % 32 == 0, N % 128 == 0) -> (q int8 [M,N], scales [M/32, N/128])."""
    m, n = x.shape
    gm, gn = m // QUANT_BM, n // QUANT_BN
    r, t = _steps(gm * gn)
    col = jnp.pad(_tile_column(x), ((0, (t - gm * gn) * QUANT_BM), (0, 0)))
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(t // r,),
        in_specs=[pl.BlockSpec((r * QUANT_BM, QUANT_BN), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((r * QUANT_BM, QUANT_BN), lambda i: (i, 0)),
            pl.BlockSpec((r, 1), lambda i: (i, 0), memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t * QUANT_BM, QUANT_BN), jnp.int8),
            jax.ShapeDtypeStruct((t, 1), jnp.float32),
        ],
        interpret=interpret,
    )(col)
    q = q[:gm * gn * QUANT_BM].reshape(gm, gn, QUANT_BM, QUANT_BN)
    return _from_tiles(q), s[:gm * gn].reshape(gm, gn)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequantize8_pallas(q: jnp.ndarray, scales: jnp.ndarray, *,
                       interpret: bool = True):
    gm, gn = scales.shape
    r, t = _steps(gm * gn)
    pad = t - gm * gn
    col = jnp.pad(_tile_column(q), ((0, pad * QUANT_BM), (0, 0)))
    s = jnp.pad(scales.reshape(gm * gn, 1), ((0, pad), (0, 0)),
                constant_values=1.0)
    x = pl.pallas_call(
        _dequant_kernel,
        grid=(t // r,),
        in_specs=[
            pl.BlockSpec((r * QUANT_BM, QUANT_BN), lambda i: (i, 0)),
            pl.BlockSpec((r, 1), lambda i: (i, 0), memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((r * QUANT_BM, QUANT_BN), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t * QUANT_BM, QUANT_BN), jnp.float32),
        interpret=interpret,
    )(col, s)
    x = x[:gm * gn * QUANT_BM].reshape(gm, gn, QUANT_BM, QUANT_BN)
    return _from_tiles(x)


@jax.jit
def quantize8_xla(x: jnp.ndarray):
    """Same contract and bitwise-same outputs as :func:`quantize8_pallas`."""
    tiles = _as_tiles(x.astype(jnp.float32))
    amax = jnp.max(jnp.abs(tiles), axis=(2, 3))
    scales = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.round(tiles / scales[:, :, None, None]).astype(jnp.int8)
    return _from_tiles(q), scales


@jax.jit
def dequantize8_xla(q: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    tiles = _as_tiles(q).astype(jnp.float32) * scales[:, :, None, None]
    return _from_tiles(tiles)
