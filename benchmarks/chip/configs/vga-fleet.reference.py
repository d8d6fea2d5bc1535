"""Plain reference of a camera frame's round trip through the offloading
fabric, in numpy: the client's test pattern made float, the client's codec
encode, the hub's decode, the per-pixel ReLU filter, the hub's answer
encode and the client's decode.

Written from the codecs' stated semantics, not from their code:

* quant8: the frame viewed as rows of its last axis is cut into tiles of
  32 rows by 128 columns (zero-padded); each tile keeps
  ``round(x / s)`` as int8 with ``s = absmax / 127`` (1 for a zero tile),
  and decodes to ``q * s``.
* sparse (density 0.25): the flattened frame is cut into blocks of 512
  values; each block keeps its first ``kb`` nonzero values in position
  order, ``kb`` being the per-block share of ``size * density`` rounded up
  to a multiple of 8; the rest decode to zero.

``dtype`` runs the same arithmetic in another precision (the control
computes it in bfloat16).
"""
from __future__ import annotations

import numpy as np

TILE_ROWS, TILE_COLS, BLOCK = 32, 128, 512


def client_frame(shape, index: int, dtype=np.float32) -> np.ndarray:
    """The camera's test pattern for frame ``index`` minus 127.5."""
    h, w, c = shape
    y = np.arange(h)[:, None, None]
    x = np.arange(w)[None, :, None]
    ch = np.arange(c)[None, None, :]
    pattern = (y * 3 + x * 5 + ch * 17 + index * 7) % 256
    return pattern.astype(dtype) - dtype(127.5)


def quant8_round_trip(x: np.ndarray) -> np.ndarray:
    dt = x.dtype.type
    rows = x.reshape(-1, x.shape[-1]) if x.ndim > 1 else x.reshape(1, -1)
    m, n = rows.shape
    pm, pn = -m % TILE_ROWS, -n % TILE_COLS
    padded = np.pad(rows, ((0, pm), (0, pn)))
    gm, gn = padded.shape[0] // TILE_ROWS, padded.shape[1] // TILE_COLS
    tiles = padded.reshape(gm, TILE_ROWS, gn, TILE_COLS)
    amax = np.abs(tiles).max(axis=(1, 3), keepdims=True)
    scale = np.where(amax > 0, amax / dt(127.0), dt(1.0)).astype(x.dtype)
    q = np.round(tiles / scale).astype(np.int8)
    out = (q.astype(x.dtype) * scale).reshape(padded.shape)
    return out[:m, :n].reshape(x.shape)


def sparse_round_trip(x: np.ndarray, density: float = 0.25) -> np.ndarray:
    flat = x.reshape(-1)
    size = flat.size
    nb = -(-size // BLOCK)
    kb = max(1, max(1, int(size * density)) // nb)
    kb = min(BLOCK, -(-kb // 8) * 8)
    blocks = np.pad(flat, (0, nb * BLOCK - size)).reshape(nb, BLOCK)
    nonzero = blocks != 0
    keep = nonzero & (np.cumsum(nonzero, axis=1) <= kb)
    return np.where(keep, blocks, 0).reshape(-1)[:size].reshape(x.shape)


ROUND_TRIPS = {"quant8": quant8_round_trip, "sparse": sparse_round_trip}


def answer(shape, index: int, codec: str, dtype=np.float32) -> np.ndarray:
    """What the client should hold for frame ``index`` sent with ``codec``."""
    trip = ROUND_TRIPS[codec]
    served = trip(client_frame(shape, index, dtype))
    return trip(np.maximum(served, dtype(0)))
