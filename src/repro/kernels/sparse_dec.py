"""Pallas TPU kernel: block-COO sparse decode (``tensor_sparse_dec``).

Inverse of sparse_enc: each grid step reconstructs one B=512 dense block from
its KB coordinate slots.  GPU would scatter with atomics; TPU has no scatter
in VMEM, so we again use a one-hot MXU matmul:

    local   = indices - block_base                 # [KB]
    onehotT = (arange(B)[:,None] == local[None,:]) # [B, KB]
    dense   = values @ onehotT.T                   # MXU   [B]

Empty slots carry (value=0, index=block_base): their one-hot row is real but
the zero value contributes nothing — the "no-op scatter" trick that keeps
the framing fixed-capacity and the kernel branch-free.

Off-TPU the interpreter's per-block one-hot emulation is ~10× slower than
XLA's native scatter-add, which IS the decode contract (real indices are
unique within a block; empty slots add 0):  :func:`sparse_dec_xla` is the
bitwise-identical fast path ``ops.sparse_dec`` dispatches to on non-TPU
backends (pinned by tests/test_wire_path.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import SPARSE_B


#: COO blocks per grid step (TPU block rows divide by 8 or span the array)
BLOCKS_PER_STEP = 8


def _dec_kernel(vals_ref, idx_ref, out_ref):
    g, kb = vals_ref.shape
    b = out_ref.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (b, kb), 0)
    for r in range(g):
        local = idx_ref[r:r + 1, :] - (pl.program_id(0) * g + r) * b  # [1, KB]
        onehot_t = (rows == local).astype(jnp.float32)               # [B, KB]
        v = vals_ref[r:r + 1, :].astype(jnp.float32)                 # [1, KB]
        # HIGHEST: each value passes the MXU exactly (bitwise the scatter)
        dense = jax.lax.dot_general(v, onehot_t, (((1,), (1,)), ((), ())),
                                    precision=jax.lax.Precision.HIGHEST,
                                    preferred_element_type=jnp.float32)
        out_ref[r:r + 1, :] = dense.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sparse_dec_pallas(v2: jnp.ndarray, i2: jnp.ndarray, *, interpret: bool = True):
    """v2/i2: [nb, kb] block-COO -> dense [nb*B] (block b owns indices
    [b*B, (b+1)*B))."""
    nb, kb = v2.shape
    g = min(BLOCKS_PER_STEP, nb)
    nbp = -(-nb // g) * g
    pad = ((0, nbp - nb), (0, 0))
    out = pl.pallas_call(
        _dec_kernel,
        grid=(nbp // g,),
        in_specs=[
            pl.BlockSpec((g, kb), lambda i: (i, 0)),
            pl.BlockSpec((g, kb), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((g, SPARSE_B), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nbp, SPARSE_B), v2.dtype),
        interpret=interpret,
    )(jnp.pad(v2, pad), jnp.pad(i2, pad))
    return out[:nb].reshape(-1)


@jax.jit
def sparse_dec_xla(v2: jnp.ndarray, i2: jnp.ndarray):
    """Scatter-add statement of the block decode: same signature and
    bitwise-same output as :func:`sparse_dec_pallas` (each dense position
    receives exactly one real value or only zero-valued empty slots)."""
    nb, _ = v2.shape
    dense = jnp.zeros((nb * SPARSE_B,), v2.dtype)
    return dense.at[i2.reshape(-1)].add(v2.reshape(-1))
