"""Pallas TPU kernel: block-COO sparse encoding (``tensor_sparse_enc``).

GPU stream-compaction uses warp ballots + shared-memory prefix sums — none of
which exist on TPU.  The TPU-native adaptation reformulates compaction as a
**one-hot matmul on the MXU**:

    mask    = |x| > threshold                      # [B]   VPU compare
    rank    = cumsum(mask) - 1                     # [B]   VPU scan
    onehot  = (rank[None,:] == slots[:,None]) & mask   # [KB, B]
    values  = onehot @ x                           # MXU   [KB]
    indices = onehot @ arange(B) + block_base      # MXU   [KB]

The running rank is itself a matmul (``mask @ triu(ones)``, since TPU
kernels have no scan).  Each grid step compacts ``BLOCKS_PER_STEP``
B=512-element blocks, each into its KB capacity slots; empty slots produce
(0, block_base) which decode treats as a no-op.  All operands are
VMEM-resident (the B×B triangle and one B*KB one-hot are 1 MiB each at
worst, well under the ~16 MiB VMEM budget).

Off-TPU the Pallas interpreter executes the grid loop step by step, and the
one-hot's O(nb·kb·B) materialization makes the *emulation* the slowest thing
on the wire path (~100 ms for one LM-activation frame).  The XLA fast path
(:func:`sparse_enc_xla`) states the identical block-COO contract as a rank
search instead: the k-th kept slot of a block is the position of the k-th
nonzero, i.e. ``searchsorted(cumsum(mask), k+1)`` — O(nb·kb·log B) gathers,
~36× faster under jit on CPU, and **bitwise identical** to the kernel
(pinned by tests/test_wire_path.py).  ``ops.sparse_enc`` dispatches: Pallas
on TPU silicon, XLA everywhere else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import SPARSE_B


#: COO blocks per grid step: the TPU block-shape rule wants the row count
#: of a block to divide by 8 (or to span the array)
BLOCKS_PER_STEP = 8


def _enc_kernel(x_ref, vals_ref, idx_ref, cnt_ref, *, kb: int, threshold: float):
    g, b = x_ref.shape
    x = x_ref[...].astype(jnp.float32)                        # [G, B]
    mask = (jnp.abs(x) > threshold).astype(jnp.float32)
    # inclusive prefix count as a matmul with an upper-triangular ones
    # matrix (0/1 operands: exact at any MXU precision)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (b, b), 0) <=
           jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)).astype(jnp.float32)
    rank = jnp.dot(mask, tri, preferred_element_type=jnp.float32) - 1.0
    slots = jax.lax.broadcasted_iota(jnp.int32, (kb, b), 0).astype(jnp.float32)
    local = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1).astype(jnp.float32)
    nt = (((1,), (1,)), ((), ()))                             # a @ b.T
    for r in range(g):
        keep = mask[r:r + 1] > 0
        onehot = ((rank[r:r + 1] == slots) & keep).astype(jnp.float32)
        # HIGHEST: the selected values and indices (< 2^24) pass the MXU
        # exactly, bitwise the XLA gather
        vals = jax.lax.dot_general(x[r:r + 1], onehot, nt,
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)
        idx = jax.lax.dot_general(local, onehot, nt,
                                  precision=jax.lax.Precision.HIGHEST,
                                  preferred_element_type=jnp.float32)
        base = (pl.program_id(0) * g + r) * b
        vals_ref[r:r + 1, :] = vals.astype(vals_ref.dtype)
        idx_ref[r:r + 1, :] = idx.astype(jnp.int32) + base
        cnt = jnp.sum(mask[r:r + 1], axis=1, keepdims=True).astype(jnp.int32)
        cnt_ref[r:r + 1, :] = jnp.minimum(cnt, kb)


@functools.partial(jax.jit, static_argnames=("kb", "threshold", "interpret"))
def sparse_enc_pallas(flat: jnp.ndarray, *, kb: int, threshold: float = 0.0,
                      interpret: bool = True):
    """flat: [nb*B] -> (values [nb*kb], indices int32 [nb*kb], counts int32 [nb])."""
    n = flat.shape[0]
    nb = n // SPARSE_B
    g = min(BLOCKS_PER_STEP, nb)
    nbp = -(-nb // g) * g
    x2 = jnp.pad(flat.reshape(nb, SPARSE_B), ((0, nbp - nb), (0, 0)))
    vals, idxs, cnts = pl.pallas_call(
        functools.partial(_enc_kernel, kb=kb, threshold=threshold),
        grid=(nbp // g,),
        in_specs=[pl.BlockSpec((g, SPARSE_B), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((g, kb), lambda i: (i, 0)),
            pl.BlockSpec((g, kb), lambda i: (i, 0)),
            pl.BlockSpec((g, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nbp, kb), flat.dtype),
            jax.ShapeDtypeStruct((nbp, kb), jnp.int32),
            jax.ShapeDtypeStruct((nbp, 1), jnp.int32),
        ],
        interpret=interpret,
    )(x2)
    return vals[:nb].reshape(-1), idxs[:nb].reshape(-1), cnts[:nb].reshape(-1)


@functools.partial(jax.jit, static_argnames=("kb", "threshold"))
def sparse_enc_xla(flat: jnp.ndarray, *, kb: int, threshold: float = 0.0):
    """Vectorized XLA statement of the block-COO encode (module docstring):
    same signature and bitwise-same outputs as :func:`sparse_enc_pallas`.

    ``pos[r, k] = searchsorted(cumsum(mask[r]), k+1)`` is the position of
    the (k+1)-th nonzero of block ``r`` (B for an exhausted block — masked
    to the (0, block_base) empty-slot framing the kernel emits)."""
    n = flat.shape[0]
    nb = n // SPARSE_B
    x2 = flat.reshape(nb, SPARSE_B)
    mask = jnp.abs(x2.astype(jnp.float32)) > threshold
    csum = jnp.cumsum(mask.astype(jnp.int32), axis=1)          # [nb, B]
    ks = jnp.arange(1, kb + 1, dtype=jnp.int32)                # [kb]
    pos = jax.vmap(lambda c: jnp.searchsorted(c, ks, side="left"))(csum)
    valid = pos < SPARSE_B                                     # k < block nnz
    posc = jnp.minimum(pos, SPARSE_B - 1).astype(jnp.int32)
    base = (jnp.arange(nb, dtype=jnp.int32) * SPARSE_B)[:, None]
    vals = jnp.where(valid, jnp.take_along_axis(x2, posc, axis=1), 0)
    idxs = jnp.where(valid, base + posc, base).astype(jnp.int32)
    cnts = jnp.minimum(csum[:, -1], kb).astype(jnp.int32)
    return (vals.astype(flat.dtype).reshape(-1), idxs.reshape(-1), cnts)
