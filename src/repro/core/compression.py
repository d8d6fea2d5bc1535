"""Stream codecs for inter-device transmission (paper: "Sparse tensors and
gst-gz support compressed transmissions"; clients "explicitly requested
sparse tensor streams to compress streams for language and speech models").

Codecs operate on whole StreamBuffers and report *wire bytes*, which the
benchmark harness uses to reproduce the bandwidth analysis.  The compute
hot-spots (quant8, sparse COO) are Pallas TPU kernels in repro.kernels.

Meta contract: ``encode`` stamps ``meta["codec"]`` on the *wire* buffer (the
payload really is encoded), and ``decode`` strips it again — a decoded frame
must never claim to be encoded, or a later ``decode(buf,
buf.meta["codec"])`` would corrupt the payload (double-decode) and wire
accounting would count decoded frames as compressed.  Anything that needs
the client's codec *preference* after decode (answer routing) re-attaches it
explicitly as routing meta.

Sparse encoding is capacity-bounded (block-COO): when the true nonzero count
exceeds the requested density the tail is dropped.  That loss is detected
and accounted — ``meta["sparse_dropped"]`` on the wire buffer carries the
dropped-value count and the module-level :func:`codec_stats` aggregate it —
so a lossy encode is never silent.  Truncation accounting is DEFERRED:
``_sparse_enc`` keeps the dropped count as a device scalar (no host sync per
tensor); eager :func:`encode` folds every tensor's scalar into ONE sync per
call, and the batched/fused paths carry the scalars out of the jit and sync
once per flush (see :func:`account_sparse_dropped`).

Three call layers share the same numerics bitwise:

* per-frame :func:`encode`/:func:`decode` — eager, host-level (pub/sub
  publish, legacy query round-trips);
* :func:`encode_stacked`/:func:`decode_stacked` — TRACEABLE, operate on a
  leading frame axis with the stacked kernel entry points; this is what the
  fused serving dispatch calls inside its jit;
* :func:`encode_batch`/:func:`decode_batch` — host-level batch helpers over
  same-structure frames: one stacked dispatch, ONE device fetch, numpy
  per-frame views out (eager per-frame splits would pay a dispatch per leaf
  per frame — the overhead batching exists to kill).

Wire-bytes accounting is computed from static payload shapes everywhere
(``wire_nbytes``) — no sync, valid even on traced payloads.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .buffers import Quant8Payload, SparsePayload, StreamBuffer

__all__ = ["encode", "decode", "encode_stacked", "decode_stacked",
           "encode_batch", "decode_batch", "wire_nbytes", "CODECS",
           "codec_stats", "reset_codec_stats", "account_sparse_dropped"]

CODECS = ("none", "quant8", "sparse")

#: meta keys describing the WIRE form of a buffer — stamped by encode,
#: stripped by decode (a decoded frame carries neither)
_WIRE_META = ("codec", "sparse_dropped")

# process-wide lossy-encode accounting (benchmarks / Runtime.stats surface
# this; tests reset it)
_CODEC_STATS = {"sparse_truncated_tensors": 0, "sparse_dropped_values": 0}


def codec_stats() -> Dict[str, int]:
    return dict(_CODEC_STATS)


def reset_codec_stats():
    for k in _CODEC_STATS:
        _CODEC_STATS[k] = 0


def account_sparse_dropped(per_tensor) -> int:
    """Fold synced per-tensor dropped counts (ints / numpy) into the
    process-wide codec stats; returns the total dropped values.  The single
    host sync point of the deferred truncation accounting — callers fetch
    their device scalars in one batch and hand the host values here."""
    per_tensor = [int(d) for d in per_tensor]
    total = sum(per_tensor)
    if total:
        _CODEC_STATS["sparse_truncated_tensors"] += \
            sum(1 for d in per_tensor if d)
        _CODEC_STATS["sparse_dropped_values"] += total
    return total


# ---------------------------------------------------------------------------
# per-tensor codec primitives (traceable; no host syncs)
# ---------------------------------------------------------------------------

def _quant8_enc(x: jnp.ndarray) -> Quant8Payload:
    from ..kernels import ops as kops
    from ..kernels.ops import _as2d
    q, scale = kops.quantize8(x)
    m, n = _as2d(x).shape
    return Quant8Payload(q=q, scale=scale, dtype=str(x.dtype),
                         shape=tuple(x.shape), view2d=(m, n))


def _quant8_dec(enc: Quant8Payload) -> jnp.ndarray:
    from ..kernels import ops as kops
    x = kops.dequantize8(enc.q, enc.scale)
    m, n = enc.view2d
    return x[:m, :n].astype(jnp.dtype(enc.dtype)).reshape(enc.shape)


def _sparse_cap(size: int, density: float) -> int:
    """Block-COO capacity for ``size`` elements at ``density``.

    ``density >= 1.0`` must be LOSSLESS: the naive ``int(size * density)``
    spread over ceil(size/B) blocks under-allocates per-block slots when
    ``size`` is not a multiple of the block (e.g. 600 elements -> 2 blocks
    of 300 slots, but 512 nonzeros can land in block 0), so full density
    pins every block at full capacity instead."""
    from ..kernels.ref import SPARSE_B
    if density >= 1.0:
        nb = max(1, -(-size // SPARSE_B))
        return nb * SPARSE_B
    return max(1, int(size * density))


def _sparse_enc(x: jnp.ndarray, density: float = 0.25
                ) -> Tuple[SparsePayload, jnp.ndarray]:
    """Returns (payload, dropped): ``dropped`` counts true nonzeros the
    capacity-bounded COO could not carry (0 = lossless encode).  It stays a
    DEVICE scalar — callers batch the sync (module docstring)."""
    from ..kernels import ops as kops
    cap = _sparse_cap(x.size, density)
    flat = x.reshape(-1)
    values, indices, nnz = kops.sparse_enc(flat, cap, 0.0)
    true_nnz = jnp.sum(jnp.abs(flat) > 0.0).astype(jnp.int32)
    dropped = jnp.maximum(0, true_nnz - nnz)
    return SparsePayload(values=values, indices=indices, nnz=nnz,
                         dense_shape=tuple(x.shape)), dropped


def _sparse_dec(sp: SparsePayload) -> jnp.ndarray:
    from ..kernels import ops as kops
    n = int(np.prod(sp.dense_shape))
    return kops.sparse_dec(sp.values, sp.indices, sp.nnz, n).reshape(sp.dense_shape)


# ---------------------------------------------------------------------------
# stacked codec primitives (leading frame axis; traceable)
# ---------------------------------------------------------------------------

def _view2d(shape: Tuple[int, ...]) -> Tuple[int, int]:
    """Logical 2d view of one frame (same rules as kernels/ops._as2d)."""
    if len(shape) == 0:
        return (1, 1)
    if len(shape) == 1:
        return (1, shape[0])
    return (int(np.prod(shape[:-1])), shape[-1])


def _quant8_enc_stacked(x: jnp.ndarray) -> Quant8Payload:
    """[B, *shape] -> stacked payload (q [B,Mp,Np], scale [B,gm,gn]); frame
    i is bitwise ``_quant8_enc(x[i])`` (tile merge, kernels/ops.py)."""
    from ..kernels import ops as kops
    q, scale = kops.quantize8_stacked(x)
    fshape = tuple(x.shape[1:])
    return Quant8Payload(q=q, scale=scale, dtype=str(x.dtype),
                         shape=fshape, view2d=_view2d(fshape))


def _quant8_dec_stacked(enc: Quant8Payload) -> jnp.ndarray:
    from ..kernels import ops as kops
    b = enc.q.shape[0]
    x = kops.dequantize8_stacked(enc.q, enc.scale)
    m, n = enc.view2d
    return x[:, :m, :n].astype(jnp.dtype(enc.dtype)).reshape((b,) + enc.shape)


def _sparse_enc_stacked(x: jnp.ndarray, density: float
                        ) -> Tuple[SparsePayload, jnp.ndarray]:
    """[B, *shape] -> (stacked payload, dropped int32 [B])."""
    from ..kernels import ops as kops
    fshape = tuple(x.shape[1:])
    size = int(np.prod(fshape)) if fshape else 1
    cap = _sparse_cap(size, density)
    flat = x.reshape(x.shape[0], size)
    values, indices, nnz = kops.sparse_enc_stacked(flat, cap, 0.0)
    true_nnz = jnp.sum(jnp.abs(flat) > 0.0, axis=1).astype(jnp.int32)
    dropped = jnp.maximum(0, true_nnz - nnz)
    return SparsePayload(values=values, indices=indices, nnz=nnz,
                         dense_shape=fshape), dropped


def _sparse_dec_stacked(sp: SparsePayload) -> jnp.ndarray:
    from ..kernels import ops as kops
    b = sp.values.shape[0]
    n = int(np.prod(sp.dense_shape))
    dense = kops.sparse_dec_stacked(sp.values, sp.indices, sp.nnz, n)
    return dense.reshape((b,) + sp.dense_shape)


# ---------------------------------------------------------------------------
# wire-bytes accounting (static shapes; no syncs)
# ---------------------------------------------------------------------------

def _payload_nbytes(t) -> int:
    # one source of truth for the wire framing: the payloads' own
    # wire_nbytes properties (buffers.py) / dense element bytes
    if isinstance(t, (Quant8Payload, SparsePayload)):
        return t.wire_nbytes
    return int(np.prod(t.shape)) * t.dtype.itemsize


def wire_nbytes(buf: StreamBuffer) -> int:
    """Wire bytes of an encoded buffer, from static payload shapes only —
    no device sync, valid even on traced payloads."""
    return sum(_payload_nbytes(t) for t in buf.tensors)


def _strip_wire_meta(meta: Dict) -> Dict:
    return {k: v for k, v in meta.items() if k not in _WIRE_META}


# ---------------------------------------------------------------------------
# per-frame eager API
# ---------------------------------------------------------------------------

def encode(buf: StreamBuffer, codec: str) -> Tuple[StreamBuffer, int]:
    """Returns (encoded buffer, wire bytes).  ``codec`` may carry a parameter:
    "sparse:0.15" bounds the COO capacity at 15% density."""
    codec, _, arg = codec.partition(":")
    if codec == "none":
        return buf, buf.nbytes()
    if codec == "quant8":
        enc = tuple(_quant8_enc(t) for t in buf.tensors)
        out = buf.with_(tensors=enc, meta={**buf.meta, "codec": "quant8"})
        return out, wire_nbytes(out)
    if codec == "sparse":
        density = float(arg) if arg else 0.25
        pairs = tuple(_sparse_enc(t, density) for t in buf.tensors)
        enc = tuple(p for p, _ in pairs)
        meta = {**buf.meta, "codec": "sparse"}
        # deferred truncation accounting: ONE host sync for the whole call
        # (the scalars were kept on device per tensor), folded into the
        # process stats and the wire buffer's loss signal together
        dropped = account_sparse_dropped(
            np.asarray(jnp.stack([d for _, d in pairs])))
        if dropped:
            meta["sparse_dropped"] = dropped
        out = buf.with_(tensors=enc, meta=meta)
        return out, wire_nbytes(out)
    raise ValueError(f"unknown codec {codec!r}")


def decode(buf: StreamBuffer, codec: str) -> StreamBuffer:
    codec, _, _ = codec.partition(":")
    if codec == "none":
        return buf
    if codec == "quant8":
        tensors = tuple(_quant8_dec(e) for e in buf.tensors)
    elif codec == "sparse":
        tensors = tuple(_sparse_dec(e) for e in buf.tensors)
    else:
        raise ValueError(f"unknown codec {codec!r}")
    # the payload is dense again: drop the wire-form meta — a stale
    # meta["codec"] on a decoded frame is a double-decode hazard and
    # mis-counts decoded frames as compressed in wire accounting
    return buf.with_(tensors=tensors, meta=_strip_wire_meta(buf.meta))


# ---------------------------------------------------------------------------
# stacked API (traceable — the fused serving dispatch calls these in-jit)
# ---------------------------------------------------------------------------

def encode_stacked(buf: StreamBuffer, codec: str
                   ) -> Tuple[StreamBuffer, Optional[jnp.ndarray]]:
    """Encode a STACKED buffer (leading frame axis) with one kernel
    dispatch per tensor.  Returns (stacked wire buffer, dropped int32
    [tensors, frames] or None) — frame ``i`` of every payload is bitwise
    ``encode(frame_i)``'s.  Traceable: the dropped counts stay on device
    and ``meta["sparse_dropped"]`` is NOT stamped here (the caller syncs
    once per flush and stamps host-side — see account_sparse_dropped)."""
    codec, _, arg = codec.partition(":")
    if codec == "none":
        return buf, None
    if codec == "quant8":
        enc = tuple(_quant8_enc_stacked(t) for t in buf.tensors)
        return buf.with_(tensors=enc,
                         meta={**buf.meta, "codec": "quant8"}), None
    if codec == "sparse":
        density = float(arg) if arg else 0.25
        pairs = tuple(_sparse_enc_stacked(t, density) for t in buf.tensors)
        enc = tuple(p for p, _ in pairs)
        dropped = jnp.stack([d for _, d in pairs])   # [tensors, frames]
        return buf.with_(tensors=enc,
                         meta={**buf.meta, "codec": "sparse"}), dropped
    raise ValueError(f"unknown codec {codec!r}")


def decode_stacked(buf: StreamBuffer, codec: str) -> StreamBuffer:
    """Decode a STACKED wire buffer (leading frame axis) with one kernel
    dispatch per tensor; frame ``i`` is bitwise ``decode(frame_i)``."""
    codec, _, _ = codec.partition(":")
    if codec == "none":
        return buf  # mirror per-frame decode: "none" is a strict no-op
    if codec == "quant8":
        tensors = tuple(_quant8_dec_stacked(e) for e in buf.tensors)
    elif codec == "sparse":
        tensors = tuple(_sparse_dec_stacked(e) for e in buf.tensors)
    else:
        raise ValueError(f"unknown codec {codec!r}")
    return buf.with_(tensors=tensors, meta=_strip_wire_meta(buf.meta))


# ---------------------------------------------------------------------------
# host-level batch helpers (one dispatch + one device fetch per group)
# ---------------------------------------------------------------------------

# The batch helpers run the stacked codec as ONE compiled program.  Run op
# by op, each reshape between the frames' shape and their flat wire view is
# an executable of its own, and the TPU compiler spends minutes on some of
# them (a VGA stack [B, 480, 640, 3] -> [B, 921600]); inside one program
# they merge with the kernels' own views.
_encode_stacked_jit = jax.jit(encode_stacked, static_argnames="codec")
_decode_stacked_jit = jax.jit(decode_stacked, static_argnames="codec")

def _stack_tensors(bufs: Sequence[StreamBuffer]):
    """Stack per-position tensors/payloads across same-structure frames."""
    cols = zip(*[b.tensors for b in bufs])
    return tuple(jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *col)
                 for col in cols)


def _host_frames(stacked_tensors, n: int):
    """ONE device fetch of the stacked pytree, then free numpy views per
    frame (an eager slice per leaf per frame would pay ~a dispatch each —
    the cost batching exists to remove).  Numpy leaves are bitwise the same
    frames; downstream jit calls device_put them on entry."""
    host = jax.device_get(stacked_tensors)
    return [jax.tree_util.tree_map(lambda l, _i=i: l[_i], host)
            for i in range(n)]


def encode_batch(bufs: Sequence[StreamBuffer], codec: str
                 ) -> List[Tuple[StreamBuffer, int]]:
    """Batched :func:`encode` over same-structure frames: one stacked
    kernel dispatch per tensor position, one device fetch, one truncation
    sync for the whole batch.  Element ``i`` is bitwise ``encode(bufs[i])``
    (payloads, meta — including ``sparse_dropped`` — and wire bytes)."""
    bufs = list(bufs)
    if not bufs:
        return []
    base, _, _ = codec.partition(":")
    if base == "none":
        return [(b, b.nbytes()) for b in bufs]
    n = len(bufs)
    stacked = StreamBuffer(tensors=_stack_tensors(bufs),
                           pts=jnp.int32(0), meta={})
    wire, dropped = _encode_stacked_jit(stacked, codec)
    per_tensor = ([] if dropped is None else
                  np.asarray(dropped))            # [tensors, frames], 1 sync
    frames = _host_frames(wire.tensors, n)
    out = []
    for i, (buf, tensors) in enumerate(zip(bufs, frames)):
        meta = {**buf.meta, "codec": base}
        if len(per_tensor):
            frame_dropped = account_sparse_dropped(per_tensor[:, i])
            if frame_dropped:
                meta["sparse_dropped"] = frame_dropped
        enc = buf.with_(tensors=tensors, meta=meta)
        out.append((enc, wire_nbytes(enc)))
    return out


def decode_batch(bufs: Sequence[StreamBuffer], codec: str
                 ) -> List[StreamBuffer]:
    """Batched :func:`decode` over same-structure wire frames: one stacked
    kernel dispatch per tensor position, one device fetch.  Element ``i``
    is bitwise ``decode(bufs[i])``."""
    bufs = list(bufs)
    if not bufs:
        return []
    base, _, _ = codec.partition(":")
    if base == "none":
        return bufs  # mirror per-frame decode: "none" is a strict no-op
    n = len(bufs)
    stacked = StreamBuffer(tensors=_stack_tensors(bufs),
                           pts=jnp.int32(0), meta={})
    dec = _decode_stacked_jit(stacked, codec)
    frames = _host_frames(dec.tensors, n)
    return [b.with_(tensors=t, meta=_strip_wire_meta(b.meta))
            for b, t in zip(bufs, frames)]
