"""Operations and HBM bytes of the work each kernel and each model token
needs, computed from shapes alone.

Counts follow the algorithm, not the implementation's layout: a decode
step's attention reads the keys and values of the valid positions
(``ctx + 1``), not the whole ``max_seq`` cache; a codec reads and writes
the frame's own HWC bytes, not a lane-padded tile view.  A change that
removes padding or dead reads then shows a higher share of the roofline,
never one above 100%.

A multiply-add counts as two operations.  ``cfg`` is a configuration's
JSON object (Hugging Face key names).
"""
from __future__ import annotations

from typing import Dict, Tuple

BF16 = 2
F32 = 4
INT8 = 1
INT32 = 4


def _dims(cfg: Dict) -> Tuple[int, int, int, int, int, int, int]:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = d // h
    return (d, h, kv, hd, cfg["intermediate_size"], cfg["num_hidden_layers"],
            cfg["vocab_size"])


def layer_matmul_params(cfg: Dict) -> int:
    """Weights one token multiplies through in one decoder layer: q, k, v,
    o projections and the gated MLP."""
    d, h, kv, hd, f, _, _ = _dims(cfg)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def weight_bytes(cfg: Dict) -> int:
    """Bytes of bf16 weights one decode step reads: every layer's matmul
    weights and the output head (the embedding is a row gather)."""
    d, _, _, _, _, n_layers, vocab = _dims(cfg)
    return BF16 * (n_layers * layer_matmul_params(cfg) + d * vocab)


def attention_flops(cfg: Dict, n_keys: int) -> int:
    """One query position attending to ``n_keys`` keys, all layers:
    scores and the weighted sum of values."""
    _, h, _, hd, _, n_layers, _ = _dims(cfg)
    return n_layers * 4 * h * hd * n_keys


def decode_token_flops(cfg: Dict, ctx: int) -> int:
    """One decoded token whose input sits at position ``ctx`` (it attends
    to ``ctx + 1`` keys), through every layer and the output head."""
    d, _, _, _, _, n_layers, vocab = _dims(cfg)
    return (2 * n_layers * layer_matmul_params(cfg) + 2 * d * vocab
            + attention_flops(cfg, ctx + 1))


def prefill_flops(cfg: Dict, n: int) -> int:
    """A prompt of ``n`` tokens: every position through every layer, causal
    attention over the positions before it, and the output head at the
    last position only (prefill returns the first generated token)."""
    d, h, _, hd, _, n_layers, vocab = _dims(cfg)
    causal_pairs = n * (n + 1) // 2
    return (2 * n * n_layers * layer_matmul_params(cfg) + 2 * d * vocab
            + n_layers * 4 * h * hd * causal_pairs)


def flash_decode_call(cfg: Dict, ctx: int) -> Tuple[int, int]:
    """(flops, bytes) of one layer's decode attention for one sequence at
    position ``ctx``: the query row, the ``ctx + 1`` valid keys and values
    in bf16, and the output row."""
    _, h, kv, hd, _, _, _ = _dims(cfg)
    n = ctx + 1
    flops = 4 * h * hd * n
    nbytes = BF16 * (2 * n * kv * hd + 2 * h * hd)
    return flops, nbytes


def flash_prefill_call(cfg: Dict, n: int) -> Tuple[int, int]:
    """(flops, bytes) of one layer's causal flash attention over ``n``
    prompt positions: q, k, v read once and the output written, in bf16;
    only the causal half of the score matrix is work."""
    _, h, kv, hd, _, _, _ = _dims(cfg)
    flops = 4 * h * hd * (n * (n + 1) // 2)
    nbytes = BF16 * (2 * n * h * hd + 2 * n * kv * hd)
    return flops, nbytes


# ---------------------------------------------------------------------------
# stream codecs (one frame of ``size`` float32 values)
# ---------------------------------------------------------------------------

#: quant8 keeps one float32 scale per tile of this many frame values
#: (32 rows of the frame's last axis; a VGA HWC frame: 32 pixels x 3)
QUANT_ROWS = 32
#: sparse keeps this share of each 512-value block
SPARSE_DENSITY = 0.25


def codec_call(codec: str, direction: str, size: int, last_axis: int
               ) -> Tuple[int, int]:
    """(ops, bytes) of encoding (``direction="enc"``) or decoding one frame
    of ``size`` float32 values whose last axis has ``last_axis`` values.

    quant8: encode reads the frame, takes each tile's absmax, divides and
    rounds (3 ops a value), writes int8 values and one scale a tile; decode
    reads them back and multiplies (1 op a value).  sparse: encode tests
    every value and writes the kept values with int32 indices; decode
    reads those and writes the dense frame."""
    if codec == "quant8":
        scales = -(-size // (QUANT_ROWS * last_axis))
        wire = size * INT8 + scales * F32
        if direction == "enc":
            return 3 * size, size * F32 + wire
        return size, wire + size * F32
    if codec == "sparse":
        kept = int(size * SPARSE_DENSITY)
        wire = kept * (F32 + INT32)
        if direction == "enc":
            return size, size * F32 + wire
        return kept, wire + size * F32
    raise ValueError(f"no counts for codec {codec!r}")
