"""Published peaks of the accelerators the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error: a share
of a peak is never computed against a guessed or default peak."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' (per chip)",
    },
}


def peaks_for(kind: str) -> dict:
    """The peaks of one chip of ``kind``; raises KeyError for a kind that
    has no published entry here."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def min_seconds(flops: float, nbytes: float, kind: str) -> float:
    """Least time one chip could take for ``flops`` operations moving
    ``nbytes`` bytes of HBM traffic: the larger of the two bounds."""
    p = peaks_for(kind)
    return max(flops / p["bf16_flops_per_s"], nbytes / p["hbm_bytes_per_s"])
