"""Share of the chip's bf16 peak that the model's own work fills over the
traced window, %: prefill and decode operations counted from shapes
(``counts.py``) for the requests and slots the traced window served."""
from benchmarks.chip.harness import lm_traced_flops
from benchmarks.chip.peaks import peaks_for


def read(rec):
    flops = lm_traced_flops(rec)
    span = rec.traced[1] - rec.traced[0]
    if not flops or span <= 0:
        return None
    return 100.0 * flops / (span * peaks_for(rec.kind)["bf16_flops_per_s"])
