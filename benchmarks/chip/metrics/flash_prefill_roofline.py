"""Prefill flash attention's share of its roofline, %: the least time the
causal attention of each traced prefill needs (``counts.
flash_prefill_call`` per layer) over the device time of the flash
attention kernel in the trace."""
from benchmarks.chip import counts
from benchmarks.chip.peaks import min_seconds

NEEDLES = ("_flash_kernel", "flash_attention")


def read(rec):
    if rec.trace is None:
        return None
    device = rec.trace.op_seconds(NEEDLES)
    least = sum(rec.cfg["num_hidden_layers"] * min_seconds(
        *counts.flash_prefill_call(rec.cfg, s.attrs["n"]), rec.kind)
        for s in rec.in_trace("host_prefill"))
    if device <= 0 or least <= 0:
        return None
    return 100.0 * least / device
