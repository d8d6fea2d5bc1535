"""The routed experts' share of their roofline, %: the least time the held
experts of every expert layer need in the traced window's no-join serve
ticks (``counts_moe.expert_call`` on each tick's per-layer, per-expert
load from the tick's load lane) over the device time of the operations the
program compiled under ``moe_experts`` in those ticks."""
from benchmarks.chip import counts_moe, moe_trace
from benchmarks.chip.peaks import min_seconds


def read(rec):
    device = moe_trace.scope_seconds(rec, "moe_experts")
    least = sum(min_seconds(*counts_moe.expert_call(rec.cfg, layer), rec.kind)
                for s in moe_trace.nojoin_ticks(rec)
                for layer in s.attrs["moe_load"])
    if device <= 0 or least <= 0:
        return None
    return 100.0 * least / device
