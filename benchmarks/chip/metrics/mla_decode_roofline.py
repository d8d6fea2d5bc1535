"""Absorbed latent attention's share of its roofline, %: the least time the
traced window's no-join serve ticks need for it (``counts_moe.
mla_decode_call`` for each occupied slot at its position, every layer)
over the device time of the operations the program compiled under
``mla_decode_attn`` in those ticks."""
from benchmarks.chip import counts_moe, moe_trace
from benchmarks.chip.peaks import min_seconds


def read(rec):
    device = moe_trace.scope_seconds(rec, "mla_decode_attn")
    least = 0.0
    for s in moe_trace.nojoin_ticks(rec):
        calls = [counts_moe.mla_decode_call(rec.cfg, c)
                 for c in s.attrs.get("contexts", ())]
        if calls:
            least += rec.cfg["num_hidden_layers"] * min_seconds(
                sum(f for f, _ in calls), sum(b for _, b in calls), rec.kind)
    if device <= 0 or least <= 0:
        return None
    return 100.0 * least / device
