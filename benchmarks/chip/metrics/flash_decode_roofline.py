"""Decode attention's share of its roofline, %: the least time the traced
serve ticks' decode attention needs (``counts.flash_decode_call`` for each
occupied slot at its position, every layer) over the device time of the
loops inside the serve-tick program's executions.

``flash_decode_step`` is a ``lax.scan`` over key blocks, one loop
(``%while``) per layer, and the serve tick has no other loop; a kernel
that replaces it leaves this metric with nothing to read."""
from bisect import bisect_right

from benchmarks.chip import counts
from benchmarks.chip.peaks import min_seconds


def read(rec):
    t = rec.trace
    if t is None:
        return None
    ticks = sorted((m.start, m.end) for m in t.modules
                   if "serve_tick" in m.name)
    starts = [a for a, _ in ticks]

    def in_tick(o):
        i = bisect_right(starts, o.start) - 1
        return i >= 0 and o.start + o.dur <= ticks[i][1]
    device = sum(o.dur for o in t.ops
                 if o.name.startswith("%while") and in_tick(o))
    least = 0.0
    for s in rec.in_trace("serve_tick"):
        calls = [counts.flash_decode_call(rec.cfg, c)
                 for c in s.attrs.get("contexts", ())]
        if calls:
            least += rec.cfg["num_hidden_layers"] * min_seconds(
                sum(f for f, _ in calls), sum(b for _, b in calls), rec.kind)
    if device <= 0 or least <= 0:
        return None
    return 100.0 * least / device
