"""Fetch of the joiners' prefilled caches per join tick, ms: the
program's ``lm.admit.fetch`` spans (one ``device_get`` per joiner) summed
over each decode tick of the window that took a stream into a slot."""
from benchmarks.chip.program_trace import per_join_tick_ms


def read(rec):
    v = per_join_tick_ms(rec, ("lm.admit.fetch",))
    return sum(v) / len(v) if v else None
