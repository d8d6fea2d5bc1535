"""Host assembly of the admit bundle per join tick, ms: the program's
``lm.admit.copy`` spans (the copy of the zero slot-table base, then each
joiner's scatter into its rows) summed over each join tick of the
window."""
from benchmarks.chip.program_trace import per_join_tick_ms


def read(rec):
    v = per_join_tick_ms(rec, ("lm.admit.copy",))
    return sum(v) / len(v) if v else None
