"""The join tick's serve call through its answer fetch, ms: the
program's ``lm.decode.dispatch`` and ``lm.decode.fetch`` spans of each
join tick of the window.  The dispatch ships the admit bundle to the
device and the fetch waits for the tick to run: the part of
``admit_ms`` that comes from ``serve_tick.join``."""
from benchmarks.chip.program_trace import per_join_tick_ms


def read(rec):
    v = per_join_tick_ms(rec, ("lm.decode.dispatch", "lm.decode.fetch"))
    return sum(v) / len(v) if v else None
