"""Scheduler self time per tick, ms: each ``Runtime.tick`` span minus the
spans of the layers below it (prefill, admit, serve dispatch, codecs),
for the ticks of the traced window."""
from benchmarks.chip.harness import self_seconds


def read(rec):
    v = self_seconds(rec.in_trace(""), "tick")
    return 1e3 * sum(v) / len(v) if v else None
