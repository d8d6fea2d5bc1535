"""Admit path per tick with a join, ms: ``build_admit`` with at least one
joining request plus that tick's serve dispatch (which ships the bundle
to the device and merges it)."""
from benchmarks.chip.harness import join_ticks_ms


def read(rec):
    v = join_ticks_ms(rec)
    return sum(v) / len(v) if v else None
