"""Bytes moved per join tick, MB: the joiners' caches fetched to the host
and the admit bundle shipped back, over the join ticks of the window.
The program adds the same two numbers to the batcher's
``admit_d2h_bytes`` and ``admit_h2d_bytes`` counters and to the join
tick's ``lm.decode`` span (``d2h_bytes``, ``h2d_bytes``), which is read
here."""
from benchmarks.chip.program_trace import join_ticks, program_of


def read(rec):
    prog = program_of(rec)
    ticks = join_ticks(prog) if prog is not None else []
    if not ticks:
        return None
    moved = sum(d.attrs.get("d2h_bytes", 0) + d.attrs.get("h2d_bytes", 0)
                for d in ticks)
    return moved / len(ticks) / 1e6
