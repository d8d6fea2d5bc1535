"""Share of the decode slots that held a stream, %: occupied slots summed
over the window's decode ticks over the ticks times the slots (the
``active`` and ``slots`` attributes of each ``lm.decode`` span; the
batcher's ``slot_ticks`` counter adds the same ``active``)."""
from benchmarks.chip.program_trace import program_of


def read(rec):
    prog = program_of(rec)
    ticks = prog.named("lm.decode") if prog is not None else []
    slots = sum(d.attrs.get("slots", 0) for d in ticks)
    if not slots:
        return None
    return 100.0 * sum(d.attrs.get("active", 0) for d in ticks) / slots
