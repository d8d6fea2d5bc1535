"""Wait of a request at the batcher before its prefill starts, ms: the
request stamps ``arrive`` (taken into the batcher's admission queue) to
``prefill``, mean over the requests whose prefill started in the
window."""
from benchmarks.chip.program_trace import program_of


def read(rec):
    prog = program_of(rec)
    if prog is None:
        return None
    a, b = prog.window
    v = [r["prefill"] - r["arrive"] for r in prog.requests
         if "prefill" in r and a <= r["prefill"] < b]
    return 1e3 * sum(v) / len(v) if v else None
