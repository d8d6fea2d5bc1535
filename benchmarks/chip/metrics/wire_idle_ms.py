"""Device idle inside a fused wire serve, ms: for each of the program's
``wire.serve`` spans in the trace, the part of it in which no operation
ran on the device, mean over the traced window."""
from benchmarks.chip.program_trace import mean_idle_ms


def read(rec):
    return mean_idle_ms(rec, "wire.serve")
