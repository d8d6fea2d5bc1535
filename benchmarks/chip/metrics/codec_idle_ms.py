"""Device idle inside a batched codec call, ms: for each of the program's
``codec.batch`` spans in the trace (the cameras' request encodes and
answer decodes), the part of it in which no operation ran on the device,
mean over the traced window."""
from benchmarks.chip.program_trace import mean_idle_ms


def read(rec):
    return mean_idle_ms(rec, "codec.batch")
