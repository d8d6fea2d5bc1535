"""The codec kernels' share of their roofline, %: the least time the
traced window's encodes and decodes need on the frames' own bytes
(``counts.codec_call``) over the device time of the quant8 and sparse
kernels in the trace."""
from benchmarks.chip.harness import codec_traced_work
from benchmarks.chip.peaks import min_seconds

#: the Pallas kernels' custom calls, as the device trace names them
NEEDLES = ("quantize8_pallas", "sparse_enc_pallas", "sparse_dec_pallas")


def read(rec):
    if rec.trace is None:
        return None
    device = rec.trace.op_seconds(NEEDLES)
    least = sum(n * min_seconds(ops, nbytes, rec.kind)
                for ops, nbytes, n in codec_traced_work(rec))
    if device <= 0 or least <= 0:
        return None
    return 100.0 * least / device
