"""Prefill per request, ms: the ``host_prefill`` span, which ends when the
first token has been read back to the host; the traced window's calls."""


def read(rec):
    v = [s.dur for s in rec.in_trace("host_prefill")]
    return 1e3 * sum(v) / len(v) if v else None
