"""One fused wire serve per batch, ms: decode of the requests, the hub's
pipeline, re-encode of the answers, their fetch and routing (the
``wire_serve`` span), over the traced window."""


def read(rec):
    v = [s.dur for s in rec.in_trace("wire_serve")]
    return 1e3 * sum(v) / len(v) if v else None
