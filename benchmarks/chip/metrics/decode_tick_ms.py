"""Device time per decode tick without a join, ms: the executions of the
serve-tick program that overlap a ``serve_tick.nojoin`` span of the traced
window more than any other serve span (device and host clocks of a trace
can differ by about a millisecond)."""


def _overlap(a, b):
    return min(a.start + a.dur, b.start + b.dur) - max(a.start, b.start)


def read(rec):
    t = rec.trace
    if t is None:
        return None
    serves = [s for s in t.spans if s.name.startswith("serve_tick")]
    v = []
    for m in t.modules:
        if "serve_tick" not in m.name or not serves:
            continue
        best = max(serves, key=lambda s: _overlap(s, m))
        if _overlap(best, m) > 0 and best.name == "serve_tick.nojoin":
            v.append(m.dur)
    return 1e3 * sum(v) / len(v) if v else None
