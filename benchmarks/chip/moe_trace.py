"""What the readers of a model with routed experts and latent attention
take from a traced run: the no-join serve ticks, their routed loads and
slot contexts, and the device time of the operations that a named scope of
the program compiled to (``deploy/lm_serve_moe.py`` records their names
in the set-up span ``scope_ops``).  Against a record without those
(another model, or a program without them) every function finds nothing.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from benchmarks.chip import trace as tr

OPS_SPAN = "scope_ops"


def _overlap(a, b) -> float:
    return min(a.start + a.dur, b.start + b.dur) - max(a.start, b.start)


def nojoin_modules(rec) -> List[Tuple[float, float]]:
    """Device intervals of the serve-tick executions that overlap a
    ``serve_tick.nojoin`` host span more than any other serve span."""
    t = rec.trace
    if t is None:
        return []
    serves = [s for s in t.spans if s.name.startswith("serve_tick")]
    out = []
    for m in t.modules:
        if "serve_tick" not in m.name or not serves:
            continue
        best = max(serves, key=lambda s: _overlap(s, m))
        if _overlap(best, m) > 0 and best.name == "serve_tick.nojoin":
            out.append((m.start, m.start + m.dur))
    return sorted(out)


def scope_op_names(rec, scope: str) -> Optional[set]:
    span = next((s for s in rec.spans if s.name == OPS_SPAN), None)
    names = span.attrs.get(scope) if span is not None else None
    return set(names) if names else None


def _hlo_name(op) -> str:
    return op.name.split(" = ")[0].split(" ")[0].lstrip("%")


def scope_seconds(rec, scope: str) -> float:
    """Device seconds, inside the no-join serve ticks, of the operations
    compiled under ``scope`` (overlapping operations, such as a loop and
    the operations of its body, count once)."""
    names = scope_op_names(rec, scope)
    ticks = nojoin_modules(rec)
    if not names or not ticks:
        return 0.0
    starts = [a for a, _ in ticks]
    from bisect import bisect_right

    def in_tick(o):
        i = bisect_right(starts, o.start) - 1
        return i >= 0 and o.start + o.dur <= ticks[i][1] + 1e-9
    spans = [(o.start, o.start + o.dur) for o in rec.trace.ops
             if _hlo_name(o) in names and in_tick(o)]
    return sum(b - a for a, b in tr.union(spans))


def nojoin_ticks(rec):
    """The ``serve_tick.nojoin`` host spans of the traced window that carry
    the routed load (``moe_load``)."""
    return [s for s in rec.in_trace("serve_tick.nojoin")
            if "moe_load" in s.attrs]
