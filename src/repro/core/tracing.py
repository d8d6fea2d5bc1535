"""The program's tracer: spans at the serving path's layer boundaries and
per-request stamps, kept in memory, off by default.

Off, :func:`span` returns one shared no-op context manager and
:func:`request` one shared no-op request: the cost at each site is a call
and one module-level boolean test — no allocation, no clock read, no
profiler annotation.

On (:func:`enable`), a span records its name, start and end
(``time.perf_counter_ns``), the id of the span open around it on the same
thread, the id of the request it serves (the :class:`Request` entered
around it, if any) and small integer attributes set inside it
(``sp.set(n=..., bytes=...)``).  Each span is also entered as
``jax.profiler.TraceAnnotation(name)``, so a profiler trace holds the same
span on the device's clock.  Finished spans and finished requests go to
two bounded rings, and requests still in flight are held up to the same
bound: a long-running server never grows without limit, and :func:`drain`
says how many records were dropped.

The tracer never waits for the device: it adds no ``block_until_ready``
and no ``device_get``.  A span that ends at a sync the program already
makes measures that sync; one that ends at an asynchronous dispatch
measures the dispatch.

    from repro.core import tracing
    tracing.enable()
    ...                                   # serve
    got = tracing.drain()                 # got.spans, got.requests, ...
    tracing.disable()
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Dict, List, NamedTuple, Optional

import jax

__all__ = ["span", "request", "stamp", "enable", "disable", "enabled",
           "drain", "SpanRecord", "Request", "Drained", "DEFAULT_CAPACITY"]

#: records each ring keeps by default (the oldest drop first)
DEFAULT_CAPACITY = 1 << 16

_on = False
_spans: deque = deque(maxlen=DEFAULT_CAPACITY)
_requests: deque = deque(maxlen=DEFAULT_CAPACITY)
_open: Dict[int, "Request"] = {}    # requests in flight, oldest first
_capacity = DEFAULT_CAPACITY
_dropped = 0
_span_ids = itertools.count()
_request_ids = itertools.count()
_local = threading.local()          # .stack: open spans; .rid: request


class SpanRecord:
    """One finished span; times are ``perf_counter_ns``."""
    __slots__ = ("id", "name", "start", "end", "parent", "rid", "attrs")

    def __init__(self, name: str, rid: Optional[int], parent: int):
        self.id = next(_span_ids)
        self.name = name
        self.rid = rid
        self.parent = parent             # id of the enclosing span, or -1
        self.start = self.end = 0
        self.attrs: Dict[str, int] = {}

    @property
    def dur_ns(self) -> int:
        return self.end - self.start

    def __repr__(self):
        return (f"SpanRecord({self.name!r}, id={self.id}, "
                f"parent={self.parent}, dur_ns={self.dur_ns}, "
                f"rid={self.rid}, attrs={self.attrs})")


class _NoSpan:
    """What :func:`span` returns while the tracer is off."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass


_NO_SPAN = _NoSpan()


def _stack() -> List[SpanRecord]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _keep(ring: deque, record):
    global _dropped
    if len(ring) == ring.maxlen:
        _dropped += 1
    ring.append(record)


class _Span:
    __slots__ = ("rec", "_note")

    def __init__(self, name: str):
        stack = _stack()
        self.rec = SpanRecord(name, getattr(_local, "rid", None),
                              stack[-1].id if stack else -1)

    def __enter__(self):
        rec = self.rec
        _stack().append(rec)
        self._note = jax.profiler.TraceAnnotation(rec.name)
        self._note.__enter__()
        rec.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        rec = self.rec
        rec.end = time.perf_counter_ns()
        self._note.__exit__(*exc)
        _stack().pop()
        _keep(_spans, rec)
        return False

    def set(self, **attrs):
        self.rec.attrs.update(attrs)


def span(name: str):
    """A span named ``name`` around the ``with`` block."""
    if not _on:
        return _NO_SPAN
    return _Span(name)


class Request:
    """Wall-clock stamps of one request (``perf_counter_ns`` by stamp
    name).  Entered as a context manager, it gives its id to every span
    opened inside on the same thread."""
    __slots__ = ("rid", "stamps", "_outer")

    def __init__(self, arrived_ns: int = 0):
        self.rid = next(_request_ids)
        self.stamps: Dict[str, int] = {
            "arrive": arrived_ns or time.perf_counter_ns()}

    def mark(self, what: str):
        self.stamps[what] = time.perf_counter_ns()

    def finish(self):
        """Stamp ``finish`` and keep the request for :func:`drain`."""
        self.mark("finish")
        _open.pop(self.rid, None)
        _keep(_requests, self)

    def __enter__(self):
        self._outer = getattr(_local, "rid", None)
        _local.rid = self.rid
        return self

    def __exit__(self, *exc):
        _local.rid = self._outer
        return False

    def __repr__(self):
        return f"Request(rid={self.rid}, stamps={self.stamps})"


class _NoRequest:
    """What :func:`request` returns while the tracer is off."""
    __slots__ = ()
    rid = None

    def mark(self, what: str):
        pass

    def finish(self):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NO_REQUEST = _NoRequest()


def request(arrived_ns: int = 0):
    """A new request, arrived at ``arrived_ns`` (a :func:`stamp`; now where
    it is 0), held as in flight until it finishes."""
    global _dropped
    if not _on:
        return _NO_REQUEST
    req = Request(arrived_ns)
    if len(_open) >= _capacity:
        del _open[next(iter(_open))]
        _dropped += 1
    _open[req.rid] = req
    return req


def stamp() -> int:
    """``perf_counter_ns`` now while the tracer is on, else 0."""
    return time.perf_counter_ns() if _on else 0


class Drained(NamedTuple):
    spans: List[SpanRecord]          # in the order they ended
    requests: List[Request]          # finished, in the order they finished
    #: requests still in flight, with their stamps so far; they stay held
    #: and come back in ``requests`` of a later drain once they finish
    open: List[Request]
    dropped: int                     # records lost since the last drain


def enable(capacity: int = DEFAULT_CAPACITY):
    """Turn the tracer on with empty rings of ``capacity`` records each."""
    global _on, _spans, _requests, _capacity, _dropped
    _spans = deque(maxlen=capacity)
    _requests = deque(maxlen=capacity)
    _open.clear()
    _capacity = capacity
    _dropped = 0
    _on = True


def disable():
    """Turn the tracer off; what it recorded stays until :func:`drain`."""
    global _on
    _on = False


def enabled() -> bool:
    return _on


def drain() -> Drained:
    """Return and clear what the tracer recorded; requests in flight are
    returned and kept."""
    global _dropped
    out = Drained(list(_spans), list(_requests), list(_open.values()),
                  _dropped)
    _spans.clear()
    _requests.clear()
    _dropped = 0
    return out
