"""What every cell of the benchmark shares: where things are, the compile
meter, host spans around the program's entry points, the traced
sub-window, and the record a run leaves for the per-layer readers.

A deployment module (``deploy/<kind>.py``) builds the system through its
user entry points, warms it, drives the window and checks the answers;
this module gives it the clock, the spans and the trace.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SRC = os.path.join(ROOT, "src")


def load_benchmark() -> Dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_config(name: str) -> Dict:
    bench = load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    cfg["name"] = name
    return cfg


def load_file(path: str, module_name: str):
    """Import a file of the benchmark by path (its names may hold '.' and
    '-', which ``import`` does not take)."""
    if module_name in sys.modules:
        return sys.modules[module_name]
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[module_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reference(cfg: Dict):
    return load_file(os.path.join(HERE, "configs", cfg["reference"]),
                     "reference_" + cfg["reference"].split(".")[0]
                     .replace("-", "_"))


# ---------------------------------------------------------------------------
# compiles
# ---------------------------------------------------------------------------

class CompileMeter:
    """Sums JAX's own compile events (tracing, lowering, backend compile; a
    persistent-cache load counts as a backend compile), counts cache hits
    and misses, and keeps each backend compile's seconds by name."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = self.hits = self.misses = 0
        self.backend: List[Tuple[float, str]] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, fun_name="?", **_):
        if event in self._DURATIONS:
            self.seconds += duration
        if event == self._DURATIONS[-1]:
            self.compiles += 1
            self.backend.append((round(duration, 2), fun_name))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return (self.seconds, self.compiles, self.hits, self.misses)

    def since(self, snap) -> Dict:
        s, c, h, m = (a - b for a, b in zip(self.snapshot(), snap))
        return {"compile_s": round(s, 3), "compiles": c, "cache_hits": h,
                "cache_misses": m,
                "slowest": sorted(self.backend[snap[1]:], reverse=True)[:3]}


# ---------------------------------------------------------------------------
# host spans around the program's public entry points
# ---------------------------------------------------------------------------

@dataclass
class Span:
    name: str
    start: float                   # time.perf_counter() seconds
    end: float
    attrs: Dict = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


class Spans:
    """Records a span per call of each wrapped entry point, on the host
    clock, and writes the same span into the profiler's trace
    (``jax.profiler.TraceAnnotation``) so idle gaps can be attributed.
    Installed only in traced runs; ``restore`` takes every wrapper off."""

    def __init__(self):
        self.spans: List[Span] = []
        self._undo: List[Tuple[Any, str, Any]] = []

    def record(self, name: str, fn: Callable, args=(), kwargs=None,
               attrs: Optional[Dict] = None, block: bool = False):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            out = fn(*args, **(kwargs or {}))
            if block:
                jax.block_until_ready(out)
        self.spans.append(Span(name, t0, time.perf_counter(), attrs or {}))
        return out

    def wrap(self, owner, attr: str, name, attrs_fn=None):
        """Replace ``owner.attr`` (a function or method) with a recording
        wrapper.  ``name`` may be a callable of the call's arguments."""
        orig = getattr(owner, attr)
        spans = self

        def wrapper(*args, **kwargs):
            n = name(*args, **kwargs) if callable(name) else name
            attrs = attrs_fn(*args, **kwargs) if attrs_fn else None
            return spans.record(n, orig, args, kwargs, attrs)
        self.patch(owner, attr, wrapper)

    def patch(self, owner, attr: str, new):
        """Replace ``owner.attr`` with ``new`` until :meth:`restore`."""
        self._undo.append((owner, attr, getattr(owner, attr)))
        setattr(owner, attr, new)

    def restore(self):
        for owner, attr, orig in reversed(self._undo):
            setattr(owner, attr, orig)
        self._undo.clear()


def self_seconds(spans: Sequence[Span], parent: str) -> List[float]:
    """Per span named ``parent``: its duration minus the part of it that
    the spans starting inside it (its children) cover."""
    ordered = sorted(spans, key=lambda s: s.start)
    out = []
    for i, p in enumerate(ordered):
        if p.name != parent:
            continue
        covered, hi = 0.0, p.start
        for j in range(i + 1, len(ordered)):
            c = ordered[j]
            if c.start >= p.end:
                break
            a, b = max(c.start, hi), min(c.end, p.end)
            if b > a:
                covered += b - a
                hi = b
        out.append(p.dur - covered)
    return out


# ---------------------------------------------------------------------------
# the record one run leaves for the readers
# ---------------------------------------------------------------------------

@dataclass
class RunRecord:
    """What a traced run leaves for the per-layer readers."""
    cfg: Dict
    kind: str                                  # device_kind
    spans: List[Span] = field(default_factory=list)
    trace: Any = None                          # trace.TraceSummary or None
    #: host clock (perf_counter) of the traced sub-window
    traced: Tuple[float, float] = (0.0, 0.0)

    def in_trace(self, prefix: str) -> List[Span]:
        """Spans named ``prefix...`` that started in the traced window."""
        a, b = self.traced
        return [s for s in self.spans
                if s.name.startswith(prefix) and a <= s.start < b]


def join_ticks_ms(rec: RunRecord) -> List[float]:
    """Per tick of the traced window with a join: its ``build_admit`` span
    with admits plus the ``serve_tick.join`` span that follows it in the
    same tick, ms."""
    joins = sorted(rec.in_trace("serve_tick.join"), key=lambda s: s.start)
    out = []
    for a in rec.in_trace("build_admit"):
        if not a.attrs.get("n"):
            continue
        j = next((j for j in joins if j.start >= a.start), None)
        if j is not None:
            out.append(1e3 * (a.dur + j.dur))
    return out


def lm_traced_flops(rec: RunRecord) -> float:
    """Model operations of the prefills and decode steps that started in
    the traced window (``counts.py``)."""
    from benchmarks.chip import counts
    flops = sum(counts.prefill_flops(rec.cfg, s.attrs["n"])
                for s in rec.in_trace("host_prefill"))
    for s in rec.in_trace("serve_tick"):
        flops += sum(counts.decode_token_flops(rec.cfg, c)
                     for c in s.attrs.get("contexts", ()))
    return float(flops)


def codec_traced_work(rec: RunRecord) -> List[Tuple[int, int, int]]:
    """(ops, bytes, frames) of each codec call that started in the traced
    window: the hub's decode of the requests and encode of the answers in
    each fused serve, and the clients' batched encodes and decodes."""
    from benchmarks.chip import counts
    shape = rec.cfg["frame"]
    size = 1
    for d in shape:
        size *= d
    out = []
    for s in rec.in_trace("wire_serve") + rec.in_trace("codec_batch"):
        codec = s.attrs["codec"].partition(":")[0]
        if codec == "none":
            continue
        dirs = [s.attrs["dir"]] if "dir" in s.attrs else ["dec", "enc"]
        for d in dirs:
            ops, nbytes = counts.codec_call(codec, d, size, shape[-1])
            out.append((ops, nbytes, s.attrs["n"]))
    return out


class Tracer:
    """The traced sub-window of a ``--trace 1`` run: the profiler runs
    from the window's start for ``seconds`` and writes to a temporary
    directory, which is reduced and removed."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.dir: Optional[str] = None
        self.t0 = self.t1 = 0.0

    def start(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(self.dir)
        self.t0 = time.perf_counter()

    @property
    def running(self) -> bool:
        return self.dir is not None and not self.t1

    def due(self) -> bool:
        return self.running and time.perf_counter() - self.t0 >= self.seconds

    def stop(self):
        import jax
        if self.running:
            self.t1 = time.perf_counter()
            jax.profiler.stop_trace()

    def reduce(self, span_names: Sequence[str]):
        from benchmarks.chip import trace as tr
        try:
            path = tr.find_xplane(self.dir) if self.dir else None
            return tr.reduce(path, span_names) if path else None
        finally:
            if self.dir:
                shutil.rmtree(self.dir, ignore_errors=True)


def control_checks(checks, compared: str, control_value):
    """A run's checks with the control put in the program's place: the
    number ``compared`` replaced by the control's reading of it."""
    return [(f"{name}.control", control_value, lim) if name == compared
            else (name, value, lim) for name, value, lim in checks]


def metric_file(name: str) -> str:
    """The reader of metric ``name``: ``metrics/<name>.py``, or for a
    metric split by cell (``<base>.<cell>``) the shared
    ``metrics/<base>.py`` where the split has no reader of its own."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    base = os.path.join(HERE, "metrics", f"{name.split('.')[0]}.py")
    return path if os.path.exists(path) or "." not in name else base


def read_metric(name: str, rec: RunRecord):
    """Run the reader of metric ``name`` (:func:`metric_file`) on a run's
    record; a reader that finds nothing to read returns None."""
    path = metric_file(name)
    mod = load_file(path, "metric_" + os.path.basename(path)[:-3]
                    .replace(".", "_").replace("-", "_"))
    try:
        return mod.read(rec)
    except (KeyError, ValueError, ZeroDivisionError) as e:
        log(f"metric {name}: nothing to read ({e!r})")
        return None


def free_program():
    """Free the deployment's device memory before the reference runs: the
    program's compile cache holds its plans, and through their elements
    the runs with their parameters and state."""
    import gc
    from repro.core import plan
    plan.clear_executable_cache()
    gc.collect()


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)
