"""The program's own tracer (``repro.core.tracing``) in a run of a cell:
the spans, span attributes and request stamps the program records inside
the window, and the per-layer metrics that read them.

    python3 benchmarks/chip/program_trace.py --workload <cell> --seed <n> \\
        --seconds <s> [--profile 1|0]

runs one cell as ``run.py`` does, with the program's tracer on from the
window's open to its close.  ``--profile 1`` (the default) is ``run.py
--trace 1`` with the tracer on as well: the program's span names join the
names the trace reduction keeps, so each idle gap of the breakdown is
labelled by the innermost span open in it, the program's where there is
one.  It prints the per-layer metrics of ``BENCHMARK.json`` for the cell,
the metrics of :data:`METRICS` that read the program's records, a summary
of each program span (count, mean and device-idle ms) and the device time
of the operations under each of the program's ``jax.named_scope`` names.
``--profile 0`` is ``run.py --trace 0`` with the tracer on and the
profiler off: its end-to-end metrics against a ``--trace 0`` run are what
the tracer costs.

``run.py`` itself never turns the program's tracer on, so the metrics of
:data:`METRICS` are not in ``BENCHMARK.json``: reading them in the
benchmark's own traced runs takes the window hooks of
:func:`context_class` and the span names of :func:`run_cell` in
``run.py``.  Against a program without
``repro.core.tracing``, or a record without the program's, every reader
returns None.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse      # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402
from bisect import bisect_left  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Dict, List, Optional, Sequence, Tuple  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

#: per-layer metrics that read the program's records: unit and the cell
#: each reads in (``metrics/<base>.py`` reads each, as for run.py's)
METRICS = {
    "admit_fetch_ms.lm-decode": ("ms", "stablelm.decode-long"),
    "admit_copy_ms.lm-decode": ("ms", "stablelm.decode-long"),
    "admit_ship_ms.lm-decode": ("ms", "stablelm.decode-long"),
    "admit_mb.lm-decode": ("MB", "stablelm.decode-long"),
    "slot_occupancy_pct.lm-decode": ("%", "stablelm.decode-long"),
    "lm_queue_wait_ms.lm-decode": ("ms", "stablelm.decode-long"),
    "codec_idle_ms.vga": ("ms", "vga-fleet.cams16-codec"),
    "wire_idle_ms.vga": ("ms", "vga-fleet.cams16-codec"),
}

#: the program's ``jax.named_scope`` names, looked for in the string stats
#: of the trace's device operations
SCOPES = ("lm_serve_tick", "lm_prefill", "lm_admit_merge", "flash_decode",
          "codec_encode", "codec_decode")

#: records each of the tracer's rings keeps: a 45 s window of the fleet
#: records about 30,000 spans
CAPACITY = 1 << 18


@dataclass
class ProgramSpan:
    name: str
    start: float                   # time.perf_counter() seconds
    end: float
    id: int
    parent: int                    # id of the enclosing span, or -1
    rid: Optional[int] = None      # the request it serves
    attrs: Dict = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Program:
    """What the program's tracer recorded over one window."""
    spans: List[ProgramSpan] = field(default_factory=list)
    #: each request's stamps (``arrive``, ``prefill``, ``first_token``,
    #: ``join``, ``finish``), perf_counter seconds, finished or not
    requests: List[Dict[str, float]] = field(default_factory=list)
    dropped: int = 0
    window: Tuple[float, float] = (0.0, 0.0)

    def named(self, name: str) -> List[ProgramSpan]:
        """Spans called ``name`` that started in the window."""
        a, b = self.window
        return [s for s in self.spans if s.name == name and a <= s.start < b]


def _tracing():
    """The program's tracer, or None in a program that has none."""
    try:
        return importlib.import_module("repro.core.tracing")
    except ImportError:
        return None


class ProgramTrace:
    """Turns the program's tracer on over one window and keeps what it
    recorded (:class:`Program`)."""

    def __init__(self, capacity: int = CAPACITY):
        self.tracing = _tracing()
        self.capacity = capacity
        self.program: Optional[Program] = None
        self._t0 = 0.0

    def open(self):
        if self.tracing is not None:
            self.tracing.enable(self.capacity)
            self._t0 = time.perf_counter()

    def close(self):
        tr = self.tracing
        if tr is None or not tr.enabled():
            return
        t1 = time.perf_counter()
        got = tr.drain()
        tr.disable()
        spans = [ProgramSpan(r.name, r.start * 1e-9, r.end * 1e-9, r.id,
                             r.parent, r.rid, dict(r.attrs))
                 for r in got.spans]
        reqs = [{k: v * 1e-9 for k, v in q.stamps.items()}
                for q in list(got.requests) + list(got.open)]
        self.program = Program(spans, reqs, got.dropped, (self._t0, t1))

    def span_names(self) -> List[str]:
        return sorted({s.name for s in self.program.spans}) \
            if self.program else []


# ---------------------------------------------------------------------------
# what the readers share
# ---------------------------------------------------------------------------

def program_of(rec) -> Optional[Program]:
    """The program's records of a run, or None where it has none."""
    return getattr(rec, "program", None)


def join_ticks(prog: Program) -> List[ProgramSpan]:
    """Decode ticks of the window that took a waiting stream into a
    slot."""
    return [d for d in prog.named("lm.decode") if d.attrs.get("joins")]


def per_join_tick_ms(rec, names: Sequence[str]) -> List[float]:
    """Per join tick of the window: the summed duration of the spans
    called any of ``names`` inside it, ms."""
    prog = program_of(rec)
    if prog is None:
        return []
    inner = [s for s in prog.spans if s.name in names]
    return [1e3 * sum(s.dur for s in inner
                      if d.start <= s.start and s.end <= d.end)
            for d in join_ticks(prog)]


def busy_within(busy: Sequence[Tuple[float, float]], a: float, b: float
                ) -> float:
    """Seconds of ``[a, b]`` covered by ``busy`` (sorted, disjoint)."""
    i = max(bisect_left(busy, (a, a)) - 1, 0)
    covered = 0.0
    for lo, hi in busy[i:]:
        if lo >= b:
            break
        covered += max(0.0, min(hi, b) - max(lo, a))
    return covered


def idle_inside(summary, name: str) -> List[Tuple[float, float]]:
    """(duration, device-idle part) in seconds of each span called
    ``name`` that the trace reduction kept; both on the trace's clock,
    whose host and device lines can differ by about a millisecond."""
    if summary is None:
        return []
    return [(s.dur, s.dur - busy_within(summary.busy, s.start, s.end))
            for s in summary.spans if s.name == name]


def mean_idle_ms(rec, name: str) -> Optional[float]:
    v = [idle for _, idle in idle_inside(rec.trace, name)]
    return 1e3 * sum(v) / len(v) if v else None


# ---------------------------------------------------------------------------
# one run with the program's tracer on
# ---------------------------------------------------------------------------

def context_class():
    """``run.Context`` with the program's tracer on over the window (built
    on call: the readers import this module, and need no ``run``)."""
    from benchmarks.chip import run

    class ProgramContext(run.Context):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            self.program = ProgramTrace()

        def open_window(self) -> float:
            t0 = super().open_window()
            self.program.open()
            return t0

        def close_window(self, t_last: float):
            self.program.close()
            super().close_window(t_last)
    return ProgramContext


def run_cell(cell: dict, seed: int, seconds: float, profile: bool,
             kind: str, meter, t_process: Optional[float] = None):
    """``run.run_cell`` with the context of :func:`context_class`:
    (context, the deployment's outcome, the record for the readers with
    ``program`` set, or None without the profiler).  ``t_process`` is
    where set-up starts (the run module's import where it is None)."""
    from benchmarks.chip import harness, traffic
    cfg = harness.load_config(cell["config"])
    mix = traffic.load(cell["traffic"])
    deploy = importlib.import_module(f"benchmarks.chip.deploy.{cfg['kind']}")
    ctx = context_class()(cfg, mix, seed, seconds, profile, kind, meter)
    if t_process is not None:
        ctx.t_process = t_process
    out = deploy.run(ctx)
    rec = None
    if profile:
        names = list(out["span_names"]) + ctx.program.span_names()
        rec = harness.RunRecord(cfg=cfg, kind=kind, spans=ctx.spans.spans,
                                trace=ctx.tracer.reduce(names),
                                traced=(ctx.tracer.t0, ctx.tracer.t1))
        rec.program = ctx.program.program
    return ctx, out, rec


def span_summary(rec) -> Dict[str, Dict]:
    """Per program span name: count and mean ms over the window, and
    where the trace kept it, its mean ms there and the mean device-idle
    ms inside it."""
    prog = program_of(rec)
    out: Dict[str, Dict] = {}
    if prog is None:
        return out
    for name in sorted({s.name for s in prog.spans}):
        v = [s.dur for s in prog.named(name)]
        if not v:
            continue
        kept = idle_inside(rec.trace, name)
        out[name] = {"n": len(v), "mean_ms": 1e3 * sum(v) / len(v),
                     "traced_ms": 1e3 * sum(d for d, _ in kept) / len(kept)
                     if kept else None,
                     "idle_ms": mean_idle_ms(rec, name)}
    return out


def gaps_at(summary, n: int = 10) -> List[List]:
    """The ``n`` longest idle gaps as ``[label, seconds, seconds from the
    trace window's start]``; the label is the innermost span open at the
    gap's middle, as ``TraceSummary.longest_gaps`` has it."""
    gaps = sorted(summary.gaps(), key=lambda g: g[0] - g[1])[:n]
    return [[summary.span_at((a + b) / 2), b - a, a - summary.window[0]]
            for a, b in gaps]


def scope_seconds(summary) -> Dict[str, List]:
    """Per named scope: operations whose string stats hold it, and their
    device seconds."""
    out = {}
    for scope in SCOPES:
        ops = [o for o in summary.ops if scope in o.text]
        out[scope] = [len(ops), sum(o.dur for o in ops)]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--profile", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)

    sys.path[:1] = [os.path.join(ROOT, "src"), ROOT]
    from benchmarks.chip import harness, peaks
    from benchmarks.chip.run import verdict
    bench = harness.load_benchmark()
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        harness.log(f"no workload {args.workload!r} in BENCHMARK.json")
        return 1
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = jax.devices()[0]
    if dev.platform != "tpu" or len(jax.devices()) < cell["chips"]:
        harness.log(f"the cell needs {cell['chips']} TPU chips; JAX sees "
                    f"{len(jax.devices())} {dev.platform}")
        return 1
    peaks.peaks_for(dev.device_kind)

    ctx, out, rec = run_cell(cell, args.seed, args.seconds,
                             bool(args.profile), dev.device_kind,
                             harness.CompileMeter(), T_PROCESS)
    prog = ctx.program.program
    result = {"correct": verdict(out["checks"]),
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "memory_peak_bytes": ctx.memory_peak_bytes},
              "setup_s": ctx.setup_s, "window_compiles": ctx.window_compiles,
              "program": {"spans": len(prog.spans) if prog else None,
                          "requests": len(prog.requests) if prog else None,
                          "dropped": prog.dropped if prog else None},
              "counters": out["counters"]}
    if args.profile:
        metrics = {}
        names = [m["name"] for m in bench["per_layer"]
                 if args.workload in m.get("workloads", [args.workload])]
        names += [n for n, (_, c) in METRICS.items() if c == args.workload]
        for name in names:
            value = harness.read_metric(name, rec)
            if value is not None:
                metrics[name] = value
        result["metrics"] = metrics
        result["spans"] = span_summary(rec)
        if rec.trace is not None:
            result["device"].update(busy_s=rec.trace.busy_s,
                                    window_s=rec.trace.window_s)
            result["breakdown"] = {"device_ops": rec.trace.top_ops(10),
                                   "idle_gaps": gaps_at(rec.trace, 10)}
            ticks = [s for s in rec.trace.spans if s.name == "sched.tick"]
            if ticks:
                # where the program's ticks lie in the trace window
                result["ticks_in_window"] = [
                    min(s.start for s in ticks) - rec.trace.window[0],
                    rec.trace.window[1] - max(s.end for s in ticks)]
            result["scopes"] = scope_seconds(rec.trace)
    else:
        result["metrics"] = dict(out["metrics"], setup_s=ctx.setup_s)
    print(json.dumps(result, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
