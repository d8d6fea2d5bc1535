"""Chip benchmark of the serving fabric: one cell of ``BENCHMARK.json``,
one process holding the cell's chips.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

A cell is a configuration (``configs/<name>.json``, built by
``deploy/<kind>.py``) under a traffic mix (``traffic/<mix>.json``).  The
run builds the deployment through the program's user entry points, warms
every shape the mix uses (set-up, reported as ``setup_s``), measures for
``--seconds``, checks the answers against the configuration's plain
reference, and prints one JSON line last on stdout.  ``--trace 0``
reports the cell's end-to-end metrics; ``--trace 1`` installs host spans,
traces the start of the window with the profiler, and reports the cell's
per-layer metrics instead (``metrics/<name>.py`` reads each).

Without a TPU, with fewer chips than the cell asks for, on a device kind
with no published peaks, or outside a checkout of the repository, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse      # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


class Context:
    """One run: its settings, the set-up clock, the traced sub-window and
    the memory reading.  Handed to the deployment module."""

    def __init__(self, cfg, mix, seed, seconds, trace, kind, meter,
                 control=False):
        from benchmarks.chip import harness
        self.cfg, self.mix, self.seed, self.seconds = cfg, mix, seed, seconds
        self.kind, self.meter, self.control = kind, meter, control
        self.spans = harness.Spans() if trace else None
        self.tracer = harness.Tracer(mix["trace_seconds"]) if trace else None
        self.t_process = T_PROCESS
        self.setup_s = None
        self.window = (0.0, 0.0)
        self.memory_peak_bytes = None
        self.window_compiles = None
        self._snap = None

    def open_window(self) -> float:
        """Set-up ends here; the traced run's profiler starts here."""
        t0 = time.perf_counter()
        self.setup_s = t0 - self.t_process
        self._snap = self.meter.snapshot()
        if self.tracer is not None:
            self.tracer.start()
        self.window = (t0, t0)
        return t0

    def tick_tracer(self):
        if self.tracer is not None and self.tracer.due():
            self.tracer.stop()

    def close_window(self, t_last: float):
        if self.tracer is not None:
            self.tracer.stop()
        self.window = (self.window[0], t_last)
        self.window_compiles = self.meter.since(self._snap)
        if self.spans is not None:
            self.spans.restore()

    def read_memory(self):
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None


def verdict(checks) -> bool:
    """``correct``: every number compared is within its limit."""
    return all(value <= limit for _, value, limit in checks)


def _fail(msg: str) -> int:
    print(f"chip benchmark: {msg}", file=sys.stderr)
    return 1


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, kind: str,
             meter, control: bool = False, mix: dict = None):
    """Build, warm, measure and check one cell; returns (context, the
    deployment's outcome, the record for the per-layer readers).  ``mix``
    replaces the cell's traffic file (the knee sweep varies its rate)."""
    from benchmarks.chip import harness, traffic
    cfg = harness.load_config(cell["config"])
    mix = mix or traffic.load(cell["traffic"])
    deploy = importlib.import_module(f"benchmarks.chip.deploy.{cfg['kind']}")
    ctx = Context(cfg, mix, seed, seconds, trace, kind, meter, control)
    out = deploy.run(ctx)
    rec = None
    if trace:
        summary = ctx.tracer.reduce(out["span_names"])
        rec = harness.RunRecord(cfg=cfg, kind=kind, spans=ctx.spans.spans,
                                trace=summary,
                                traced=(ctx.tracer.t0, ctx.tracer.t1))
    return ctx, out, rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return _fail(f"no program at {os.path.join(ROOT, 'src')}; run from a "
                     f"checkout of the repository")
    # the program, then this benchmark as a package of the checkout
    sys.path[:1] = [os.path.join(ROOT, "src"), ROOT]
    from benchmarks.chip import harness, peaks
    bench = harness.load_benchmark()
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload),
                None)
    if cell is None:
        return _fail(f"no workload {args.workload!r} in BENCHMARK.json")

    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return _fail(f"JAX found no TPU (platform {dev.platform!r})")
    if len(devices) < cell["chips"]:
        return _fail(f"the cell needs {cell['chips']} chips; JAX sees "
                     f"{len(devices)}")
    try:
        peaks.peaks_for(dev.device_kind)
    except KeyError as e:
        return _fail(str(e))
    harness.log(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
                f"compile cache {cache_dir}")

    ctx, out, rec = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                             dev.device_kind, harness.CompileMeter())
    harness.log(f"setup_s {ctx.setup_s:.3f}; compiles in the window: "
                f"{json.dumps(ctx.window_compiles)}")
    harness.log(f"counters {json.dumps(out['counters'], default=str)}")

    if args.trace:
        metrics = {}
        for m in bench["per_layer"]:
            if args.workload not in m.get("workloads", [args.workload]):
                continue
            value = harness.read_metric(m["name"], rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(out["metrics"], setup_s=ctx.setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in bench["end_to_end"]
                   if args.workload in m.get("workloads", [args.workload])
                   and m["name"] in values}

    checks = out["checks"]
    correct = verdict(checks)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"],
              "memory_peak_bytes": ctx.memory_peak_bytes}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if args.trace and rec.trace is not None:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        result["breakdown"] = {"device_ops": rec.trace.top_ops(10),
                               "idle_gaps": rec.trace.longest_gaps(10)}
    result["compared"] = {name: {"value": v, "limit": lim}
                          for name, v, lim in checks}
    for name, v, lim in checks:
        harness.log(f"compared {name} {v!r} limit {lim!r}")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
