"""Find the knee of an open-loop mix on a configuration: the highest mean
rate whose backlog does not grow over the window.

    python3 benchmarks/chip/sweep.py --config <config> --traffic <mix> \\
        --rates 0.5,1,2 --seconds <s> --seed <n>

The knee is found before the cell exists, so the pair need not be a
cell of ``BENCHMARK.json``.  Runs the pair once per rate, in one
process, with the rate of its traffic file replaced, and prints per rate
the requests due, the backlog at the
window's close, the most requests open in each period of the mix's
phases (a backlog that grows shows as rising peaks), p90 latency of the
requests due in the window's first and second halves, and the device's
peak memory.  The knee goes into the traffic file by hand
(``knee_per_s``, ``rate_per_s`` = 0.8 x knee).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:1] = [os.path.join(ROOT, "src"), ROOT]
    from benchmarks.chip import harness, run, traffic
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"sweep: JAX found no TPU ({dev.platform})", file=sys.stderr)
        return 1
    cell = {"name": f"{args.config}.{args.traffic}", "config": args.config,
            "traffic": args.traffic, "chips": 1}
    meter = harness.CompileMeter()
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(traffic.load(cell["traffic"]), rate_per_s=rate)
        try:
            ctx, out, _ = run.run_cell(cell, args.seed, args.seconds, False,
                                       dev.device_kind, meter, mix=mix)
        except Exception as e:                    # noqa: BLE001
            print(json.dumps({"rate": rate, "error": repr(e)[:400]}),
                  flush=True)
            break
        c = out["counters"]
        period = sum(length for length, _ in mix["phases"])
        peaks = {}
        for t, n in ctx.backlog_series:
            k = int(t // period)
            peaks[k] = max(peaks.get(k, 0), n)
        print(json.dumps({
            "rate": rate, "answered": c["answered"], "failed": out["failed"],
            "backlog_at_close": c["backlog_at_close"],
            "open_peak_per_period": [peaks[k] for k in sorted(peaks)],
            "p90_ms": out["metrics"].get("lm_latency_p90_ms"),
            "p90_first_half_ms": c.get("latency_p90_first_half_ms"),
            "p90_second_half_ms": c.get("latency_p90_second_half_ms"),
            "generator_late_s": c["generator_late_s"],
            "correct": run.verdict(out["checks"]),
            "memory_peak_bytes": ctx.memory_peak_bytes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
