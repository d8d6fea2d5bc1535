"""Readings that set a cell's limit: the program's number on many seeds
and the control's on the same answers, in one process.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds <s> [--out <readings>.jsonl]

For each seed the cell runs as ``run.py`` runs it (a shorter window is
enough: it only has to finish the mix's longest requests), then the check
computes both the served answers' number and the control's: the
configuration's reference in the next lower precision (fp8 matmuls for a
bf16 model, bfloat16 for float32 frames), read against the float32
reference at the same positions.  The control is judged as the program
is: ``run.verdict`` over the run's checks with the control's number in
the program's place.  One JSON line per seed, and a summary: the largest
program reading, the smallest control reading, and whether the program
came out correct on every seed and the control on none (else the exit
code is 1).
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
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:1] = [os.path.join(ROOT, "src"), ROOT]
    from benchmarks.chip import harness, run
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"control: JAX found no TPU ({dev.platform})", file=sys.stderr)
        return 1
    cell = next(w for w in harness.load_benchmark()["workloads"]
                if w["name"] == args.workload)
    meter = harness.CompileMeter()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx, out, _ = run.run_cell(cell, seed, args.seconds, False,
                                   dev.device_kind, meter, control=True)
        compared = out["checks"][0][0]
        row = {"seed": seed, "program": out["checks"][0][1],
               "control": out["control_checks"][0][1],
               "program_correct": run.verdict(out["checks"]),
               "control_correct": run.verdict(out["control_checks"]),
               "checks": out["checks"],
               "control_checks": out["control_checks"],
               "metrics": out["metrics"], "setup_s": ctx.setup_s,
               "memory_peak_bytes": ctx.memory_peak_bytes}
        rows.append(row)
        line = json.dumps(row, default=str)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    # the program is correct on every seed and the control on none
    ok = all(r["program_correct"] and not r["control_correct"] for r in rows)
    print(json.dumps({"workload": args.workload, "compared": compared,
                      "program_max": max(r["program"] for r in rows),
                      "control_min": min(r["control"] for r in rows),
                      "program_correct_on_all": all(
                          r["program_correct"] for r in rows),
                      "control_correct_on_none": not any(
                          r["control_correct"] for r in rows)}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
