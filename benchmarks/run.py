# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV on stdout AND dumps every row as machine-readable JSON (BENCH_PR5.json
# at the repo root) so the perf trajectory is tracked across PRs.  Each
# suite's wall time is recorded in the JSON (``suite_seconds``) so bench
# regressions are diffable across PRs, not just the measured rows.
#
#   Fig. 7 pub/sub  -> bench_pubsub         (RELAY vs HYBRID vs DIRECT, 3 bands)
#   Fig. 7 query    -> bench_query          (MQTT-hybrid vs TCP + failover)
#   §4.2.3 sync     -> bench_sync           (NTP rebase vs raw clocks)
#   §3/§4.1 codecs  -> bench_compression    (sparse/quant8 wire bytes)
#   kernels         -> bench_kernels        (Pallas codec kernels, interpret)
#   §Roofline       -> bench_roofline       (reads results/dryrun.json)
#   engine          -> bench_step_overhead  (compiled plan + burst vs seed loop)
#   serving         -> bench_query_batching (micro-batched offloading, >=2x gate
#                                            + batched-beats-sequential e2e gate)
#   failover        -> bench_failover       (ticks-to-recovery <=2 gate, heartbeat cost)
#   reconfig        -> bench_reconfig       (hot-swap cutover pause <=2 ticks gate,
#                                            post-swap throughput >=0.95x gate)
#   mesh serving    -> bench_sharded_serving (calibrated mesh placement, >=2x gate)
#   wire path       -> bench_wire_path      (fused codec serving >=2x e2e gate,
#                                            sparse enc >=10x vs PR-4)
#   model serving   -> bench_model_serving  (continuous-batched decode >=2x
#                                            sequential at 8 streams gate)
#   pp serving      -> bench_pp_serving     (2-stage among-device chain
#                                            steady-state >=1.5x mono gate)
#   qos serving     -> bench_qos            (1k-client Zipf+burst load: overload
#                                            p99 isolation <=1.5x gate, zero
#                                            silent drops, goodput >=0.9x gate)
#   netfault        -> bench_netfault       (delivery layer <=1.10x fault-free
#                                            gate; 5% loss: complete + bitwise +
#                                            conservation gate)
import json
import os
import platform
import sys
import time
import traceback

BENCH_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_PR10.json")


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (bench_compression, bench_failover, bench_kernels,
                   bench_model_serving, bench_netfault, bench_pp_serving,
                   bench_pubsub, bench_qos, bench_query,
                   bench_query_batching, bench_reconfig, bench_roofline,
                   bench_sharded_serving, bench_step_overhead, bench_sync,
                   bench_wire_path)
    from .common import ROWS, reset_rows

    reset_rows()
    print("name,us_per_call,derived")
    suites = [
        ("pubsub", bench_pubsub.run),
        ("query", bench_query.run),
        ("query_failover", bench_query.run_failover),
        ("query_batching", bench_query_batching.run),
        ("wire_path", bench_wire_path.run),
        ("model_serving", bench_model_serving.run),
        ("pp_serving", bench_pp_serving.run),
        ("qos", bench_qos.run),
        ("netfault", bench_netfault.run),
        ("sharded_serving", bench_sharded_serving.run),
        ("failover", bench_failover.run),
        ("reconfig", bench_reconfig.run),
        ("sync", bench_sync.run),
        ("compression", bench_compression.run),
        ("kernels", bench_kernels.run),
        ("step_overhead", bench_step_overhead.run),
        ("roofline", bench_roofline.run),
    ]
    failed = []
    suite_seconds = {}
    for name, fn in suites:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:
            failed.append(name)
            traceback.print_exc()
            print(f"{name},0.0,SUITE_FAILED")
        finally:
            suite_seconds[name] = round(time.perf_counter() - t0, 3)

    import jax
    payload = {
        "schema": 1,
        "pr": 10,
        "backend": jax.default_backend(),
        "python": platform.python_version(),
        "suites_failed": failed,
        "suite_seconds": suite_seconds,
        "rows": ROWS,
    }
    with open(BENCH_JSON, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {os.path.normpath(BENCH_JSON)} ({len(ROWS)} rows)")
    for name, secs in suite_seconds.items():
        print(f"# suite {name}: {secs}s")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
