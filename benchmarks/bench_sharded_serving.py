"""Mesh-sharded serving: server frames/sec with 8 concurrent clients on an
8-way host mesh — the PR-4 tentpole lever on top of PR 2's micro-batching.

Four serving modes, measured on the serving path itself (requests
pre-queued, flush timed, exactly like bench_query_batching), interleaved
round-robin so host load drift hits all of them equally:

* ``mesh_auto``  — the production config: ``Runtime(mesh=...)`` with the
                   calibrated placement (probe sharded-vs-single per batch
                   size, keep the faster — core/batching.py);
* ``sharded``    — the sharded executable FORCED (``shard_mode="always"``):
                   batch-8 laid out along the mesh's data axes, one frame
                   slice per device;
* ``batched``    — batch-8 flush on a single device (the PR-2 path);
* ``sequential`` — one interpreted round-trip per request (the paper's
                   Fig. 2 baseline).

GATE: batch-8 serving on the 8-way host mesh (``mesh_auto``, the config a
deployment actually runs) must sustain >= 2x the sequential server
frames/sec.  The forced-sharded ratios are reported alongside
(``sharded_vs_sequential``, ``shard_vs_batched``): on real multi-chip
meshes they are the win, on a host-forged mesh (8 "devices" timeshared on
a couple of cores) SPMD dispatch overhead makes them < 1 — which is
exactly the dispatch-vs-silicon gap the calibrated placement exists to
absorb, and why the gate is on the calibrated path.

XLA fixes the device count at backend init, and benchmarks/run.py runs many
suites in one process that must see the host as-is — so when this process
has fewer than 2 CPU devices the measurement re-executes itself in a
CPU-only subprocess with ``--xla_force_host_platform_device_count=8`` and
adopts its rows.  An accelerator belongs to the one process that opened
it, so on a single chip the suite fails instead of starting that child.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from .common import emit

N_CLIENTS = 8
N_DEVICES = 8
GATE_SPEEDUP = 2.0
_SENTINEL = "BENCH_SHARDED_ROWS_JSON:"


def _ensure_model(d: int = 192):
    import jax
    import jax.numpy as jnp
    from repro.core import TensorSpec
    from repro.core.elements import register_model

    key = f"shard_mlp_{d}"

    def init(rng):
        k1, k2 = jax.random.split(rng)
        return {"w1": jax.random.normal(k1, (d, d)) * 0.05,
                "w2": jax.random.normal(k2, (d, 16)) * 0.05}

    def apply(p, x):
        h = jnp.tanh(x.astype(jnp.float32).reshape(1, -1) @ p["w1"])
        return h @ p["w2"]

    register_model(key, init, apply,
                   out_specs=(TensorSpec((1, 16), "float32"),))
    return key


def _build(query_batch: int, mesh, d: int, shard_mode: str = "auto"):
    from repro.core import parse_launch
    from repro.runtime import Device, Runtime

    rt = Runtime(query_batch=query_batch, mesh=mesh, shard_mode=shard_mode)
    model = _ensure_model(d)
    hub = Device("hub")
    srv = parse_launch(
        f"tensor_query_serversrc operation=svc name=ssrc ! "
        f"tensor_filter model={model} ! tensor_query_serversink name=ssink")
    srv.elements["ssink"].pair_with(srv.elements["ssrc"])
    srv_run = hub.add_pipeline(srv, jit=False)
    rt.add_device(hub)
    qcs = []
    for i in range(N_CLIENTS):
        dev = Device(f"tv{i}")
        cli = parse_launch(
            f"testsrc width={d // 3} height=1 ! tensor_converter ! "
            f"tensor_query_client operation=svc name=qc ! appsink name=o")
        dev.add_pipeline(cli, jit=False)
        rt.add_device(dev)
        qcs.append(cli.elements["qc"])
    return rt, srv_run, qcs


def _round_fn(rt, qcs, d: int):
    """One serving round: queue one request per client, flush the batch."""
    import jax.numpy as jnp
    from repro.core.buffers import StreamBuffer

    batcher = next(iter(rt._batchers.values()))
    frame = StreamBuffer(tensors=(jnp.arange(d, dtype=jnp.float32) / d,),
                         pts=jnp.int32(0))

    def one_round():
        for qc in qcs:
            qc.send_query(frame)
        batcher.flush()

    def drain():
        for qc in qcs:
            while qc.recv_answer() is not None:
                pass
    return one_round, drain


def _interleaved_medians(entries, rounds: int, warmup: int = 5):
    """Time each mode's rounds ROUND-ROBIN and report the median round per
    mode.  The host-mesh CI box forges 8 devices on very few, noisily
    shared cores: load drift between two back-to-back measurement windows
    swings 2x+, so separate windows would measure the machine, not the
    serving paths.  Interleaving exposes every mode to the same drift;
    the median discards the scheduler spikes."""
    times = {name: [] for name, _, _ in entries}
    for name, one_round, _ in entries:
        for _ in range(warmup):
            one_round()
    for _ in range(rounds):
        for name, one_round, _ in entries:
            t0 = time.perf_counter()
            one_round()
            times[name].append(time.perf_counter() - t0)
    for _, _, drain in entries:
        drain()
    return {name: sorted(ts)[len(ts) // 2] for name, ts in times.items()}


def _measure(rounds: int = 30, d: int = 192):
    """Requires >= 2 local devices; returns the structured rows."""
    import jax
    from repro.launch.mesh import data_axis_size, make_host_mesh

    mesh = make_host_mesh()
    dsize = data_axis_size(mesh)
    rows = []

    rt_a, _, qcs_a = _build(N_CLIENTS, mesh, d, shard_mode="auto")
    rt_sh, _, qcs_sh = _build(N_CLIENTS, mesh, d, shard_mode="always")
    rt_b, _, qcs_b = _build(N_CLIENTS, None, d)
    rt_s, _, qcs_s = _build(0, None, d)
    meds = _interleaved_medians(
        [("mesh_auto", *_round_fn(rt_a, qcs_a, d)),
         ("sharded", *_round_fn(rt_sh, qcs_sh, d)),
         ("batched", *_round_fn(rt_b, qcs_b, d)),
         ("sequential", *_round_fn(rt_s, qcs_s, d))], rounds)
    fps_auto = N_CLIENTS / meds["mesh_auto"]
    fps_sharded = N_CLIENTS / meds["sharded"]
    fps_batched = N_CLIENTS / meds["batched"]
    fps_seq = N_CLIENTS / meds["sequential"]
    assert rt_sh.stats()["query_batching"]["sharded_frames"] > 0, \
        "forced mesh path never engaged"
    placement = next(iter(rt_a._batchers.values())).placements.get(
        N_CLIENTS, "single")

    speedup = fps_auto / fps_seq
    rows.append(dict(
        name=f"sharded_serving/serving_fps/mesh{dsize}_auto_batch{N_CLIENTS}",
        us=1e6 / fps_auto, derived=(f"frames_per_sec={fps_auto:.0f};"
                                    f"placement={placement}"),
        fps=round(fps_auto, 1), devices=dsize, placement=placement))
    rows.append(dict(
        name=f"sharded_serving/serving_fps/mesh{dsize}_forced_batch{N_CLIENTS}",
        us=1e6 / fps_sharded, derived=f"frames_per_sec={fps_sharded:.0f}",
        fps=round(fps_sharded, 1), devices=dsize))
    rows.append(dict(
        name="sharded_serving/serving_fps/single_device_batch",
        us=1e6 / fps_batched, derived=f"frames_per_sec={fps_batched:.0f}",
        fps=round(fps_batched, 1)))
    rows.append(dict(
        name="sharded_serving/serving_fps/sequential",
        us=1e6 / fps_seq, derived=f"frames_per_sec={fps_seq:.0f}",
        fps=round(fps_seq, 1)))
    rows.append(dict(
        name="sharded_serving/speedup", us=0.0,
        derived=(f"mesh_auto_vs_sequential={speedup:.2f}x;gate>=2x;"
                 f"pass={speedup >= GATE_SPEEDUP}"),
        speedup=round(speedup, 3), gate=GATE_SPEEDUP,
        gate_pass=bool(speedup >= GATE_SPEEDUP),
        sharded_vs_sequential=round(fps_sharded / fps_seq, 3),
        shard_vs_batched=round(fps_sharded / fps_batched, 3)))
    return rows


def _measure_subprocess(rounds: int):
    """Re-exec with forged devices; adopt the child's rows."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = (flags +
                        f" --xla_force_host_platform_device_count={N_DEVICES}"
                        ).strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")] +
        ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_sharded_serving",
         "--rounds", str(rounds)],
        capture_output=True, text=True, env=env, timeout=560,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    for line in out.stdout.splitlines():
        if line.startswith(_SENTINEL):
            return json.loads(line[len(_SENTINEL):])
    raise RuntimeError(
        f"sharded-serving subprocess produced no rows\nstdout:\n{out.stdout}"
        f"\nstderr:\n{out.stderr}")


def run(rounds: int = 30):
    import jax
    if len(jax.devices()) >= 2:
        rows = _measure(rounds)
    elif jax.default_backend() == "cpu":
        rows = _measure_subprocess(rounds)
    else:
        raise RuntimeError(
            f"sharded serving needs >= 2 devices; this process holds one "
            f"{jax.default_backend()} device, and a child process cannot "
            f"open the chip its parent holds")
    gate_row = None
    for r in rows:
        fields = {k: v for k, v in r.items()
                  if k not in ("name", "us", "derived")}
        emit(r["name"], r["us"], r["derived"], **fields)
        if r["name"].endswith("/speedup"):
            gate_row = r
    if gate_row is None or not gate_row["gate_pass"]:
        got = gate_row and gate_row["speedup"]
        raise AssertionError(
            f"sharded serving gate failed: {got}x < {GATE_SPEEDUP}x")


if __name__ == "__main__":
    rounds = 30
    if "--rounds" in sys.argv:
        rounds = int(sys.argv[sys.argv.index("--rounds") + 1])
    rows = _measure(rounds)
    print(_SENTINEL + json.dumps(rows))
