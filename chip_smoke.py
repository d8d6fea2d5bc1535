"""Smoke run of the serving fabric on TPU, through the entry points a user
calls, checking every answer.

One chip (no arguments):

1. LM serving: stablelm-1.6b at its published widths behind ``model_serve``
   (8 decode slots, 1024-position cache) on a hub ``Device``, four streaming
   client ``Device``s with two requests each (seeded prompts of 7, 64 and
   600 tokens; 16 and 8 generated tokens).  Every answer is checked against
   ``sequential_decode`` on the same chip, the §7 token conservation law
   against ``Runtime.stats()``, and the compiled prefill for the Pallas
   flash kernel.
2. Codec wire path: VGA float frames through ``codec=quant8`` and
   ``codec=sparse`` on the fused batched wire path, so the Pallas codec
   kernels run compiled; answers are checked bit for bit against the same
   round trip on the kernels' XLA statements.

``--chips 4``: only the mesh-sharded offloading path (DESIGN.md §4) —
eight clients' VGA frames served by ``Runtime(mesh=make_host_mesh(),
shard_mode="always")`` and, for comparison, by a single-device runtime on
the same traffic.

Each phase prints its compile seconds, persistent-cache hits, the answers
it checked and the device's peak memory.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU, or without this checkout's ``src/`` beside it, the script
exits non-zero and prints no such line.

    python3 chip_smoke.py [--seed N] [--chips 1|4]
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

LM_MODEL = "stablelm-1.6b"
LM_SLOTS = 8
LM_MAX_SEQ = 1024
PROMPT_LENGTHS = (7, 64, 600)    # 600: not a multiple of the flash block
GENS = (16, 8)                   # each client's first and second request
N_LM_CLIENTS = 4
MAX_TICKS = 64                   # ample: the longest client needs ~26 ticks
VGA = (480, 640)                 # benchmarks/common.py BANDWIDTHS["mid_vga"]

#: Fallback acceptance for a served token that differs from
#: ``sequential_decode``: its logit under the b=1 reference, teacher-forced
#: along the served tokens, may sit at most this far below the reference's
#: largest logit.  Logits are bf16; random-init logits peak near 4-5, where
#: one bf16 step is 2**-5, so this is two bf16 steps at the top logit — a
#: near-tie that a different summation order may break, not a wrong token.
LOGIT_GAP_TOL = 2.0 ** -4


class CompileMeter:
    """Sums JAX's own compile events — tracing, lowering and the backend
    compile (a persistent-cache load counts as a backend compile) — counts
    persistent-cache hits and misses, and keeps each backend compile's
    seconds by function name."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = self.hits = self.misses = 0
        self.backend = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, fun_name="?", **_):
        if event in self._DURATIONS:
            self.seconds += duration
        if event == self._DURATIONS[-1]:
            self.compiles += 1
            self.backend.append((round(duration, 1), fun_name))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return (self.seconds, self.compiles, self.hits, self.misses)

    def since(self, snap) -> str:
        s, c, h, m = (a - b for a, b in zip(self.snapshot(), snap))
        slowest = sorted(self.backend[snap[1]:], reverse=True)[:3]
        return (f"compile_s={s:.1f} compiles={c} cache_hits={h} "
                f"cache_misses={m} slowest_backend_compiles={slowest}")


def peak_bytes() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


# ---------------------------------------------------------------------------
# phase 1: LM serving
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=4)
def _logit_fns(cfg, max_seq):
    """b=1 prefill and decode step returning logits, and the decode step
    vmapped over a slot axis as the serve tick runs it (the diagnostics of
    a token that breaks bitwise parity).  Params are arguments, never
    closed over: a closure would bake gigabytes of weights into the
    program as constants."""
    import jax
    from repro.models import transformer
    prefill = jax.jit(lambda p, toks: transformer.lm_prefill(
        p, cfg, toks[None], max_seq))
    decode = jax.jit(lambda p, tok, c: transformer.lm_decode(
        p, cfg, tok[None], c))
    batched = jax.jit(lambda p, c, toks: jax.vmap(
        lambda cc, t: transformer.lm_decode(p, cfg, t[None], cc)[0][0])(
            c, toks))
    return prefill, decode, batched


def teacher_forced_gaps(params, cfg, prompt, served, max_seq):
    """Per served token: the b=1 reference's largest logit minus its logit
    for the served token, decoding along the served tokens."""
    import jax.numpy as jnp
    prefill, decode, _ = _logit_fns(cfg, max_seq)
    logits, cache = prefill(params, jnp.asarray(prompt, jnp.int32))
    gaps = []
    for t, tok in enumerate(served):
        row = np.asarray(logits[0], np.float32)
        gaps.append(float(row.max() - row[tok]))
        if t + 1 < len(served):
            logits, cache = decode(params, jnp.int32(tok), cache)
    return gaps


def batched_vs_single_logits(params, cfg, prompt, tok, max_seq, slots):
    """Largest |difference| between the logits of the first decode step
    after the prefill of ``prompt`` (input token ``tok``) run b=1 and run
    as slot 0 of a ``slots``-wide vmapped decode, on the same cache."""
    import jax
    import jax.numpy as jnp
    prefill, decode, batched = _logit_fns(cfg, max_seq)
    _, cache = prefill(params, jnp.asarray(prompt, jnp.int32))
    single = decode(params, jnp.int32(tok), cache)[0][0]
    stacked = jax.tree_util.tree_map(lambda l: jnp.stack([l] * slots), cache)
    row0 = batched(params, stacked, jnp.full((slots,), tok, jnp.int32))[0]
    return float(jnp.max(jnp.abs(single.astype(jnp.float32) -
                                 row0.astype(jnp.float32))))


def serve_lm(seed: int, model: str = LM_MODEL, slots: int = LM_SLOTS,
             max_seq: int = LM_MAX_SEQ, lengths=PROMPT_LENGTHS, gens=GENS,
             n_clients: int = N_LM_CLIENTS, max_ticks: int = MAX_TICKS,
             require_kernel: bool = True) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.launch import model_serve as ms
    from repro.runtime import Device, Runtime

    rng = np.random.default_rng(seed)
    rt = Runtime()
    hub = Device("hub")
    ps = ms.serve_pipeline(model=model, slots=slots, max_seq=max_seq)
    run = hub.add_pipeline(ps, rng=jax.random.PRNGKey(seed), jit=False)
    rt.add_device(hub)
    cfg = ps.elements["lm"].cfg

    clients, k = [], 0
    for i in range(n_clients):
        reqs = []
        for gen in gens:
            prompt = rng.integers(0, cfg.vocab, lengths[k % len(lengths)])
            reqs.append((prompt.tolist(), gen))
            k += 1
        dev = Device(f"client{i}")
        crun = dev.add_pipeline(ms.client_pipeline(
            prompts=";".join(",".join(map(str, p)) for p, _ in reqs),
            gens=";".join(str(g) for _, g in reqs)), jit=False)
        rt.add_device(dev)
        clients.append((crun, reqs))

    def waiting():
        return [i for i, (c, reqs) in enumerate(clients)
                if len(c.sink_log.get("res", [])) < len(reqs)]
    ticks = 0
    while waiting() and ticks < max_ticks:
        rt.run(1)
        ticks += 1
    if waiting():
        raise RuntimeError(f"clients {waiting()} still lack answers after "
                           f"{ticks} ticks")

    qb = rt.stats()["query_batching"]
    if qb["tokens_generated"] != (qb["tokens_delivered"] + qb["tokens_dropped"]
                                  + qb["tokens_in_flight"]):
        raise AssertionError(f"token conservation broken: {qb}")

    # the prefill executable the server compiled for the longest prompt
    elem = ps.elements["lm"]
    hlo = elem._prefill_jit.lower(
        run.params["lm"], jnp.zeros((max(lengths),), jnp.int32)
    ).compile().as_text()
    has_kernel = "tpu_custom_call" in hlo
    if require_kernel and not has_kernel:
        raise AssertionError("the compiled prefill holds no Pallas kernel "
                             "(tpu_custom_call): flash attention fell back")

    params = run.params["lm"]
    bitwise, near = 0, []
    for i, (crun, reqs) in enumerate(clients):
        answers = [np.asarray(b.tensor).tolist()
                   for b in crun.sink_log["res"][:len(reqs)]]
        for j, ((prompt, gen), ans) in enumerate(zip(reqs, answers)):
            if len(ans) != gen:
                raise AssertionError(f"client {i} answer {j}: {len(ans)} "
                                     f"tokens, asked for {gen}")
            ref = ms.sequential_decode(params, cfg, prompt, gen, max_seq)
            if ans == ref:
                bitwise += 1
                continue
            step = next(t for t, (a, b) in enumerate(zip(ans, ref)) if a != b)
            print(f"  client {i} answer {j} (prompt {len(prompt)}): differs "
                  f"from sequential_decode from token {step}", flush=True)
            gaps = teacher_forced_gaps(params, cfg, prompt, ans, max_seq)
            drift = batched_vs_single_logits(params, cfg, prompt, ans[0],
                                             max_seq, slots)
            print(f"    max reference logit gap {max(gaps):.6f} (tolerance "
                  f"{LOGIT_GAP_TOL}); first decode step's logits b=1 vs "
                  f"{slots}-slot differ by up to {drift:.6f}", flush=True)
            near.append(max(gaps))
            if max(gaps) > LOGIT_GAP_TOL:
                raise AssertionError(
                    f"client {i} answer {j}: served token {max(gaps):.6f} "
                    f"below the reference's best logit")
    return {"ticks": ticks, "answers": bitwise + len(near),
            "bitwise": bitwise, "within_logit_tol": len(near),
            "prefill_tpu_custom_call": has_kernel,
            "tokens_generated": qb["tokens_generated"],
            "streams_finished": qb["streams_finished"]}


# ---------------------------------------------------------------------------
# phases 2 and 3: frames over the wire
# ---------------------------------------------------------------------------

def _relu_model(hw):
    """A per-pixel ReLU server model: exact in any lowering, and about half
    of its output is zero, so the sparse codec has real work."""
    import jax
    from repro.core import TensorSpec
    from repro.core.elements import register_model
    key = f"smoke_relu_{hw[0]}x{hw[1]}"
    register_model(key, lambda rng: {}, lambda p, x: jax.nn.relu(x),
                   out_specs=(TensorSpec(hw + (3,), "float32"),))
    return key


def _frame(hw, t):
    """Client frame ``t``: testsrc's pattern made float (values k - 127.5,
    exact in float32)."""
    from repro.core.element import PipelineContext
    from repro.core.elements import TestSrc
    import jax.numpy as jnp
    src = TestSrc(name="src", width=hw[1], height=hw[0])
    buf = src.apply({}, [], PipelineContext({"src": {"frame": jnp.int32(t)}}))
    return np.asarray(buf[0].tensors[0], np.float32) - 127.5


def _wire_fleet(rt, hw, codecs):
    from repro.core import parse_launch
    from repro.runtime import Device
    model = _relu_model(hw)
    hub = Device("hub")
    srv = parse_launch(
        f"tensor_query_serversrc operation=frames name=ssrc ! "
        f"tensor_filter model={model} ! tensor_query_serversink name=ssink")
    srv.elements["ssink"].pair_with(srv.elements["ssrc"])
    hub.add_pipeline(srv, jit=False)
    rt.add_device(hub)
    runs = []
    for i, codec in enumerate(codecs):
        dev = Device(f"cam{i}")
        runs.append(dev.add_pipeline(parse_launch(
            f"testsrc width={hw[1]} height={hw[0]} ! tensor_converter ! "
            f"tensor_transform mode=arithmetic "
            f"option=typecast:float32,add:-127.5 ! "
            f"tensor_query_client operation=frames codec={codec} name=qc ! "
            f"appsink name=res"), jit=False))
        rt.add_device(dev)
    return srv, runs


@contextlib.contextmanager
def xla_codecs():
    """Route the codec kernels to their XLA statements (kernels/ops.py picks
    Pallas only where it reports a TPU) for the reference round trip."""
    from repro.kernels import ops as kops
    real = kops.use_interpret
    kops.use_interpret = lambda: True
    try:
        yield
    finally:
        kops.use_interpret = real


def serve_codecs(seed: int, hw=VGA, codecs=("quant8", "quant8", "sparse",
                                            "sparse"), ticks: int = 2,
                 require_kernel: bool = True) -> dict:
    import jax
    from repro.core import compression as comp
    from repro.core.buffers import StreamBuffer
    from repro.kernels import ops as kops
    from repro.runtime import Runtime

    del seed      # the frames are testsrc's deterministic pattern
    if require_kernel and kops._impl(None) != "pallas":
        raise AssertionError("the codecs would run their XLA statements, "
                             "not the Pallas kernels")
    rt = Runtime(query_batch=len(codecs))
    _, runs = _wire_fleet(rt, hw, codecs)
    rt.run(ticks)
    qb = rt.stats()["query_batching"]
    if qb["fused_frames"] != ticks * len(codecs):
        raise AssertionError(f"fused wire path served {qb['fused_frames']} "
                             f"of {ticks * len(codecs)} frames")

    def round_trip(x, codec):
        wire, _ = comp.encode(StreamBuffer(tensors=(x,)), codec)
        return comp.decode(wire, codec).tensors[0]

    checked = 0
    for codec, crun in zip(codecs, runs):
        got = [np.asarray(b.tensor) for b in crun.sink_log["res"]]
        if len(got) != ticks:
            raise AssertionError(f"{codec} client got {len(got)} answers")
        for t, ans in enumerate(got):
            with xla_codecs():
                ref = round_trip(jax.nn.relu(round_trip(_frame(hw, t),
                                                        codec)), codec)
                ref = np.asarray(ref)
            if not np.array_equal(ans, ref):
                bad = int(np.sum(ans != ref))
                raise AssertionError(
                    f"{codec} answer {t}: {bad} of {ans.size} values differ "
                    f"from the XLA codec round trip")
            checked += 1
    return {"answers": checked, "fused_frames": qb["fused_frames"],
            "codec_impl": kops._impl(None), "frame": list(hw) + [3]}


def serve_mesh(seed: int, hw=VGA, n_clients: int = 8,
               ticks: int = 2) -> dict:
    """§4 mesh-sharded offloading on every local device, against the same
    traffic served single-device."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.buffers import StreamBuffer
    from repro.launch.mesh import batch_spec, make_host_mesh
    from repro.runtime import Runtime

    del seed
    mesh = make_host_mesh()
    answers, sharded_frames = {}, 0
    for label, kw in (("sharded", dict(mesh=mesh, shard_mode="always")),
                      ("single", {})):
        rt = Runtime(query_batch=n_clients, **kw)
        srv, runs = _wire_fleet(rt, hw, ["none"] * n_clients)
        rt.run(ticks)
        answers[label] = [[np.asarray(b.tensor) for b in r.sink_log["res"]]
                          for r in runs]
        if label == "sharded":
            sharded_frames = rt.stats()["query_batching"]["sharded_frames"]
            server_run = rt.devices[0].runs[0]
    if sharded_frames != ticks * n_clients:
        raise AssertionError(f"mesh served {sharded_frames} of "
                             f"{ticks * n_clients} frames")
    for i, (a, b) in enumerate(zip(answers["sharded"], answers["single"])):
        if len(a) != ticks or len(b) != ticks:
            raise AssertionError(f"client {i}: {len(a)}/{len(b)} answers")
        for t, (x, y) in enumerate(zip(a, b)):
            want = np.maximum(_frame(hw, t), 0)
            if not (np.array_equal(x, y) and np.array_equal(x, want)):
                raise AssertionError(f"client {i} frame {t}: sharded and "
                                     f"single-device answers differ")

    # where the sharded executable leaves its outputs: one burst through the
    # same compiled step the batcher uses, before any host fetch
    plan = srv.plan
    src = plan.query_sources[0].name
    step = plan.compiled_step_n(hoist_io=True, hoist_queries=True, mesh=mesh)
    frames = np.stack([_frame(hw, 0)] * n_clients)
    stacked = {src: StreamBuffer(tensors=(frames,),
                                 pts=np.zeros((n_clients,), np.int32))}
    stacked = jax.device_put(stacked, NamedSharding(mesh, P(batch_spec(mesh))))
    params = jax.device_put(server_run.params,
                            NamedSharding(mesh, P()))
    outs, _ = step(params, server_run.state, stacked)
    spread = [len(l.sharding.device_set)
              for l in jax.tree_util.tree_leaves(outs)]
    print(f"  sharded outputs: device_set sizes {spread} "
          f"(mesh of {mesh.size})")
    if min(spread) != mesh.size:
        raise AssertionError("sharded outputs do not span the mesh")
    return {"answers": 2 * ticks * n_clients, "mesh": dict(mesh.shape),
            "sharded_frames": sharded_frames}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-sharded offloading path")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no package at {SRC}; run from a checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache_dir} ({entries} entries at start)",
          flush=True)

    meter = CompileMeter()
    phases = ([("mesh_offloading", serve_mesh)] if args.chips == 4 else
              [("lm_serving", serve_lm), ("codec_wire", serve_codecs)])
    for name, fn in phases:
        snap = meter.snapshot()
        result = fn(args.seed)
        print(f"phase {name}: {meter.since(snap)} "
              f"peak_bytes_in_use={peak_bytes()} {json.dumps(result)}",
              flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
