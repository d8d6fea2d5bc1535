"""Deployment kind ``camera_fleet``: camera ``Device``s that offload every
frame to one hub over ``tensor_query_client``, as the paper's Fig. 6/7
fleets do.  Each camera runs ``testsrc ! tensor_converter ! tensor_transform
typecast:float32,add:-127.5 ! tensor_query_client codec=... ! appsink``;
the hub runs ``tensor_query_serversrc ! tensor_filter ! tensor_query_
serversink`` with a per-pixel ReLU as its model.

The filter is a stand-in: what this deployment measures is the fabric (the
scheduler, the query batcher, the fused wire dispatch) and the codecs.

``correct`` compares a seeded sample of the answers each codec's cameras
held after the window with the configuration's reference round trip; the
number compared is the largest share, over the sampled frames, of a
frame's values that differ from the reference's.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from benchmarks.chip import harness, traffic as tf

SPAN_NAMES = ("tick", "wire_serve", "codec_batch")

#: a served value differs from the reference's where they are further
#: apart than this (the frame's values run from -127.5 to 127.5)
MISMATCH = 1e-3


def relu_model(shape) -> str:
    import jax
    from repro.core import TensorSpec
    from repro.core.elements import register_model
    key = "bench_relu_" + "x".join(map(str, shape))
    register_model(key, lambda rng: {}, lambda p, x: jax.nn.relu(x),
                   out_specs=(TensorSpec(tuple(shape), "float32"),))
    return key


class Fleet:
    def __init__(self, cfg: Dict, codecs: List[str], offsets: List[int]):
        import jax.numpy as jnp
        from repro.core import parse_launch
        from repro.runtime import Device, Runtime
        h, w, _ = cfg["frame"]
        self.rt = Runtime(query_batch=cfg["query_batch"])
        hub = Device("hub")
        srv = parse_launch(
            f"tensor_query_serversrc operation=frames name=ssrc ! "
            f"tensor_filter model={relu_model(cfg['frame'])} ! "
            f"tensor_query_serversink name=ssink")
        srv.elements["ssink"].pair_with(srv.elements["ssrc"])
        hub.add_pipeline(srv, jit=False)
        self.rt.add_device(hub)
        self.cams = []
        for i, (codec, off) in enumerate(zip(codecs, offsets)):
            dev = Device(f"cam{i}")
            run = dev.add_pipeline(parse_launch(
                f"testsrc name=src width={w} height={h} ! tensor_converter ! "
                f"tensor_transform mode=arithmetic "
                f"option=typecast:float32,add:-127.5 ! "
                f"tensor_query_client operation=frames codec={codec} "
                f"name=qc ! appsink name=res"), jit=False)
            run.state["src"] = {"frame": jnp.int32(off)}
            self.rt.add_device(dev)
            self.cams.append((codec, off, run))

    def held(self) -> List[int]:
        """Answers each camera holds at its ``appsink``."""
        return [len(run.sink_log.get("res", [])) for _, _, run in self.cams]

    def answered(self) -> int:
        return sum(self.held())

    def errors(self) -> int:
        return sum(len(v) for _, _, run in self.cams
                   for k, v in run.sink_log.items() if k.endswith(".error"))


class AnswerClock:
    """Frame latency per camera.  A camera keeps one frame in flight, so
    its next frame is due when its previous answer arrives (at the
    window's open for its first); each new answer is timed from there,
    whichever tick it comes in."""

    def __init__(self, t0: float, held: List[int]):
        self.last = [t0] * len(held)       # host clock of the last answer
        self.last_tick = [-1] * len(held)
        self.held = list(held)
        self.tick = 0
        self.lat_ms: List[float] = []
        self.spanned = 0     # answers whose round trip took over one tick

    def after_tick(self, now: float, held: List[int]):
        for i, n in enumerate(held):
            if n > self.held[i]:
                self.lat_ms.extend([(now - self.last[i]) * 1e3]
                                   * (n - self.held[i]))
                self.spanned += self.tick - self.last_tick[i] > 1
                self.last[i], self.last_tick[i], self.held[i] = \
                    now, self.tick, n
        self.tick += 1


def install_spans(spans: harness.Spans):
    from repro.core import compression as comp
    from repro.core.batching import QueryBatcher
    from repro.runtime import Runtime
    spans.wrap(Runtime, "tick", "tick")
    spans.wrap(QueryBatcher, "_serve_batched_wire", "wire_serve",
               attrs_fn=lambda self, pairs, codec: {"codec": codec,
                                                    "n": len(pairs)})
    for fn, direction in (("encode_batch", "enc"), ("decode_batch", "dec")):
        spans.wrap(comp, fn, "codec_batch",
                   attrs_fn=lambda bufs, codec, _d=direction: {
                       "codec": codec, "n": len(bufs), "dir": _d})


def run(ctx) -> Dict:
    cfg, mix = ctx.cfg, ctx.mix
    ref = harness.load_reference(cfg)
    codecs = tf.camera_codecs(mix)
    offsets = tf.frame_offsets(mix, ctx.seed)
    fleet = Fleet(cfg, codecs, offsets)
    fleet.rt.run(mix["warm_ticks"])                 # every batch shape
    before = fleet.held()
    if ctx.spans is not None:
        install_spans(ctx.spans)
    n0, e0 = fleet.answered(), fleet.errors()
    t0 = ctx.open_window()
    t_end = t0 + ctx.seconds
    clock = AnswerClock(t0, before)
    while time.perf_counter() < t_end:
        ctx.tick_tracer()
        fleet.rt.tick()
        clock.after_tick(time.perf_counter(), fleet.held())
    t1 = time.perf_counter()
    lat = clock.lat_ms
    ctx.close_window(t1)
    frames = fleet.answered() - n0
    failed = fleet.errors() - e0
    stats = fleet.rt.stats()["query_batching"]
    stats_ticks = fleet.rt.ticks
    ctx.read_memory()

    # a seeded sample of each codec's answers held after the window
    rng = tf.rng_for(ctx.seed, "check")
    shape = tuple(cfg["frame"])
    worst = {"share": 0.0, "share_control": 0.0, "max_abs": 0.0}
    per_codec: Dict[str, float] = {}
    checked = 0
    for codec in sorted(set(codecs)):
        cams = [(i, c) for i, c in enumerate(fleet.cams) if c[0] == codec]
        for _ in range(mix["check_frames_per_codec"]):
            i, (_, off, run) = cams[rng.integers(len(cams))]
            res = run.sink_log.get("res", [])
            j = int(rng.integers(before[i], len(res))) if len(res) > before[i] \
                else len(res) - 1
            got = np.asarray(res[j].tensor, np.float32)
            want = ref.answer(shape, off + j, codec).astype(np.float32)
            diff = np.abs(got - want)
            share = float(np.mean(diff > MISMATCH))
            worst["share"] = max(worst["share"], share)
            worst["max_abs"] = max(worst["max_abs"], float(diff.max()))
            per_codec[codec] = max(per_codec.get(codec, 0.0), share)
            checked += 1
            if ctx.control:
                import ml_dtypes
                low = ref.answer(shape, off + j, codec, ml_dtypes.bfloat16)
                worst["share_control"] = max(worst["share_control"], float(
                    np.mean(np.abs(low.astype(np.float32) - want) > MISMATCH)))
    # every camera keeps a frame in flight: one that got no answer in the
    # whole window lost it
    silent = sum(1 for i, (_, _, run) in enumerate(fleet.cams)
                 if len(run.sink_log.get("res", [])) <= before[i])
    del fleet
    harness.free_program()
    counters = {"frames_window": frames, "ticks": stats_ticks,
                "window_ticks": clock.tick,
                "answers_spanning_ticks": clock.spanned,
                "mismatch_share_by_codec": per_codec,
                "max_abs_diff": worst["max_abs"], "stats": stats}
    metrics = {"frames_per_s": frames / (t1 - t0)}
    if len(lat) >= 20:
        metrics["frame_latency_p95_ms"] = tf.percentile(lat, 95)
        counters["latency_p50_ms"] = tf.percentile(lat, 50)
    limit = cfg["limits"]["mismatch_share"]
    checks = [("mismatch_share", worst["share"], limit),
              ("silent_cameras", silent, 0),
              ("unchecked", int(checked == 0), 0)]
    out = {"attempted": frames + failed, "failed": failed,
           "metrics": metrics, "checks": checks, "counters": counters,
           "span_names": SPAN_NAMES}
    if ctx.control:
        out["control_checks"] = harness.control_checks(
            checks, "mismatch_share", worst["share_control"])
    return out
