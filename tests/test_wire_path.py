"""Fused batched wire path (DESIGN.md §5): bitwise parity of every fast
path against its eager reference.

The PR-5 contract is that NOTHING on the wire path may move a bit:

* the XLA kernel fast paths equal the Pallas kernels (the TPU story and the
  CPU story encode the same block/tile contract);
* the stacked kernel entry points equal per-frame calls (tile/block merge);
* the batched host codec helpers equal per-frame ``encode``/``decode``
  including meta and the deferred truncation accounting totals;
* jitted deferred segments equal the interpreted deferred walk;
* a fused runtime's client responses equal the eager runtime's AND the
  sequential runtime's, at batch {1, 4, 8}, for quant8 and sparse clients.

Perf-marked smoke checks keep generous bounds — speed is measured on the
chip by ``benchmarks/chip/``.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import StreamBuffer, TensorSpec, parse_launch
from repro.core import compression as comp
from repro.core.elements import register_model
from repro.kernels import ops as kops
from repro.runtime import Device, Runtime


@pytest.fixture(scope="module", autouse=True)
def models():
    def init(rng):
        return {"w": jax.random.normal(rng, (12, 4)) * 0.3}

    def apply(p, x):
        return x.astype(jnp.float32).reshape(1, -1) @ p["w"]

    register_model("wpsvc", init, apply,
                   out_specs=(TensorSpec((1, 4), "float32"),))


def _server(rt, name="hub"):
    dev = Device(name)
    ps = parse_launch(
        "tensor_query_serversrc operation=op name=ssrc ! "
        "tensor_filter model=wpsvc ! tensor_query_serversink name=ssink")
    ps.elements["ssink"].pair_with(ps.elements["ssrc"])
    run = dev.add_pipeline(ps, jit=False)
    rt.add_device(dev)
    return run


def _clients(rt, n, codec="quant8"):
    runs = []
    for i in range(n):
        dev = Device(f"tv{i}")
        pc = parse_launch(
            f"testsrc width=2 height=2 ! tensor_converter ! "
            f"tensor_query_client operation=op codec={codec} name=qc ! "
            f"appsink name=res")
        runs.append(dev.add_pipeline(pc, jit=False))
        rt.add_device(dev)
    return runs


def _responses(run):
    return [np.asarray(b.tensor) for b in run.sink_log["res"]]


def _truncation(stats):
    """The lossy-encode totals of ``comp.codec_stats()``."""
    return {k: stats[k] for k in ("sparse_truncated_tensors",
                                  "sparse_dropped_values")}


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# kernel layer
# ---------------------------------------------------------------------------

class TestKernelImplParity:
    """The XLA fast paths ARE the kernels, bit for bit."""

    @pytest.mark.parametrize("shape", [(13, 7), (129,), (3, 5, 2), (),
                                       (64, 256)])
    def test_quant8_xla_equals_pallas(self, shape):
        x = jax.random.normal(jax.random.PRNGKey(0), shape)
        qp, sp = kops.quantize8(x, impl="pallas")
        qx, sx = kops.quantize8(x, impl="xla")
        np.testing.assert_array_equal(np.asarray(qp), np.asarray(qx))
        np.testing.assert_array_equal(np.asarray(sp), np.asarray(sx))
        np.testing.assert_array_equal(
            np.asarray(kops.dequantize8(qp, sp, impl="pallas")),
            np.asarray(kops.dequantize8(qp, sp, impl="xla")))

    @pytest.mark.parametrize("n,cap", [(7, 3), (200, 20), (600, 600),
                                       (1024, 256), (5000, 1000)])
    def test_sparse_xla_equals_pallas(self, n, cap):
        x = jax.random.normal(jax.random.PRNGKey(1), (n,))
        x = jnp.where(jax.random.uniform(jax.random.PRNGKey(2), (n,)) < 0.3,
                      x, 0.0)
        vp, ip, np_ = kops.sparse_enc(x, cap, impl="pallas")
        vx, ix, nx = kops.sparse_enc(x, cap, impl="xla")
        np.testing.assert_array_equal(np.asarray(vp), np.asarray(vx))
        np.testing.assert_array_equal(np.asarray(ip), np.asarray(ix))
        assert int(np_) == int(nx)
        np.testing.assert_array_equal(
            np.asarray(kops.sparse_dec(vp, ip, np_, n, impl="pallas")),
            np.asarray(kops.sparse_dec(vp, ip, np_, n, impl="xla")))

    def test_auto_dispatch_picks_xla_off_tpu(self):
        assert kops.use_interpret()          # CI boxes have no TPU
        assert kops._impl(None) == "xla"
        with pytest.raises(ValueError, match="impl"):
            kops._impl("fast")


class TestStackedKernelParity:
    """Stacked entry points == per-frame calls (tile/block merge)."""

    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    @pytest.mark.parametrize("shape", [(13, 7), (40,), (3, 5, 2)])
    def test_quant8_stacked(self, impl, shape):
        xs = jax.random.normal(jax.random.PRNGKey(3), (5,) + shape)
        qs, ss = kops.quantize8_stacked(xs, impl=impl)
        xr = kops.dequantize8_stacked(qs, ss, impl=impl)
        for i in range(5):
            q1, s1 = kops.quantize8(xs[i], impl=impl)
            np.testing.assert_array_equal(np.asarray(qs[i]), np.asarray(q1))
            np.testing.assert_array_equal(np.asarray(ss[i]), np.asarray(s1))
            np.testing.assert_array_equal(
                np.asarray(xr[i]),
                np.asarray(kops.dequantize8(q1, s1, impl=impl)))

    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    @pytest.mark.parametrize("n,cap", [(200, 20), (1024, 256), (600, 600)])
    def test_sparse_stacked(self, impl, n, cap):
        xs = jax.random.normal(jax.random.PRNGKey(4), (4, n))
        xs = jnp.where(
            jax.random.uniform(jax.random.PRNGKey(5), (4, n)) < 0.3, xs, 0.0)
        vs, is_, nz = kops.sparse_enc_stacked(xs, cap, impl=impl)
        ds = kops.sparse_dec_stacked(vs, is_, nz, n, impl=impl)
        for i in range(4):
            v1, i1, n1 = kops.sparse_enc(xs[i], cap, impl=impl)
            np.testing.assert_array_equal(np.asarray(vs[i]), np.asarray(v1))
            np.testing.assert_array_equal(np.asarray(is_[i]), np.asarray(i1))
            assert int(nz[i]) == int(n1)
            np.testing.assert_array_equal(
                np.asarray(ds[i]),
                np.asarray(kops.sparse_dec(v1, i1, n1, n, impl=impl)))


# ---------------------------------------------------------------------------
# codec layer
# ---------------------------------------------------------------------------

class TestBatchCodecParity:
    """encode_batch/decode_batch == per-frame encode/decode, including
    meta (codec claim, sparse_dropped) and the deferred accounting totals."""

    @pytest.mark.parametrize("codec", ["quant8", "sparse:0.25",
                                       "sparse:0.05", "none"])
    @pytest.mark.parametrize("batch", [1, 4, 8])
    def test_encode_batch_bitwise(self, codec, batch):
        bufs = [StreamBuffer(
            tensors=(jax.random.normal(jax.random.PRNGKey(i), (13, 7)),),
            pts=jnp.int32(i), meta={"client_id": i}) for i in range(batch)]
        comp.reset_codec_stats()
        eager = [comp.encode(b, codec) for b in bufs]
        stats_eager = comp.codec_stats()
        comp.reset_codec_stats()
        batched = comp.encode_batch(bufs, codec)
        stats = comp.codec_stats()
        # deferred totals agree
        assert _truncation(stats) == _truncation(stats_eager)
        # the batch helper counts its call and the frames it fetched; the
        # frames were on the device already, so nothing shipped to it
        assert stats["batch_calls"] == (codec != "none")
        assert stats["d2h_bytes"] == (codec != "none") * sum(
            leaf.nbytes for b, _ in batched
            for leaf in jax.tree_util.tree_leaves(b.tensors))
        assert stats["h2d_bytes"] == 0
        for (eb, en), (bb, bn) in zip(eager, batched):
            assert en == bn
            assert eb.meta == bb.meta              # incl. sparse_dropped
            _leaves_equal(eb.tensors, bb.tensors)

    @pytest.mark.parametrize("codec", ["quant8", "sparse:0.25", "none"])
    def test_decode_batch_bitwise(self, codec):
        bufs = [StreamBuffer(
            tensors=(jax.random.normal(jax.random.PRNGKey(i), (13, 7)),),
            pts=jnp.int32(i), meta={"client_id": i}) for i in range(4)]
        wire = [comp.encode(b, codec)[0] for b in bufs]
        eager = [comp.decode(w, codec) for w in wire]
        batched = comp.decode_batch(wire, codec)
        for e, b in zip(eager, batched):
            assert e.meta == b.meta                # wire meta stripped alike
            _leaves_equal(e.tensors, b.tensors)

    def test_truncation_accounting_defers_to_one_sync(self):
        """The dropped counts cross the host boundary once per batch call,
        and the per-frame meta signal survives the deferral."""
        dense = jnp.asarray(np.arange(1, 201, dtype=np.float32))
        bufs = [StreamBuffer(tensors=(dense * (i + 1),), pts=jnp.int32(i))
                for i in range(4)]
        comp.reset_codec_stats()
        batched = comp.encode_batch(bufs, "sparse:0.05")
        stats = comp.codec_stats()
        assert stats["sparse_truncated_tensors"] == 4
        per_frame = [b.meta["sparse_dropped"] for b, _ in batched]
        assert all(d > 0 for d in per_frame)
        assert sum(per_frame) == stats["sparse_dropped_values"]


# ---------------------------------------------------------------------------
# plan layer
# ---------------------------------------------------------------------------

class TestDeferredSegments:
    def test_compiled_deferral_matches_interpreted_bitwise(self):
        pc = parse_launch(
            "testsrc width=2 height=2 ! tensor_converter ! "
            "tensor_query_client operation=x name=qc ! appsink name=o"
        ).realize()
        params, s0 = pc.init(jax.random.PRNGKey(0)), pc.init_state()
        assert pc.plan.deferred_compilable
        pq_i = pc.plan.run_deferred(params, s0)
        pq_c = pc.plan.run_deferred_compiled(params, s0)
        assert pq_c.is_compiled and pq_c.client is pq_i.client
        _leaves_equal(pq_i.request, pq_c.request)
        answer = pq_i.request.with_(tensors=(jnp.ones((1, 4)),))
        out_i, st_i = pq_i.resume(answer)
        out_c, st_c = pq_c.resume(answer)
        _leaves_equal(out_i["o"], out_c["o"])
        _leaves_equal(st_i, st_c)

    def test_segments_cached_by_fingerprint(self):
        pc = parse_launch(
            "testsrc width=2 height=2 ! tensor_converter ! "
            "tensor_query_client operation=x name=qc ! appsink name=o"
        ).realize()
        params, s0 = pc.init(jax.random.PRNGKey(0)), pc.init_state()
        pc.plan.run_deferred_compiled(params, s0)
        fns = pc.plan._cache()["fns"]
        assert ("defer_seg", -1) in fns
        n = len(fns)
        pq = pc.plan.run_deferred_compiled(params, s0)
        pq.resume(pq.request.with_(tensors=(jnp.ones((1, 4)),)))
        assert ("defer_seg", pq.op_idx) in fns or \
            any(k[0] == "defer_seg" for k in fns)
        pc.plan.run_deferred_compiled(params, s0)
        assert len(fns) == len(pc.plan._cache()["fns"])

    def test_impure_prefix_is_not_compilable(self):
        pc = parse_launch(
            "mqttsrc sub-topic=cam name=src ! tensor_converter ! "
            "tensor_query_client operation=x name=qc ! appsink name=o"
        ).realize()
        assert pc.plan.has_query_clients
        assert not pc.plan.deferred_compilable


# ---------------------------------------------------------------------------
# runtime level (the acceptance surface)
# ---------------------------------------------------------------------------

class TestFusedRuntimeParity:
    @pytest.mark.parametrize("codec", ["quant8", "sparse:0.25"])
    @pytest.mark.parametrize("batch", [1, 4, 8])
    def test_fused_matches_sequential_bitwise(self, codec, batch):
        """THE acceptance pin: fused batched responses == sequential,
        bitwise, for codec clients at batch {1,4,8} — and the fused path
        really served (no silent fallback)."""
        ticks, n_clients = 2, 4
        streams, truncation = {}, {}
        for label, kw in (
                ("fused", dict(query_batch=batch)),
                ("sequential", dict(query_batch=0))):
            comp.reset_codec_stats()
            rt = Runtime(**kw)
            _server(rt)
            runs = _clients(rt, n_clients, codec=codec)
            rt.run(ticks)
            streams[label] = [_responses(r) for r in runs]
            if label == "fused":
                qb = rt.stats()["query_batching"]
                assert qb["fused_frames"] == ticks * n_clients
            truncation[label] = _truncation(comp.codec_stats())
        # deferred truncation accounting sums to the per-frame totals
        assert truncation["fused"] == truncation["sequential"]
        for ref, got in zip(streams["fused"], streams["sequential"]):
            assert len(ref) == len(got) == ticks
            for a, b in zip(ref, got):
                np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("codec", ["quant8", "sparse:0.25"])
    def test_decoded_answers_never_claim_a_codec(self, codec):
        """meta["codec"]-strip contract through the whole fused round trip:
        what lands in the client's appsink is a DECODED frame."""
        rt = Runtime(query_batch=4)
        _server(rt)
        runs = _clients(rt, 4, codec=codec)
        rt.run(2)
        for r in runs:
            for buf in r.sink_log["res"]:
                assert "codec" not in buf.meta
                assert "sparse_dropped" not in buf.meta

    def test_wire_buffers_on_the_channel_do_claim_their_codec(self):
        """...while the frames actually in flight are stamped wire-form."""
        rt = Runtime(query_batch=8)
        srv = _server(rt)
        _clients(rt, 2, codec="quant8")
        ssrc = srv.pipe.elements["ssrc"]
        seen = []
        orig_push = ssrc.endpoint.requests.push

        def spy(buf, nbytes=None):
            seen.append(buf)
            return orig_push(buf, nbytes)
        ssrc.endpoint.requests.push = spy
        rt.run(1)
        assert seen
        for buf in seen:
            assert buf.meta["codec"] == "quant8"
            from repro.core.buffers import Quant8Payload
            assert all(isinstance(t, Quant8Payload) for t in buf.tensors)


# ---------------------------------------------------------------------------
# perf smoke (generous bounds; speed is measured on the chip)
# ---------------------------------------------------------------------------

@pytest.mark.perf
class TestPerfSmoke:
    def test_sparse_enc_lm_frame_under_pr4_floor(self):
        """PR-4 measured ~101.8 ms for this exact encode; the fast path
        must land far under it even on a noisy CI box (bound 10x slack
        over the ~2.7 ms measured)."""
        x = jax.random.normal(jax.random.PRNGKey(0), (64 * 1024,))
        cap = int(x.size * 0.25)
        jax.block_until_ready(kops.sparse_enc(x, cap))  # compile
        best = min(_timed(lambda: jax.block_until_ready(
            kops.sparse_enc(x, cap))) for _ in range(3))
        assert best < 0.030, f"sparse_enc took {best * 1e3:.1f} ms"
        # and it is still the kernel, bit for bit
        v, i, n = kops.sparse_enc(x, cap)
        vp, ip, np_ = kops.sparse_enc(x, cap, impl="pallas")
        np.testing.assert_array_equal(np.asarray(v), np.asarray(vp))

    def test_encode_batch_amortizes_dispatch(self, monkeypatch):
        """The amortization property itself, deterministically: a batch of
        8 frames hits the stacked kernel ONCE where the per-frame loop pays
        8 kernel dispatches (wall-clock comparison at this size is
        noise)."""
        calls = {"single": 0, "stacked": 0}
        real_single, real_stacked = kops.quantize8, kops.quantize8_stacked

        def spy_single(*a, **k):
            calls["single"] += 1
            return real_single(*a, **k)

        def spy_stacked(*a, **k):
            calls["stacked"] += 1
            return real_stacked(*a, **k)
        monkeypatch.setattr(kops, "quantize8", spy_single)
        monkeypatch.setattr(kops, "quantize8_stacked", spy_stacked)
        frames = [StreamBuffer(
            tensors=(jax.random.normal(jax.random.PRNGKey(i), (192,)),),
            pts=jnp.int32(i)) for i in range(8)]
        [comp.encode(f, "quant8") for f in frames]
        assert calls == {"single": 8, "stacked": 0}
        calls.update(single=0)
        comp.encode_batch(frames, "quant8")
        assert calls == {"single": 0, "stacked": 1}


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0
