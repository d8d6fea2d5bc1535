"""The program's tracer (``core/tracing.py``).

* off, it records nothing and builds no profiler annotation, and the
  answers are bitwise those of a run with it on, for an LM behind
  ``model_serve`` and for a small camera fleet on the fused wire path;
* on, every decode tick holds the expected span tree (each child inside
  its parent) and each request's stamps come in order: arrival, prefill,
  first token, join, finish;
* the batcher's counters are computed from static shapes: each joiner
  writes one slot's cache on the device, nothing is fetched, and a join
  tick ships the admit lanes only;
* the rings stay bounded and say how much they dropped.
"""
import jax
import numpy as np
import pytest

from repro.core import TensorSpec, parse_launch, tracing
from repro.core.elements import register_model
from repro.launch import model_serve as ms
from repro.runtime import Device, Runtime

PROMPTS = ["1,2,3", "4,5,6,7", "8,9", "10,11,12"]
GENS = ["5", "3;6", "4", "6;2"]


def _reset():
    tracing.enable()          # empties the rings and the requests held
    tracing.disable()


@pytest.fixture(autouse=True)
def tracer_off():
    """Every test starts and ends with the tracer off and empty."""
    _reset()
    yield
    _reset()


@pytest.fixture
def count_annotations(monkeypatch):
    made = []
    real = jax.profiler.TraceAnnotation

    def counting(name, **kwargs):
        made.append(name)
        return real(name, **kwargs)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    return made


def _lm(ticks=14, slots=4):
    """An LM hub with four streaming clients; answers per client and the
    runtime."""
    rt = Runtime(query_batch=8)
    hub = Device("hub")
    hub.add_pipeline(ms.serve_pipeline(model="stablelm-smoke-flash",
                                       slots=slots, max_seq=32), jit=False)
    rt.add_device(hub)
    runs = []
    for i, (p, g) in enumerate(zip(PROMPTS, GENS)):
        dev = Device(f"tv{i}")
        runs.append(dev.add_pipeline(ms.client_pipeline(prompts=p, gens=g),
                                     jit=False))
        rt.add_device(dev)
    rt.run(ticks)
    return [[np.asarray(b.tensor) for b in r.sink_log.get("res", [])]
            for r in runs], rt


def _fleet(ticks=3):
    """Four 8x8 cameras, two on quant8 and two on sparse, offloading to a
    ReLU hub over the fused wire path; answers per camera."""
    register_model("trace_relu", lambda rng: {},
                   lambda p, x: jax.nn.relu(x),
                   out_specs=(TensorSpec((8, 8, 3), "float32"),))
    rt = Runtime(query_batch=4)
    hub = Device("hub")
    srv = parse_launch("tensor_query_serversrc operation=f name=ssrc ! "
                       "tensor_filter model=trace_relu ! "
                       "tensor_query_serversink name=ssink")
    srv.elements["ssink"].pair_with(srv.elements["ssrc"])
    hub.add_pipeline(srv, jit=False)
    rt.add_device(hub)
    runs = []
    for i, codec in enumerate(("quant8", "quant8", "sparse", "sparse")):
        dev = Device(f"cam{i}")
        runs.append(dev.add_pipeline(parse_launch(
            f"testsrc width=8 height=8 ! tensor_converter ! "
            f"tensor_transform mode=arithmetic "
            f"option=typecast:float32,add:-127.5 ! "
            f"tensor_query_client operation=f codec={codec} name=qc ! "
            f"appsink name=res"), jit=False))
        rt.add_device(dev)
    rt.run(ticks)
    return [[np.asarray(b.tensor) for b in r.sink_log.get("res", [])]
            for r in runs]


def _assert_same(a, b):
    assert [len(x) for x in a] == [len(x) for x in b]
    for xs, ys in zip(a, b):
        for x, y in zip(xs, ys):
            np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("serve", ["lm", "fleet"])
def test_off_records_nothing_and_answers_as_on(serve, count_annotations):
    run = (lambda: _lm()[0]) if serve == "lm" else _fleet
    off = run()
    assert count_annotations == []
    got = tracing.drain()
    assert got.spans == [] and got.requests == [] and got.open == []
    tracing.enable()
    on = run()
    tracing.disable()
    assert count_annotations, "an enabled tracer annotates the profiler"
    assert tracing.drain().spans
    assert sum(len(x) for x in off) > 0
    _assert_same(off, on)


def test_off_hands_out_shared_no_ops():
    assert tracing.span("a") is tracing.span("b")
    assert tracing.request() is tracing.request()
    assert tracing.stamp() == 0


def _inside(child, parent):
    return parent.start <= child.start and child.end <= parent.end


def test_on_each_decode_tick_holds_its_span_tree():
    tracing.enable()
    _, rt = _lm()
    got = tracing.drain()
    by_id = {s.id: s for s in got.spans}
    names = {s.name for s in got.spans}
    assert {"sched.tick", "lm.admit", "lm.prefill", "lm.decode",
            "lm.admit.build", "lm.admit.write",
            "lm.decode.dispatch", "lm.decode.fetch", "lm.finish"} <= names
    assert not {"lm.admit.fetch", "lm.admit.copy"} & names
    want_parent = {"lm.admit": "sched.tick", "lm.decode": "sched.tick",
                   "lm.prefill": "lm.admit",
                   "lm.admit.build": "lm.decode",
                   "lm.admit.write": "lm.decode",
                   "lm.decode.dispatch": "lm.decode",
                   "lm.decode.fetch": "lm.decode", "lm.finish": "lm.decode"}
    for s in got.spans:
        if s.name in want_parent:
            parent = by_id[s.parent]
            assert parent.name == want_parent[s.name], s
            assert _inside(s, parent), (s, parent)
    decodes = [s for s in got.spans if s.name == "lm.decode"]
    qb = rt.stats()["query_batching"]
    assert len(decodes) == qb["decode_ticks"]
    assert sum(d.attrs["active"] for d in decodes) == qb["slot_ticks"]
    assert sum(1 for d in decodes if d.attrs["joins"]) == qb["join_ticks"]
    assert sum(d.attrs.get("d2h_bytes", 0) for d in decodes) == \
        qb["admit_d2h_bytes"]
    assert sum(d.attrs.get("h2d_bytes", 0) for d in decodes) == \
        qb["admit_h2d_bytes"]
    for key in ("admit_rows", "admit_write_bytes"):
        assert sum(d.attrs.get(key, 0) for d in decodes) == qb[key] > 0
    writes = [s for s in got.spans if s.name == "lm.admit.write"]
    assert sum(w.attrs["n"] for w in writes) == qb["admit_rows"]
    # within a join tick the lanes' build comes first, then the row
    # writes, the dispatch, and the fetch of the answer lanes
    d = next(d for d in decodes if d.attrs["joins"])
    kids = sorted((s for s in got.spans if s.parent == d.id),
                  key=lambda s: s.start)
    assert [s.name for s in kids][:4] == ["lm.admit.build",
                                          "lm.admit.write",
                                          "lm.decode.dispatch",
                                          "lm.decode.fetch"]
    assert all(a.end <= b.start for a, b in zip(kids, kids[1:]))


def test_on_request_stamps_come_in_order_and_name_their_spans():
    tracing.enable()
    _lm()
    got = tracing.drain()
    assert got.requests
    order = ("arrive", "prefill", "first_token", "join", "finish")
    for req in got.requests:
        stamps = [req.stamps[k] for k in order]
        assert stamps == sorted(stamps), req
    rids = {r.rid for r in got.requests}
    for name in ("lm.prefill", "lm.finish"):
        tagged = {s.rid for s in got.spans if s.name == name}
        assert None not in tagged
        assert rids <= tagged
    assert {s.rid for s in got.spans if s.name == "lm.decode"} == {None}


def test_admit_writes_one_slots_cache_per_joiner_and_ships_lanes_only():
    from repro.models import transformer
    _, rt = _lm()
    elem = next(e for e in rt.devices[0].runs[0].pipe.elements.values()
                if getattr(e, "is_stream_serve", False))
    one = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(
        transformer.cache_init(elem.cfg, 1, elem.max_seq)))
    qb = rt.stats()["query_batching"]
    # each started stream joined a slot once: one row written on the
    # device, nothing fetched to the host
    joined = qb["streams_started"] - len(
        next(iter(rt._batchers.values()))._waiting)
    assert qb["admit_rows"] == joined
    assert qb["admit_write_bytes"] == joined * one
    assert qb["admit_d2h_bytes"] == 0
    # a join tick ships the admit lanes only
    lanes = elem.slots * (1 + 4 + 4)
    assert qb["admit_h2d_bytes"] == qb["join_ticks"] * lanes
    assert qb["slot_ticks"] <= qb["decode_ticks"] * elem.slots


def test_rings_stay_bounded_and_count_what_they_drop():
    tracing.enable(capacity=8)
    for i in range(20):
        with tracing.span("s") as sp:
            sp.set(i=i)
    for _ in range(12):
        tracing.request().finish()
    got = tracing.drain()
    assert [s.attrs["i"] for s in got.spans] == list(range(12, 20))
    assert len(got.requests) == 8
    assert got.dropped == 12 + 4
    assert tracing.drain().spans == []


def test_requests_in_flight_are_held_until_they_finish():
    tracing.enable(capacity=4)
    reqs = [tracing.request() for _ in range(6)]
    got = tracing.drain()
    assert [r.rid for r in got.open] == [r.rid for r in reqs[2:]]
    assert got.dropped == 2
    reqs[5].finish()
    got = tracing.drain()
    assert [r.rid for r in got.requests] == [reqs[5].rid]
    assert reqs[5] not in got.open


def test_spans_nest_per_thread_and_take_the_request_entered():
    import threading
    tracing.enable()
    req = tracing.request()

    def other():
        with tracing.span("other"):
            pass
    with req, tracing.span("outer"):
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        with tracing.span("inner") as sp:
            sp.set(n=3)
    spans = {s.name: s for s in tracing.drain().spans}
    assert spans["inner"].parent == spans["outer"].id
    assert spans["other"].parent == -1 and spans["other"].rid is None
    assert spans["inner"].rid == spans["outer"].rid == req.rid
    assert spans["inner"].attrs == {"n": 3}
    assert _inside(spans["inner"], spans["outer"])
