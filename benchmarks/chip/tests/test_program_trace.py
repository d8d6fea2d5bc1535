"""The readers of the program's own records (``program_trace.py``):
each on a record built here, each returning None where a record holds
nothing of the program's, the device-idle part of a span on the small
recorded trace, and a tiny run on the CPU whose trace reduction keeps
the program's spans beside the benchmark's own."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import harness  # noqa: E402
from benchmarks.chip import program_trace as pt  # noqa: E402
from benchmarks.chip import trace as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny.xplane.pb")
with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                       "stablelm-1.6b.json")) as _f:
    LM = json.load(_f)


def _span(name, start, end, sid, parent=-1, rid=None, **attrs):
    return pt.ProgramSpan(name, start, end, sid, parent, rid, attrs)


def lm_record():
    """Two decode ticks of a 1 s window: a join tick (two joiners, fetch
    10 ms each, copy 20 ms of base and 5 ms per joiner, dispatch 100 ms,
    fetch 5 ms) and a tick without a join; two requests, one of which
    waited 40 ms for its prefill and one whose prefill came before the
    window."""
    spans = [
        _span("lm.admit.copy", 0.100, 0.120, 2, 1),
        _span("lm.admit.fetch", 0.120, 0.130, 3, 1),
        _span("lm.admit.copy", 0.130, 0.135, 9, 1),
        _span("lm.admit.fetch", 0.135, 0.145, 10, 1),
        _span("lm.admit.copy", 0.145, 0.150, 11, 1),
        _span("lm.admit.build", 0.100, 0.152, 1, 0, n=2),
        _span("lm.decode.dispatch", 0.152, 0.252, 4, 0),
        _span("lm.decode.fetch", 0.252, 0.257, 5, 0),
        _span("lm.decode", 0.100, 0.260, 0, joins=2, active=4, slots=8,
              d2h_bytes=400_000_000, h2d_bytes=3_000_000_000),
        _span("lm.decode.dispatch", 0.400, 0.401, 7, 6),
        _span("lm.decode.fetch", 0.401, 0.550, 8, 6),
        _span("lm.decode", 0.400, 0.551, 6, joins=0, active=2, slots=8),
    ]
    reqs = [{"arrive": 0.010, "prefill": 0.050, "first_token": 0.060},
            {"arrive": -0.5, "prefill": -0.4, "first_token": -0.3,
             "join": 0.1, "finish": 0.6}]
    rec = harness.RunRecord(cfg=LM, kind="TPU v5 lite", spans=[],
                            trace=None, traced=(0.0, 1.0))
    rec.program = pt.Program(spans, reqs, 0, (0.0, 1.0))
    return rec


@pytest.mark.parametrize("name,want", [
    ("admit_fetch_ms.lm-decode", 20.0),
    ("admit_copy_ms.lm-decode", 30.0),
    ("admit_ship_ms.lm-decode", 105.0),
    ("admit_mb.lm-decode", 3400.0),
    ("slot_occupancy_pct.lm-decode", 100.0 * 6 / 16),
    ("lm_queue_wait_ms.lm-decode", 40.0)])
def test_lm_reader_on_a_record_built_here(name, want):
    assert harness.read_metric(name, lm_record()) == pytest.approx(want)


def fleet_record():
    """Busy 0.10-0.20 and 0.50-0.60 of a 1 s trace: a codec call of 0.3 s
    with 0.1 s busy in it, another wholly idle, and a wire serve of 0.2 s
    half busy."""
    busy = [(0.10, 0.20), (0.50, 0.60)]
    spans = [tr.Span("codec.batch", 0.05, 0.30), tr.Span("codec.batch",
                                                         0.30, 0.10),
             tr.Span("wire.serve", 0.45, 0.20),
             tr.Span("codec_batch", 0.05, 0.30)]
    summary = tr.TraceSummary(window=(0.0, 1.0), busy_s=0.2, devices=1,
                              spans=spans, busy=busy)
    return harness.RunRecord(cfg={}, kind="TPU v5 lite", spans=[],
                             trace=summary, traced=(0.0, 1.0))


@pytest.mark.parametrize("name,want", [
    ("codec_idle_ms.vga", 1e3 * ((0.30 - 0.10) + 0.10) / 2),
    ("wire_idle_ms.vga", 100.0)])
def test_idle_reader_on_a_record_built_here(name, want):
    assert harness.read_metric(name, fleet_record()) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(pt.METRICS))
def test_reader_with_nothing_of_the_programs_returns_none(name):
    assert os.path.exists(harness.metric_file(name))
    bare = harness.RunRecord(cfg=LM, kind="TPU v5 lite")
    assert harness.read_metric(name, bare) is None
    bare.program = pt.Program()
    bare.trace = tr.TraceSummary(window=(0.0, 1.0), busy_s=0.0, devices=1)
    assert harness.read_metric(name, bare) is None


def test_idle_inside_the_recorded_wait_span():
    """The ``wait`` span of the recorded trace sleeps 50 ms on the host
    with the device idle."""
    summary = tr.reduce(DATA, ("busy", "wait"))
    (dur, idle), = pt.idle_inside(summary, "wait")
    assert dur == pytest.approx(0.05, abs=5e-3)
    assert idle == pytest.approx(dur, abs=2e-3)
    assert pt.idle_inside(None, "wait") == []


def test_a_program_without_the_tracer_records_nothing(monkeypatch):
    monkeypatch.setattr(pt, "_tracing", lambda: None)
    trace = pt.ProgramTrace()
    trace.open()
    trace.close()
    assert trace.program is None and trace.span_names() == []


def test_reduction_keeps_the_programs_spans_in_a_tiny_cpu_run():
    """The fleet at a tiny size, traced on the CPU: the reduction keeps
    the program's spans, the gaps are labelled by them, the benchmark's
    own span list holds only its own names, and the tracer is off
    again after the window."""
    from repro.core import tracing
    from benchmarks.chip.deploy import camera_fleet
    cfg = json.load(open(os.path.join(ROOT, "benchmarks", "chip",
                                      "configs", "vga-fleet.json")))
    cfg.update(frame=[16, 32, 3], query_batch=2, name="vga-fleet")
    mix = {"kind": "camera_loop", "cameras": 4,
           "codecs": [["quant8", 2], ["sparse", 2]], "warm_ticks": 3,
           "check_frames_per_codec": 2, "trace_seconds": 1}
    ctx = pt.context_class()(cfg, mix, 2 ** 31 + 7, 1.0, True, "cpu",
                             harness.CompileMeter())
    out = camera_fleet.run(ctx)
    assert not tracing.enabled()
    names = list(out["span_names"]) + ctx.program.span_names()
    summary = ctx.tracer.reduce(names)
    kept = {s.name for s in summary.spans}
    assert {"codec.batch", "codec.stack", "codec.run", "codec.fetch",
            "wire.serve", "wire.dispatch", "wire.fetch", "wire.route",
            "sched.tick"} <= kept
    assert {s.name for s in ctx.spans.spans} <= set(camera_fleet.SPAN_NAMES)
    labels = {name for name, _ in summary.longest_gaps(10)}
    assert labels & set(ctx.program.span_names())
    for dur, idle in pt.idle_inside(summary, "codec.batch"):
        assert 0.0 <= idle <= dur
