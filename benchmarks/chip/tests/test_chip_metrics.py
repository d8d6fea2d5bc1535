"""Per-layer readers of the chip benchmark on records built here: what each
reads, and that a reader with nothing to read returns None, never 0."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import counts, harness, peaks  # noqa: E402
from benchmarks.chip import trace as tr  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                       "stablelm-1.6b.json")) as _f:
    LM = json.load(_f)
KIND = "TPU v5 lite"
CONTEXTS = [63, 400, 1000]


def _summary(ops, modules, spans=()):
    busy = tr.union((o.start, o.start + o.dur) for o in ops)
    return tr.TraceSummary(window=(0.0, 1.0),
                           busy_s=sum(b - a for a, b in busy), devices=1,
                           ops=ops, modules=modules, spans=list(spans),
                           busy=busy)


def _record(summary, spans):
    return harness.RunRecord(cfg=LM, kind=KIND, spans=spans, trace=summary,
                             traced=(0.0, 1.0))


def _op(name, start, dur):
    return tr.Op(start, dur, name, name)


def decode_record():
    """Two serve ticks, each with a 2 ms loop; a third loop outside any
    serve tick does not count."""
    modules = [tr.Span("jit_serve_tick(1)", 0.10, 0.05),
               tr.Span("jit_serve_tick(1)", 0.30, 0.05),
               tr.Span("jit_prefill(2)", 0.50, 0.05)]
    ops = [_op("%while.1 = (f32[48,1]) while()", 0.11, 0.002),
           _op("%fusion.3 = bf16[3,2048] fusion()", 0.12, 0.01),
           _op("%while.1 = (f32[48,1]) while()", 0.31, 0.002),
           _op("%while.9 = (s32[]) while()", 0.51, 0.004)]
    spans = [harness.Span("serve_tick.nojoin", 0.10, 0.16,
                          {"contexts": CONTEXTS}),
             harness.Span("serve_tick.nojoin", 0.30, 0.36,
                          {"contexts": CONTEXTS})]
    in_trace = [tr.Span(s.name, s.start, s.dur) for s in spans]
    return _record(_summary(ops, modules, in_trace), spans)


def test_flash_decode_roofline_counts_valid_positions_per_tick():
    calls = [counts.flash_decode_call(LM, c) for c in CONTEXTS]
    least = 24 * peaks.min_seconds(sum(f for f, _ in calls),
                                   sum(b for _, b in calls), KIND)
    want = 100.0 * 2 * least / 0.004
    assert harness.read_metric("flash_decode_roofline",
                               decode_record()) == pytest.approx(want)


def test_decode_tick_ms_reads_the_program_inside_no_join_spans():
    assert harness.read_metric("decode_tick_ms", decode_record()) == \
        pytest.approx(50.0)


@pytest.mark.parametrize("name", [
    "flash_decode_roofline", "flash_prefill_roofline", "codec_roofline",
    "decode_tick_ms", "device_idle_pct.lm-decode", "lm_mfu_pct.decode",
    "prefill_ms", "wire_serve_ms", "admit_ms.lm-burst"])
def test_reader_with_nothing_to_read_returns_none(name):
    empty = _record(_summary([], []), [])
    assert harness.read_metric(name, empty) is None
    untraced = _record(None, [])
    assert harness.read_metric(name, untraced) is None


def test_idle_share_is_the_complement_of_busy():
    rec = decode_record()
    busy = rec.trace.busy_s
    assert harness.read_metric("device_idle_pct.lm-decode", rec) == \
        pytest.approx(100.0 * (1.0 - busy))


@pytest.mark.parametrize("name,reader", [
    ("admit_ms.lm-decode", "admit_ms.py"),
    ("device_idle_pct.vga", "device_idle_pct.py"),
    ("sched_self_ms.any-cell", "sched_self_ms.py"),
    ("codec_roofline", "codec_roofline.py")])
def test_a_metric_split_by_cell_reads_its_shared_reader(name, reader):
    assert os.path.basename(harness.metric_file(name)) == reader


def test_every_per_layer_metric_has_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        assert os.path.exists(harness.metric_file(m["name"])), m["name"]


def test_control_takes_the_programs_place_in_the_checks():
    checks = [("logit_gap", 0.05, 0.25), ("wrong_lengths", 0, 0)]
    ctl = harness.control_checks(checks, "logit_gap", 0.9)
    assert ctl == [("logit_gap.control", 0.9, 0.25), ("wrong_lengths", 0, 0)]


def test_frame_latency_is_timed_per_camera():
    """Camera 0 is answered every tick, camera 1 every other tick: each
    answer is timed from its own camera's previous answer, not from the
    previous tick."""
    from benchmarks.chip.deploy.camera_fleet import AnswerClock
    clock = AnswerClock(0.0, [5, 7])
    for k, now in enumerate((1.0, 2.0, 3.0, 4.0)):
        clock.after_tick(now, [6 + k, 7 + (k + 1) // 2])
    assert clock.lat_ms == pytest.approx([1e3, 1e3, 2e3, 1e3, 1e3, 2e3])
    assert clock.spanned == 2      # each of camera 1's answers took two
    assert clock.tick == 4
