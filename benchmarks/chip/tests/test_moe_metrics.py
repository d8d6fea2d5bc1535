"""Counts and readers of the DeepSeek-V2 cell (``counts_moe.py``,
``moe_trace.py``, the readers of ``moe_expert_roofline``,
``mla_decode_roofline`` and ``lm_mfu_pct.deepseek``), and the map from a
compiled serve tick's HLO to the operations under each named scope, on
records and HLO fragments built here."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import counts_moe, harness, peaks  # noqa: E402
from benchmarks.chip import trace as tr  # noqa: E402
from benchmarks.chip.deploy.lm_serve_moe import scope_ops  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                       "deepseek-v2-236b.json")) as _f:
    DS = json.load(_f)
KIND = "TPU v5 lite"
CONTEXTS = [255, 1000, 2046]
#: per expert layer (4), per held expert (20): one tick's load lane
LOAD = [[3, 0, 1, 2] + [1] * 16] * 4


def test_counts_follow_published_widths():
    assert counts_moe.mla_params(DS) == (5120 * 1536 + 1536 * 128 * 192
                                         + 5120 * 576 + 512 * 128 * 256
                                         + 128 * 128 * 5120)
    assert counts_moe.mla_params(DS) == pytest.approx(149.2e6, rel=1e-3)
    assert counts_moe.expert_params(DS) == 3 * 5120 * 1536
    assert counts_moe.n_moe_layers(DS) == 4
    assert counts_moe.dense_token_params(DS) == (
        5 * counts_moe.mla_params(DS) + 3 * 5120 * 12288
        + 4 * 2 * counts_moe.expert_params(DS))


def test_mla_decode_call_counts_valid_positions():
    f, b = counts_moe.mla_decode_call(DS, 99)
    assert f == 2 * 128 * 576 * 100 + 2 * 128 * 512 * 100
    assert b == 2 * 576 * 100


def test_expert_call_reads_each_hit_expert_once():
    f, b = counts_moe.expert_call(DS, [3, 0, 1])
    p = 3 * 5120 * 1536
    assert f == 2 * p * 4
    assert b == 2 * (2 * p + 2 * 5120 * 4)
    assert counts_moe.expert_call(DS, [0] * 20) == (0, 0)


def test_token_counts_add_up():
    head = 2 * 5120 * 102400
    dense = 2 * counts_moe.dense_token_params(DS)
    assert counts_moe.decode_token_flops(DS, 99) == \
        dense + head + 5 * counts_moe.mla_decode_call(DS, 99)[0]
    assert counts_moe.prefill_flops(DS, 4) == \
        4 * dense + head + 5 * 2 * 128 * (128 + 64 + 128) * 10
    assert counts_moe.routed_flops(DS, 10) == 20 * 3 * 5120 * 1536


def _op(name, start, dur):
    return tr.Op(start, dur, name, name)


def moe_record():
    """Two no-join ticks and a join tick.  In each no-join tick the expert
    loop (``%while.3``, 3 ms) holds its body's op (1 ms, counted once) and
    the latent attention's fusion takes 2 ms; the same names outside a
    serve tick, or in the join tick, do not count."""
    modules = [tr.Span("jit_serve_tick(1)", 0.10, 0.05),
               tr.Span("jit_serve_tick(1)", 0.30, 0.05),
               tr.Span("jit_serve_tick(2)", 0.50, 0.05),
               tr.Span("jit_prefill(3)", 0.70, 0.05)]
    ops = []
    for t0 in (0.10, 0.30, 0.50, 0.70):
        ops += [_op("%while.3 = (s32[]) while()", t0 + 0.01, 0.003),
                _op("%convolution_fusion.7 = bf16[8,1536] fusion()",
                    t0 + 0.011, 0.001),
                _op("%fusion.91 = f32[64,128,2048] fusion()", t0 + 0.02,
                    0.002),
                _op("%fusion.92 = bf16[64,5120] fusion()", t0 + 0.03, 0.004)]
    attrs = {"contexts": CONTEXTS, "moe_load": LOAD}
    spans = [harness.Span("scope_ops", 0.0, 0.0,
                          {"moe_experts": ["convolution_fusion.7",
                                           "while.3"],
                           "mla_decode_attn": ["fusion.91"]}),
             harness.Span("serve_tick.nojoin", 0.10, 0.16, attrs),
             harness.Span("serve_tick.nojoin", 0.30, 0.36, attrs),
             harness.Span("serve_tick.join", 0.50, 0.56, attrs),
             harness.Span("host_prefill", 0.70, 0.76,
                          {"n": 512, "moe_held": 400})]
    in_trace = [tr.Span(s.name, s.start, s.end - s.start)
                for s in spans[1:4]]
    busy = tr.union((o.start, o.start + o.dur) for o in ops)
    summary = tr.TraceSummary(window=(0.0, 1.0),
                              busy_s=sum(b - a for a, b in busy), devices=1,
                              ops=ops, modules=modules, spans=in_trace,
                              busy=busy)
    return harness.RunRecord(cfg=DS, kind=KIND, spans=spans, trace=summary,
                             traced=(0.0, 1.0))


def test_moe_expert_roofline_reads_the_loop_once_per_no_join_tick():
    least = sum(peaks.min_seconds(*counts_moe.expert_call(DS, layer), KIND)
                for layer in LOAD)
    want = 100.0 * 2 * least / (2 * 0.003)
    assert harness.read_metric("moe_expert_roofline", moe_record()) == \
        pytest.approx(want)


def test_mla_decode_roofline_counts_occupied_slots_every_layer():
    calls = [counts_moe.mla_decode_call(DS, c) for c in CONTEXTS]
    least = 5 * peaks.min_seconds(sum(f for f, _ in calls),
                                  sum(b for _, b in calls), KIND)
    want = 100.0 * 2 * least / (2 * 0.002)
    assert harness.read_metric("mla_decode_roofline", moe_record()) == \
        pytest.approx(want)


def test_deepseek_mfu_counts_prefills_and_every_tick():
    flops = counts_moe.prefill_flops(DS, 512) + \
        counts_moe.routed_flops(DS, 400)
    per_tick = sum(counts_moe.decode_token_flops(DS, c) for c in CONTEXTS) \
        + counts_moe.routed_flops(DS, sum(map(sum, LOAD)))
    flops += 3 * per_tick
    want = 100.0 * flops / peaks.peaks_for(KIND)["bf16_flops_per_s"]
    assert harness.read_metric("lm_mfu_pct.deepseek", moe_record()) == \
        pytest.approx(want)


@pytest.mark.parametrize("name", ["moe_expert_roofline",
                                  "mla_decode_roofline",
                                  "lm_mfu_pct.deepseek",
                                  "decode_tick_ms.deepseek",
                                  "device_idle_pct.deepseek"])
def test_moe_readers_read_none_without_the_programs_records(name):
    """A record of a program without the load lane or the scope map (the
    parent of this configuration, or another model) reads None."""
    rec = moe_record()
    rec.spans = [s for s in rec.spans if s.name != "scope_ops"]
    for s in rec.spans:
        s.attrs.pop("moe_load", None)
        s.attrs.pop("moe_held", None)
    got = harness.read_metric(name, rec)
    if name.startswith(("moe_", "mla_", "lm_mfu")):
        assert got is None
    empty = harness.RunRecord(cfg=DS, kind=KIND)
    assert harness.read_metric(name, empty) is None


HLO = '''HloModule jit_serve_tick, entry_computation_layout={()->()}

%fused_computation.74.clone (param_0: bf16[20,5120,1536], param_1: s32[]) -> bf16[5120,1536] {
  %param_0 = bf16[20,5120,1536]{2,1,0} parameter(0)
  %dynamic_slice.193 = bf16[1,5120,1536]{2,1,0} dynamic-slice(%param_0, %param_1), dynamic_slice_sizes={1,5120,1536}, metadata={op_name="jit(serve_tick)/lm_serve_tick/vmap(moe_experts)/while/body/dynamic_slice"}
  ROOT %bitcast.834 = bf16[5120,1536]{1,0} bitcast(%dynamic_slice.193), metadata={op_name="jit(serve_tick)/lm_serve_tick/vmap(moe_experts)/while/body/squeeze"}
}

%fused_computation.80 (param_0.1: bf16[8,5120], param_1.2: bf16[20,5120,1536], param_2: s32[]) -> bf16[8,1536] {
  %fusion.804 = bf16[5120,1536]{1,0} fusion(%param_1.2, %param_2), kind=kLoop, calls=%fused_computation.74.clone
  ROOT %convolution.147 = bf16[8,1536]{1,0} convolution(%param_0.1, %fusion.804), dim_labels=bf_io->bf
}

%body.5 (p: (s32[], f32[64,5120], s32[], s32[20], s32[20], /*index=5*/s32[20])) -> (s32[], f32[64,5120], s32[], s32[20], s32[20], /*index=5*/s32[20]) {
  %p = (s32[], f32[64,5120]) parameter(0)
  %convolution_fusion.7 = bf16[8,1536]{1,0} fusion(%a, %b, %c), kind=kOutput, calls=%fused_computation.80
  ROOT %tuple.1 = (s32[], f32[64,5120]) tuple(%x, %y)
}

%cond.5 (p.1: (s32[], f32[64,5120])) -> pred[] {
  ROOT %compare.1 = pred[] compare(%i, %n), direction=LT, metadata={op_name="jit(serve_tick)/lm_serve_tick/vmap(moe_experts)/while/cond/lt"}
}

%fused_computation.90 (param_0.9: f32[64,128,2048]) -> f32[64,128,2048] {
  ROOT %add.3 = f32[64,128,2048]{2,1,0} add(%param_0.9, %param_0.9), metadata={op_name="jit(serve_tick)/lm_serve_tick/vmap(jit(main))/mla_decode_attn/add"}
}

ENTRY %main.100 (Arg_0.1: f32[64,128,2048]) -> f32[64,128,2048] {
  %fusion.91 = f32[64,128,2048]{2,1,0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.90
  %while.3 = (s32[], f32[64,5120]) while(%tuple.0), condition=%cond.5, body=%body.5, metadata={op_name="jit(serve_tick)/lm_serve_tick/vmap(moe_experts)/while"}
  ROOT %fusion.92 = f32[64,128,2048]{2,1,0} fusion(%fusion.91), kind=kLoop, calls=%fused_computation.93, metadata={op_name="jit(serve_tick)/lm_serve_tick/other"}
}
'''


def test_scope_ops_finds_the_operations_that_run_under_each_scope():
    """Operations of the entry and of loop bodies and conditions, by their
    own metadata or by what they fuse; instructions inside a fusion are not
    operations of their own.  A computation's header may hold ``=`` in
    ``/*index=5*/`` comments of a long tuple (the expert loop's body)."""
    got = scope_ops(HLO)
    assert got == {"moe_experts": ["compare.1", "convolution_fusion.7",
                                   "while.3"],
                   "mla_decode_attn": ["fusion.91"]}
