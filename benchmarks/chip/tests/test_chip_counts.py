"""Operation and byte counts, peaks and the traffic generator of the chip
benchmark, on the CPU."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import counts, peaks, traffic  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                       "stablelm-1.6b.json")) as _f:
    LM = json.load(_f)

LAYER = 4 * 2048 * 2048 + 3 * 2048 * 5632          # q, k, v, o + gated MLP
HEAD = 2048 * 100352


def test_decode_token_flops_at_stablelm_widths():
    # 2 flops per multiply-add through 24 layers and the head, plus the
    # attention of one query over ctx + 1 keys
    assert counts.layer_matmul_params(LM) == LAYER
    want = 2 * (24 * LAYER + HEAD) + 24 * 4 * 32 * 64 * 101
    assert counts.decode_token_flops(LM, 100) == want
    assert 2.8e9 < counts.decode_token_flops(LM, 0) < 2.9e9


def test_weight_bytes_are_the_matmul_weights_in_bf16():
    assert counts.weight_bytes(LM) == 2 * (24 * LAYER + HEAD)


def test_prefill_is_the_sum_of_its_positions():
    """A prompt's prefill does each position's layer work once and the
    head once: the per-token decode counts summed, less n - 1 heads."""
    n = 37
    per_token = sum(counts.decode_token_flops(LM, i) for i in range(n))
    assert counts.prefill_flops(LM, n) == per_token - (n - 1) * 2 * HEAD


@pytest.mark.parametrize("ctx", [0, 63, 1023])
def test_flash_decode_reads_only_valid_positions(ctx):
    flops, nbytes = counts.flash_decode_call(LM, ctx)
    assert flops == 4 * 32 * 64 * (ctx + 1)
    assert nbytes == 2 * (2 * (ctx + 1) * 32 * 64 + 2 * 32 * 64)


def test_flash_prefill_counts_the_causal_half():
    flops, nbytes = counts.flash_prefill_call(LM, 768)
    assert flops == 4 * 32 * 64 * (768 * 769 // 2)
    assert nbytes == 2 * 4 * 768 * 2048


VGA = 480 * 640 * 3


@pytest.mark.parametrize("codec,direction,ops,nbytes", [
    ("quant8", "enc", 3 * VGA, VGA * 4 + VGA + (VGA // 96) * 4),
    ("quant8", "dec", VGA, VGA + (VGA // 96) * 4 + VGA * 4),
    ("sparse", "enc", VGA, VGA * 4 + (VGA // 4) * 8),
    ("sparse", "dec", VGA // 4, (VGA // 4) * 8 + VGA * 4),
])
def test_codec_counts_on_the_frame_bytes(codec, direction, ops, nbytes):
    assert counts.codec_call(codec, direction, VGA, 3) == (ops, nbytes)


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
    # bandwidth bounds a byte-heavy call, compute a flop-heavy one
    assert peaks.min_seconds(1.0, 819e9, "TPU v5 lite") == pytest.approx(1.0)
    assert peaks.min_seconds(197e12, 1.0, "TPU v5 lite") == pytest.approx(1.0)


@pytest.mark.parametrize("mix", ["decode-long", "burst-prefill"])
def test_every_seed_gets_the_same_work(mix):
    m = traffic.load(mix)
    seeds = (1, 2 ** 31 + 7, 2 ** 33 + 5)
    if m["kind"] == "lm_closed_loop":
        runs = [[r for c in traffic.closed_loop_cycles(m, s, 1000) for r in c]
                for s in seeds]
    else:
        runs = [traffic.open_loop_schedule(m, s, 45.0, 1000) for s in seeds]
    sizes = [sorted((len(r.prompt), r.gen) for r in run) for run in runs]
    assert sizes[0] == sizes[1] == sizes[2]
    assert [r.prompt for r in runs[0]] != [r.prompt for r in runs[1]]
    if m["kind"] == "lm_open_loop":
        # the same count of arrivals in each phase of each period
        phase = [sorted(int(r.due // 2) for r in run) for run in runs]
        assert phase[0] == phase[1] == phase[2]
        assert all(0 <= r.due < 45.0 for r in runs[0])


def test_each_round_of_the_closed_loop_is_the_same_work():
    """The window starts with every client's first request: that wave,
    and every later one, holds the same sizes whatever the seed."""
    m = traffic.load("decode-long")
    waves = []
    for seed in (3, 2 ** 32 + 1):
        cycles = traffic.closed_loop_cycles(m, seed, 1000)
        waves.append([sorted((len(c[k].prompt), c[k].gen) for c in cycles)
                      for k in range(m["cycle"])])
    assert waves[0] == waves[1]
    assert waves[0][0] == waves[0][1]


def test_proportional_sizes():
    assert traffic.proportional([[64, 0.5], [192, 0.3], [448, 0.2]], 10) == \
        [64] * 5 + [192] * 3 + [448] * 2
    assert len(traffic.proportional([[4, 0.3], [8, 0.3], [16, 0.25],
                                     [32, 0.15]], 7)) == 7
