"""The chip benchmark's check, driven end to end on the CPU at a tiny size:
a sound run comes out correct, and a run with the timed path broken
underneath comes out not correct, once for each fault the cells can have.

The harness's look for a chip is skipped: the deployment modules run with
a context built here, on the configuration files of the benchmark with
their sizes cut down, so what is checked is the benchmark's own path from
the first request to ``correct``.
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import harness, run  # noqa: E402
from benchmarks.chip.deploy import camera_fleet, lm_serve  # noqa: E402

CONFIGS = os.path.join(ROOT, "benchmarks", "chip", "configs")
SEED = 2 ** 31 + 12345


def _config(name, **cut):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        cfg = json.load(f)
    cfg.update(cut, name=name)
    return cfg


def tiny_lm():
    """stablelm at the program's CPU preset sizes, float32: the logit gap
    of a sound run is rounding only."""
    return _config("stablelm-1.6b", hidden_size=256, intermediate_size=512,
                   num_hidden_layers=2, num_attention_heads=4,
                   num_key_value_heads=4, vocab_size=512,
                   torch_dtype="float32", preset="stablelm-smoke-flash",
                   slots=4, max_seq=64, limits={"logit_gap": 1e-3})


LM_MIX = {"kind": "lm_closed_loop", "clients": 4, "cycle": 2,
          "prompt_lengths": [[8, 1], [16, 1]], "gen_lengths": [[4, 1], [6, 1]],
          "check_tokens": 24, "drain_s": 20, "trace_seconds": 1}


OPEN_MIX = {"kind": "lm_open_loop", "rate_per_s": 4.0,
            "phases": [[0.5, 2.5], [1.0, 0.25]],
            "prompt_lengths": [[8, 1], [16, 1]], "gen_lengths": [[2, 1], [5, 1]],
            "clients_ready": 2, "check_tokens": 12, "drain_s": 30,
            "trace_seconds": 1}


def tiny_fleet():
    return _config("vga-fleet", frame=[48, 64, 3], query_batch=2)


CAM_MIX = {"kind": "camera_loop", "cameras": 4,
           "codecs": [["quant8", 2], ["sparse", 2]], "warm_ticks": 3,
           "check_frames_per_codec": 4, "trace_seconds": 1}


def _run(deploy, cfg, mix, control=False, seconds=1.5):
    ctx = run.Context(cfg, mix, SEED, seconds, False, "cpu",
                      harness.CompileMeter(), control=control)
    out = deploy.run(ctx)
    assert out["attempted"] > 0
    return out


def _correct(deploy, cfg, mix):
    out = _run(deploy, cfg, mix)
    return run.verdict(out["checks"]), out["checks"]


def test_sound_lm_run_is_correct_and_its_control_is_not():
    """The served tokens pass; the reference in the next lower precision
    (fp8 matmuls), read at the same positions, fails the same limit."""
    out = _run(lm_serve, tiny_lm(), LM_MIX, control=True)
    assert run.verdict(out["checks"]), out["checks"]
    assert not run.verdict(out["control_checks"]), out["control_checks"]
    assert out["control_checks"][0][0] == "logit_gap.control"


def test_sound_open_loop_run_answers_every_request_correctly():
    out = _run(lm_serve, tiny_lm(), OPEN_MIX)
    checks = {name: (value, limit) for name, value, limit in out["checks"]}
    assert checks["unanswered"] == (0, 0)
    assert run.verdict(out["checks"]), out["checks"]
    assert out["failed"] == 0


def test_decode_step_that_leaves_its_cache_unchanged_is_caught(monkeypatch):
    from repro.core.plan import ExecutionPlan
    orig = ExecutionPlan.compiled_serve_tick

    def frozen(plan, state, donate=None):
        fn = orig(plan, state, donate=False)

        def serve(params, st, inputs):
            outs, new = fn(params, st, inputs)
            new = dict(new, lm=dict(new["lm"], cache=st["lm"]["cache"]))
            return outs, new
        return serve
    monkeypatch.setattr(ExecutionPlan, "compiled_serve_tick", frozen)
    ok, checks = _correct(lm_serve, tiny_lm(), LM_MIX)
    assert not ok, checks


def test_token_altered_where_it_is_produced_is_caught(monkeypatch):
    from repro.core.modelserve import ModelServeElement
    orig = ModelServeElement.host_prefill

    def shifted(self, params, prompt):
        tok, cache = orig(self, params, prompt)
        return (tok + 1) % self.cfg.vocab, cache
    monkeypatch.setattr(ModelServeElement, "host_prefill", shifted)
    ok, checks = _correct(lm_serve, tiny_lm(), LM_MIX)
    assert not ok, checks


def test_sound_fleet_run_is_correct_and_its_control_is_not():
    """The answers pass; the reference round trip computed in bfloat16
    fails the same limit."""
    out = _run(camera_fleet, tiny_fleet(), CAM_MIX, control=True)
    assert run.verdict(out["checks"]), out["checks"]
    assert not run.verdict(out["control_checks"]), out["control_checks"]
    assert out["control_checks"][0][0] == "mismatch_share.control"


def test_answer_altered_where_it_is_produced_is_caught(monkeypatch):
    import jax
    from repro.core import TensorSpec
    from repro.core.elements import register_model

    def off_by_half(shape):
        key = "bench_relu_off_" + "x".join(map(str, shape))
        register_model(key, lambda rng: {},
                       lambda p, x: jax.nn.relu(x) + 0.5,
                       out_specs=(TensorSpec(tuple(shape), "float32"),))
        return key
    monkeypatch.setattr(camera_fleet, "relu_model", off_by_half)
    ok, checks = _correct(camera_fleet, tiny_fleet(), CAM_MIX)
    assert not ok, checks


@pytest.mark.parametrize("name", ["stablelm-2", "vga-fleet"])
def test_references_import_nothing_of_the_program(name):
    with open(os.path.join(CONFIGS, f"{name}.reference.py")) as f:
        src = f.read()
    assert "repro" not in src and "benchmarks" not in src
