"""The chip benchmark's check for the deepseek-v2 cell, driven end to end on
the CPU at the program's smoke size: a sound run comes out correct and its
fp8 control does not, and a run with the timed path broken underneath comes
out not correct, once for each fault the expert and latent layers can have.

The harness, the mixes and the run helpers are those of
``test_chip_faults.py``; what is added here is the deployment module
``deploy/lm_serve_moe.py`` and its faults.
"""
import dataclasses
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import run  # noqa: E402
from benchmarks.chip.deploy import lm_serve_moe  # noqa: E402
from benchmarks.chip.tests.test_chip_faults import (  # noqa: E402
    CONFIGS, LM_MIX, _correct, _run)
from benchmarks.chip.tests.test_deepseek_v2 import tiny_deepseek  # noqa: E402


def test_sound_moe_run_is_correct_and_its_control_is_not():
    """deepseek-v2 at the program's smoke size: the served tokens pass, the
    fp8 control fails the same limit, and the program routes every compared
    position as the reference does."""
    out = _run(lm_serve_moe, tiny_deepseek(), LM_MIX, control=True)
    assert run.verdict(out["checks"]), out["checks"]
    assert not run.verdict(out["control_checks"]), out["control_checks"]
    assert out["counters"]["route_compared"] > 0
    assert out["counters"]["route_differ_positions"] == 0
    stats = out["counters"]["stats"]
    assert 0 < stats["moe_routed_held"] < stats["moe_routed_total"]


def _route_fault(monkeypatch, fault):
    from repro.models import moe
    orig = moe.route

    def route(router, cfg, xt):
        if fault == "ungrouped":
            cfg = dataclasses.replace(cfg, n_group=1, topk_group=1)
        w, i, probs = orig(router, cfg, xt)
        if fault == "unscaled":
            w = w / cfg.routed_scaling_factor
        return w, i, probs
    monkeypatch.setattr(moe, "route", route)


@pytest.mark.parametrize("fault", ["ungrouped", "unscaled"])
def test_moe_routing_fault_is_caught(monkeypatch, fault):
    """A plain top-k over all experts in place of the group-limited one, or
    the routed weights without their x16 scaling."""
    _route_fault(monkeypatch, fault)
    ok, checks = _correct(lm_serve_moe, tiny_deepseek(), LM_MIX)
    assert not ok, checks


def test_decode_step_that_leaves_its_latent_cache_unchanged_is_caught(
        monkeypatch):
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
    ok, checks = _correct(lm_serve_moe, tiny_deepseek(), LM_MIX)
    assert not ok, checks


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(CONFIGS, "deepseek-v2.reference.py")) as f:
        src = f.read()
    assert "repro" not in src and "benchmarks" not in src
