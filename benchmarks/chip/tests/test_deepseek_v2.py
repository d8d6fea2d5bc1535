"""DeepSeek-V2 served by the program against the benchmark's plain float32
reference (``configs/deepseek-v2.reference.py``), on the CPU at the
program's smoke size: prefill, decoding through the latent cache, the
expert layer's held shares summed against the uncut layer, and the routing
of the two sides position by position.

Both sides run in float32 here, so what separates them is summation order
only: logits agree to 1e-4 of their scale (the program's decode reads the
latent cache in the absorbed form, the reference recomputes keys and
values in the naive form), and routing agrees exactly at these sizes.
"""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.chip import harness  # noqa: E402
from benchmarks.chip.deploy import lm_serve_moe  # noqa: E402

CONFIGS = os.path.join(ROOT, "benchmarks", "chip", "configs")
#: logits of the two float32 sides, relative to the largest logit
LOGIT_RTOL = 1e-4


def tiny_deepseek(**cut):
    """deepseek-v2-236b at the ``deepseek-v2-smoke`` preset's sizes, float32:
    3 layers (dense, then two expert layers), 16 experts in 4 groups with
    the best 2 kept, top-4, 8 held, the published YaRN and x16 scaling."""
    with open(os.path.join(CONFIGS, "deepseek-v2-236b.json")) as f:
        cfg = json.load(f)
    cfg.update(name="deepseek-v2-236b", hidden_size=256,
               intermediate_size=512, moe_intermediate_size=128,
               num_hidden_layers=3, num_attention_heads=4,
               num_key_value_heads=4, vocab_size=512, kv_lora_rank=64,
               q_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
               v_head_dim=32, n_routed_experts=8, n_routed_experts_total=16,
               n_group=4, topk_group=2, num_experts_per_tok=4,
               torch_dtype="float32", preset="deepseek-v2-smoke", slots=4,
               max_seq=64, initializer_range=0.05,
               limits={"logit_gap_mean": 1e-4})
    cfg.update(cut)
    return cfg


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_deepseek()
    ref = harness.load_reference(cfg)
    w = ref.init_weights(cfg, jax.random.PRNGKey(7))
    model_cfg = lm_serve_moe.preset_config(cfg)
    return cfg, ref, w, model_cfg, lm_serve_moe.to_program(w, cfg)


def _close(got, want):
    scale = float(np.max(np.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=LOGIT_RTOL * scale, rtol=0)


def test_prefill_logits_match_reference(setup):
    from repro.models import transformer
    cfg, ref, w, mc, p = setup
    toks = jax.random.randint(jax.random.PRNGKey(1), (40,), 0, 512)
    for n in (9, 23, 40):
        logits, _ = transformer.lm_prefill(p, mc, toks[None, :n], 64)
        want = ref.logits_at(w, cfg, toks[:n], jnp.asarray([n - 1]))
        _close(logits, want)


def test_decode_through_the_cache_matches_reference(setup):
    """Prefill 20 tokens, then decode 20 more through the latent cache:
    every step's logits against the reference's full forward."""
    from repro.models import transformer
    cfg, ref, w, mc, p = setup
    toks = jax.random.randint(jax.random.PRNGKey(2), (40,), 0, 512)
    _, cache = transformer.lm_prefill(p, mc, toks[None, :20], 64)
    step = jax.jit(lambda p, t, c: transformer.lm_decode(p, mc, t, c))
    got = []
    for i in range(20, 40):
        logits, cache = step(p, toks[i][None], cache)
        got.append(logits[0])
    want = ref.logits_at(w, cfg, toks, jnp.arange(20, 40))
    _close(jnp.stack(got), want)


def test_routes_match_reference_at_every_position(setup):
    from repro.models import transformer
    cfg, ref, w, mc, p = setup
    toks = jax.random.randint(jax.random.PRNGKey(3), (48,), 0, 512)
    _, _, routes = transformer.lm_prefill(p, mc, toks[None], 64,
                                          with_routes=True)
    want = ref.routes_at(w, cfg, toks)
    np.testing.assert_array_equal(np.sort(np.asarray(routes[:, 0]), -1),
                                  np.sort(np.asarray(want), -1))


@pytest.mark.parametrize("shares", [2, 4])
def test_held_shares_sum_to_the_uncut_layer(shares):
    """One expert layer: the routed parts that disjoint held shares give,
    plus the shared experts once, equal the reference's layer with every
    expert held."""
    from repro.models import moe as MOE
    cfg = tiny_deepseek(n_routed_experts=16)
    ref = harness.load_reference(cfg)
    w = ref.init_weights(cfg, jax.random.PRNGKey(11))
    mc = lm_serve_moe.preset_config(tiny_deepseek())
    p = lm_serve_moe.to_program(w, cfg)["layers"][1]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(12), (24, 256))
    size = 16 // shares
    total = MOE._shared_experts(p, mc, x)
    for k in range(shares):
        part = {n: p[n][k * size:(k + 1) * size]
                for n in ("w_gate", "w_up", "w_down")}
        part.update(router=p["router"], shared=p["shared"])
        y, _ = MOE.moe_tokens(part, mc, x, first=k * size, shared=False)
        total = total + y
    items = dict(ref._items(cfg))
    items["rope_scaling"] = cfg["rope_scaling"]
    want, _ = ref._moe(x, w["layers"][1]["mlp"], items, False)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
