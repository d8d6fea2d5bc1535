"""The serving expert layer (``models/moe.py`` ``moe_serve``) and the
DeepSeek-V2 mechanisms under it: group-limited routing against a
brute-force selection, YaRN rotary frequencies and softmax scale against
the published formulas, dropless dispatch (a batch routed entirely to one
expert gives what each token gives alone), and the slot batch routed as
one group under ``jax.vmap``."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import model_serve as ms
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models.config import ModelConfig


def _cfg(**kw):
    base = dict(name="t", arch_type="moe", n_layers=2, d_model=32,
                n_heads=2, n_kv_heads=2, d_ff=64, vocab=64, n_experts=16,
                top_k=4, n_group=4, topk_group=2, norm_topk_prob=False,
                routed_scaling_factor=16.0, d_ff_expert=16,
                n_shared_experts=2, first_dense=1, dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def _brute_force(probs, n_group, topk_group, k):
    """Published group_limited_greedy, one token at a time in numpy."""
    t, e = probs.shape
    size = e // n_group
    out = []
    for row in probs:
        groups = [row[g * size:(g + 1) * size].max() for g in range(n_group)]
        kept = sorted(range(n_group), key=lambda g: -groups[g])[:topk_group]
        allowed = [i for i in range(e) if i // size in kept]
        out.append(sorted(allowed, key=lambda i: -row[i])[:k])
    return out


def test_group_limited_routing_matches_brute_force():
    cfg = _cfg()
    router = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    w, idx, probs = MOE.route(router, cfg, x)
    probs = np.asarray(probs, np.float64)
    want = _brute_force(probs, 4, 2, 4)
    for t in range(64):
        assert sorted(np.asarray(idx[t]).tolist()) == sorted(want[t])
        np.testing.assert_allclose(
            np.asarray(w[t]), 16.0 * probs[t, np.asarray(idx[t])], rtol=1e-6)
    # every chosen expert lies in one of the two kept groups
    assert all(len({i // 4 for i in row}) <= 2 for row in want)


def test_routing_without_groups_is_plain_renormalised_top_k():
    """The defaults keep the routing every other family had: top-k of the
    softmax, renormalised, no scaling."""
    cfg = _cfg(n_group=1, topk_group=1, norm_topk_prob=True,
               routed_scaling_factor=1.0)
    router = jax.random.normal(jax.random.PRNGKey(2), (32, 16))
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 32))
    w, idx, _ = MOE.route(router, cfg, x)
    probs = jax.nn.softmax(x @ router, -1)
    tw, ti = jax.lax.top_k(probs, 4)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ti))
    np.testing.assert_allclose(np.asarray(w),
                               np.asarray(tw / tw.sum(-1, keepdims=True)),
                               rtol=1e-6)


def _published_yarn(dim, base, factor, orig, beta_fast, beta_slow):
    """modeling_deepseek.py: DeepseekV2YarnRotaryEmbedding._set_cos_sin_cache
    and its helpers, transcribed."""
    def find_dim(num_rot):
        return (dim * math.log(orig / (num_rot * 2 * math.pi))) / (
            2 * math.log(base))
    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    linear = (np.arange(dim // 2, dtype=np.float32) - low) / (high - low)
    inv_freq_mask = 1.0 - np.clip(linear, 0, 1)
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2,
                                                    dtype=np.float32) / dim))
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


@pytest.mark.parametrize("preset", ["deepseek-v2-236b-ep8",
                                    "deepseek-v2-smoke"])
def test_yarn_frequencies_and_mscale_match_published_formulas(preset):
    cfg = ms.SERVE_MODELS[preset]()
    want = _published_yarn(cfg.qk_rope_dim, cfg.rope_theta, cfg.yarn_factor,
                           cfg.yarn_original_max_pos, cfg.yarn_beta_fast,
                           cfg.yarn_beta_slow)
    np.testing.assert_allclose(MLA.rope_inv_freq(cfg), want, rtol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1.0
    assert mscale == pytest.approx(1.2608, abs=1e-4)
    assert MLA.softmax_scale(cfg) == pytest.approx(
        (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5 * mscale ** 2)


def test_without_yarn_rope_and_scale_are_plain():
    cfg = _cfg()
    assert MLA.rope_inv_freq(cfg) is None
    assert MLA.softmax_scale(cfg) == (cfg.qk_nope_dim
                                      + cfg.qk_rope_dim) ** -0.5


def _params(cfg, seed=0):
    return MOE.moe_init(jax.random.PRNGKey(seed), cfg)


def _per_token(p, cfg, xt):
    """Each token alone through the serving layer."""
    return jnp.concatenate([MOE.moe_serve(p, cfg, xt[i][None, None])[0][0]
                            for i in range(xt.shape[0])], 0)


def test_skewed_batch_is_dropless():
    """Every token routed to expert 0 (and three more): the batch, as one
    prefill and as a vmapped slot batch, gives what each token gives
    alone; a capacity-bounded dispatch would drop most of them."""
    cfg = _cfg(experts_held=8)
    p = _params(cfg)
    p["router"] = p["router"].at[:, 0].set(0.0).at[0, 0].set(50.0)
    xt = jax.random.normal(jax.random.PRNGKey(4), (24, 32))
    xt = xt.at[:, 0].set(jnp.abs(xt[:, 0]) + 1.0)
    _, idx = MOE.moe_serve(p, cfg, xt[None])
    assert bool(jnp.all(jnp.any(idx[0] == 0, -1)))
    alone = _per_token(p, cfg, xt)
    batched, _ = MOE.moe_serve(p, cfg, xt[None])
    np.testing.assert_allclose(np.asarray(batched[0]), np.asarray(alone),
                               rtol=1e-5, atol=1e-5)
    slots, _ = jax.vmap(lambda x: MOE.moe_serve(p, cfg, x))(xt[:, None, None])
    np.testing.assert_allclose(np.asarray(slots[:, 0, 0]), np.asarray(alone),
                               rtol=1e-5, atol=1e-5)


def test_held_share_computes_only_its_experts():
    """A token routed to no held expert gets the shared experts alone."""
    cfg = _cfg(experts_held=4)
    p = _params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (6, 32))
    y, idx = MOE.moe_serve(p, cfg, x[None])
    none_held = ~jnp.any(idx[0] < 4, -1)
    shared = MOE._shared_experts(p, cfg, x)
    routed, _ = MOE.moe_tokens(p, cfg, x, shared=False)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(routed + shared),
                               rtol=1e-5, atol=1e-6)
    assert np.all(np.asarray(routed)[np.asarray(none_held)] == 0)


def test_slot_batch_is_one_group_under_vmap():
    """The vmapped layer holds one expert loop, not one per slot: its
    lowered program has as many loops as the unbatched layer."""
    cfg = _cfg(experts_held=8)
    p = _params(cfg)
    x = jnp.zeros((16, 1, 1, 32))
    one = jax.jit(lambda x: MOE.moe_serve(p, cfg, x)).lower(x[0]).as_text()
    many = jax.jit(jax.vmap(lambda x: MOE.moe_serve(p, cfg, x))).lower(
        x).as_text()
    assert one.count("stablehlo.while") == many.count("stablehlo.while") == 1
    assert "tensor<16x4xi32>" in many      # 16 slot tokens routed together


def test_smoke_keeps_groups_consistent():
    from repro.configs import deepseek_v2_236b
    s = deepseek_v2_236b.config().smoke()
    assert s.n_experts % s.n_group == 0 and s.topk_group <= s.n_group
    assert s.top_k <= s.topk_group * (s.n_experts // s.n_group)
    odd = dataclasses.replace(deepseek_v2_236b.config(), n_experts=6,
                              n_group=4, topk_group=3).smoke()
    assert odd.n_experts % odd.n_group == 0 and odd.topk_group <= odd.n_group


@pytest.mark.modelserve
def test_served_tokens_match_sequential_decode_with_staggered_joins():
    """deepseek-v2-smoke behind ``model_serve``: streams of different
    lengths join and leave the 4-slot table at different ticks, the expert
    layers route the slot batch as one group, and every answer is the
    per-request sequential decode's, with the held experts' load counted."""
    from repro.runtime import Device, Runtime
    rt = Runtime()
    hub = Device("hub")
    ps = ms.serve_pipeline(model="deepseek-v2-smoke", slots=4, max_seq=32)
    run = hub.add_pipeline(ps, jit=False)
    rt.add_device(hub)
    prompts = ["1,2,3", "5,6,7,8,9", "11,12", "3,4,5,6", "7,7"]
    gens = ["6", "9", "4", "7", "5"]
    clients = []
    for i, (p, g) in enumerate(zip(prompts, gens)):
        dev = Device(f"c{i}")
        clients.append(dev.add_pipeline(
            ms.client_pipeline(prompts=p, gens=g), jit=False))
        rt.add_device(dev)
    for _ in range(30):
        rt.tick()
    elem = ps.elements["lm"]
    params = run.params[elem.name]
    for r, p, g in zip(clients, prompts, gens):
        answers = [np.asarray(b.tensor).tolist()
                   for b in r.sink_log.get("res", [])]
        assert answers
        want = ms.sequential_decode(params, elem.cfg,
                                    [int(t) for t in p.split(",")], int(g), 32)
        assert all(a == want for a in answers)
    st = rt.stats()["query_batching"]
    assert 0 < st["moe_routed_held"] < st["moe_routed_total"]
    assert 0 < st["moe_load_max"] <= 4
