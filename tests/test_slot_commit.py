"""The serve tick commits each slot's cache at the row (DESIGN.md §7).

Each layer writes ``where(active, new row, old row)`` at the slot's
position, a recurrent state is selected whole, and ``pos`` advances by
``active``.  Over several ticks under a mixed, changing active mask the
slot table, the lanes and the tokens are, bit for bit, those of the
commit the tick ran before: an uncommitted step selected tree-wide with
``where(active, new, old)``.  Cases: dense KV, windowed ring plus rGLRU,
latent attention with experts, int8 KV with scales, an SSM, and a
two-stage §8 hop.  The tick's ``commit_bytes`` counter is the
static-shape reckoning of what the commit writes.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import modelserve
from repro.core.buffers import StreamBuffer
from repro.launch import model_serve as ms
from repro.models import transformer
from repro.runtime import Device, Runtime

SLOTS = 6
MAX_SEQ = 32
TICKS = 4
STAGED = "stablelm-smoke-4l"


def _kv_quant():
    from repro.configs import stablelm_1_6b
    return dataclasses.replace(stablelm_1_6b.config().smoke(),
                               kv_cache_quant=True)


def _ssm():
    from repro.configs import mamba2_130m
    return mamba2_130m.config().smoke()


BUILT = {"stablelm-kv-quant": _kv_quant, "mamba2-smoke": _ssm}


@pytest.fixture
def built_presets(monkeypatch):
    """The two cases no preset has, registered for this test only."""
    for key, fn in BUILT.items():
        monkeypatch.setitem(modelserve.SERVE_MODELS, key, fn)


def _fill(key, tree):
    """Random values of each leaf's shape and dtype, so every row differs."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = [jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                             jnp.float32).astype(leaf.dtype)
           for i, leaf in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def _random_cache(elem, key):
    """A slot table with random rows and each slot at its own position."""
    cache = _fill(key, elem.init_state()["cache"])
    pos = jax.random.randint(jax.random.fold_in(key, 10 ** 6),
                             (elem.slots,), 0, MAX_SEQ - TICKS)
    return {**cache, "pos": pos.astype(jnp.int32)}


def _masks(seed):
    """A mixed active mask per tick, each one different."""
    rng = np.random.default_rng(seed)
    masks = []
    while len(masks) < TICKS:
        m = rng.random(SLOTS) < 0.5
        if 0 < m.sum() < SLOTS and not any((m == p).all() for p in masks):
            masks.append(m)
    return masks


def _table_where(step, cache, x, active):
    """The commit the tick ran before: the uncommitted step on every slot,
    then ``where(active, new, old)`` over the whole slot table."""
    def slot_step(c, xx, act):
        new_c, *outs = step(c, xx, act)
        return (jax.tree_util.tree_map(
            lambda old, new: jnp.where(act, new, old), c, new_c), *outs)
    return jax.vmap(slot_step)(cache, x, active)


def _bits(tree):
    return [np.asarray(leaf).tobytes()
            for leaf in jax.tree_util.tree_leaves(tree)]


def _assert_bitwise(got, want):
    got, want = jax.device_get((got, want))
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    assert _bits(got) == _bits(want)


def _serve(model):
    ps = ms.serve_pipeline(model=model, slots=SLOTS, max_seq=MAX_SEQ)
    ps.realize()
    return ps, ps.elements["lm"]


def _run_lm(model):
    """A monolithic ``model_serve`` tick against the table-wide commit."""
    ps, elem = _serve(model)
    cfg = elem.cfg
    params = ps.init(jax.random.PRNGKey(0))
    state = ps.init_state()
    src, sink = ps.plan.query_sources[0].name, ps.plan.query_sinks[0].name
    tick = ps.plan.compiled_serve_tick(state)

    @jax.jit
    def reference(p, cache, token, active):
        def step(c, tok, act):
            logits, new_c = transformer.lm_decode(p, cfg, tok[None], c)
            new_tok = jnp.argmax(logits[0], axis=-1).astype(jnp.int32)
            return new_c, jnp.where(act, new_tok, tok)
        return _table_where(step, cache, token, active)

    cache = _random_cache(elem, jax.random.PRNGKey(7))
    token = jax.random.randint(jax.random.PRNGKey(8), (SLOTS,), 0, cfg.vocab
                               ).astype(jnp.int32)
    want_cache = cache
    for active in _masks(11):
        state = {**state, elem.name: {
            "cache": cache, "active": jnp.asarray(active), "token": token,
            "remaining": jnp.full((SLOTS,), 100, jnp.int32)}}
        outputs, state = tick(params, state, {src: elem.empty_admit()})
        want_cache, want_token = reference(params[elem.name], want_cache,
                                           token, active)
        got = state[elem.name]
        _assert_bitwise(got["cache"], want_cache)
        _assert_bitwise(got["token"], want_token)
        lanes = outputs[sink].tensors
        _assert_bitwise(lanes[:3], (want_token, active, np.zeros_like(active)))
        np.testing.assert_array_equal(got["active"], active)
        np.testing.assert_array_equal(got["remaining"], 100 - active)
        cache, token = got["cache"], got["token"]


def _run_hop():
    """Both stages of a two-stage chain, stage 0's acts fed to stage 1."""
    stages = []
    for k, ps in enumerate(ms.staged_serve_pipelines(
            model=STAGED, slots=SLOTS, max_seq=MAX_SEQ, n_stages=2)):
        ps.realize()
        elem = ps.elements["lm"]
        params = ps.init(jax.random.PRNGKey(0))
        state = ps.init_state()
        cache = _random_cache(elem, jax.random.PRNGKey(20 + k))
        stages.append([ps, elem, params, {**state, elem.name: {
            "cache": cache}}, cache])

    @functools.partial(jax.jit, static_argnums=0)
    def reference(elem, p, cache, x, active):
        cfg, stage, n = elem.cfg, elem.stage, elem.n_stages

        def step(c, xx, act):
            out, new_c = transformer.stage_decode(p, cfg, stage, n, xx[None],
                                                  c)
            if stage == n - 1:
                y = jnp.argmax(out[0], axis=-1).astype(jnp.int32)
                return new_c, jnp.where(act, y, 0)
            return new_c, jnp.where(act, out[0], jnp.zeros_like(out[0]))
        return _table_where(step, cache, x, active)

    vocab = stages[0][1].cfg.vocab
    for i, active in enumerate(_masks(12)):
        x = jax.random.randint(jax.random.PRNGKey(30 + i), (SLOTS,), 0,
                               vocab).astype(jnp.int32)
        want_x = x
        for entry in stages:
            ps, elem, params, state, want_cache = entry
            src, sink = ps.plan.query_sources[0].name, \
                ps.plan.query_sinks[0].name
            tick = ps.plan.compiled_serve_tick(state)
            outputs, state = tick(params, state, {src: StreamBuffer(
                tensors=(x, active))})
            want_cache, want_x = reference(elem, params[elem.name],
                                           want_cache, want_x, active)
            _assert_bitwise(state[elem.name]["cache"], want_cache)
            (x,) = outputs[sink].tensors
            _assert_bitwise(x, want_x)
            entry[3], entry[4] = state, want_cache


@pytest.mark.parametrize("case", [
    "stablelm-smoke-flash", "recurrentgemma-smoke", "deepseek-v2-smoke",
    "stablelm-kv-quant", "mamba2-smoke", "two-stage-hop"])
def test_row_commit_is_the_table_where_bit_for_bit(case, built_presets):
    if case == "two-stage-hop":
        _run_hop()
    else:
        _run_lm(case)


def test_commit_bytes_is_one_row_per_attention_leaf_per_slot():
    """stablelm-smoke-flash: every layer is attention, so a tick commits
    one position row of k and v per layer per slot, plus ``pos``; the
    counter rides on each ``lm.decode`` span and sums into the runtime's
    stats."""
    from repro.core import tracing
    _, elem = _serve("stablelm-smoke-flash")
    cfg = elem.cfg
    row = cfg.n_kv_heads * cfg.resolved_head_dim * \
        jnp.dtype(cfg.dtype).itemsize
    want = SLOTS * (cfg.n_layers * 2 * row + 4)
    assert elem.commit_bytes == want

    rt = Runtime(query_batch=8)
    hub = Device("hub")
    hub.add_pipeline(ms.serve_pipeline(model="stablelm-smoke-flash",
                                       slots=SLOTS, max_seq=MAX_SEQ),
                     jit=False)
    rt.add_device(hub)
    for i in range(3):
        dev = Device(f"tv{i}")
        dev.add_pipeline(ms.client_pipeline(prompts=f"{i + 1},{i + 2}",
                                            gens="5"), jit=False)
        rt.add_device(dev)
    tracing.enable()
    try:
        rt.run(6)
        spans = tracing.drain().spans
    finally:
        tracing.enable()
        tracing.disable()
    decodes = [s for s in spans if s.name == "lm.decode"]
    assert decodes
    assert {d.attrs["commit_bytes"] for d in decodes} == {want}
    qb = rt.stats()["query_batching"]
    assert qb["commit_bytes"] == qb["decode_ticks"] * want == \
        len(decodes) * want
