"""A join writes each joiner's prefilled cache into its slot row on the
device (``ModelServeElement.write_rows``), and the serve tick merges only
the admit lanes (DESIGN.md §7).  The staged chain's stages (DESIGN.md §8)
write their parked caches the same way.

* the slot table after the row writes is, bit for bit, the table the
  masked ``where`` merge of a full admit table gives, for the KV-cache
  preset, the rGLRU preset and both stages of a two-stage chain, and the
  other slots' rows are untouched;
* one compiled row write serves every joiner count;
* a host-resident (numpy) cache is written like a device one;
* a join tick's serve program takes the admit lanes only, and a stage's
  join hop takes ``(x_in, active)`` only: no bundle carries a cache.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.modelserve import ModelServeStageElement
from repro.core.plan import ExecutionPlan
from repro.launch import model_serve as ms
from repro.runtime import Device, Runtime

SLOTS = 4
MODELS = ["stablelm-smoke-flash", "recurrentgemma-smoke"]
STAGED = "stablelm-smoke-4l"
STAGES = [f"{STAGED}-stage{k}" for k in range(2)]


def _elem(model):
    if model in STAGES:
        ps = ms.stage_pipeline(model=STAGED, slots=SLOTS, max_seq=32,
                               stage=STAGES.index(model), n_stages=2)
    else:
        ps = ms.serve_pipeline(model=model, slots=SLOTS, max_seq=32)
    ps.realize()
    return ps, ps.elements["lm"]


def _fill(key, tree):
    """Random values of each leaf's shape and dtype, so every row differs."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = []
    for i, leaf in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            v = jax.random.normal(k, leaf.shape, jnp.float32)
        else:
            v = jax.random.randint(k, leaf.shape, 0, 1000)
        out.append(v.astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _joiners(elem, slots, seed):
    """``(slot, b1_cache)`` rows, as ``write_rows`` takes them."""
    template = elem._cache_template()
    return [(slot, _fill(jax.random.PRNGKey(seed + slot), template))
            for slot in slots]


def _where_merge(table, joiners, slots):
    """The merge a join tick ran before the row write: a zero admit table
    with each joiner's cache in its row, selected under the admit mask."""
    mask = np.zeros((slots,), np.bool_)
    rows = [np.zeros(np.shape(l), np.dtype(str(l.dtype)))
            for l in jax.tree_util.tree_leaves(table)]
    for slot, cache in joiners:
        mask[slot] = True
        for dst, src in zip(rows, jax.tree_util.tree_leaves(cache)):
            dst[slot] = np.asarray(src)
    leaves, treedef = jax.tree_util.tree_flatten(table)
    merged = [jnp.where(mask.reshape((-1,) + (1,) * (old.ndim - 1)), new,
                        old) for old, new in zip(leaves, rows)]
    return jax.tree_util.tree_unflatten(treedef, merged)


def _bits(tree):
    return [np.asarray(l).tobytes() for l in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("model", MODELS + STAGES)
def test_row_write_is_the_where_merge_bit_for_bit(model):
    _, elem = _elem(model)
    state = elem.init_state()
    state = {**state, "cache": _fill(jax.random.PRNGKey(1), state["cache"])}
    joiners = _joiners(elem, [0, 2], seed=10)
    want = _where_merge(state["cache"], joiners, SLOTS)
    got = elem.write_rows(state, joiners)
    assert _bits(got["cache"]) == _bits(want)


@pytest.mark.parametrize("model", MODELS)
def test_row_write_leaves_other_slots_and_the_lanes_alone(model):
    _, elem = _elem(model)
    state = elem.init_state()
    state = {**state, "cache": _fill(jax.random.PRNGKey(2), state["cache"])}
    before = jax.tree_util.tree_map(np.asarray, state)
    got = elem.write_rows(state, _joiners(elem, [1], seed=20))
    for key in ("active", "token", "remaining"):
        assert got[key] is state[key]
    for old, new in zip(jax.tree_util.tree_leaves(before["cache"]),
                        jax.tree_util.tree_leaves(got["cache"])):
        new = np.asarray(new)
        for slot in (0, 2, 3):
            assert new[slot].tobytes() == old[slot].tobytes()
        assert new[1].tobytes() != old[1].tobytes()


def test_one_row_write_serves_every_joiner_count():
    _, elem = _elem("stablelm-smoke-flash")
    state = elem.init_state()
    for n in (1, 3, SLOTS):
        state = elem.write_rows(state, _joiners(elem, range(n), seed=n))
    assert elem.row_writer()._cache_size() == 1


def test_host_resident_cache_is_written_like_a_device_one():
    _, elem = _elem("recurrentgemma-smoke")
    state = elem.init_state()
    joiners = _joiners(elem, [3], seed=30)
    host = [(slot, jax.tree_util.tree_map(np.asarray, cache))
            for slot, cache in joiners]
    assert _bits(elem.write_rows(state, host)["cache"]) == \
        _bits(elem.write_rows(state, joiners)["cache"])


def test_join_tick_takes_the_admit_lanes_only():
    ps, elem = _elem("stablelm-smoke-flash")
    params = jax.eval_shape(ps.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(ps.init_state)
    bundle = elem.build_admit(
        [(1, 5, 4, jax.eval_shape(elem._cache_template))])
    assert not bundle.meta.get("empty")
    assert [np.shape(t) for t in bundle.tensors] == [(SLOTS,)] * 3
    src = ps.plan.query_sources[0].name
    lowered = ps.plan.compiled_serve_tick(state).lower(params, state,
                                                       {src: bundle})
    (_, _, inputs), _ = lowered.args_info
    lanes = jax.tree_util.tree_leaves(inputs[src].tensors)
    assert [(a.shape, a.dtype) for a in lanes] == [
        ((SLOTS,), jnp.bool_), ((SLOTS,), jnp.int32), ((SLOTS,), jnp.int32)]
    # and the frame's timestamp: nothing of the slot table's width
    assert len(jax.tree_util.tree_leaves(inputs)) == len(lanes) + 1


def test_stage_join_hop_takes_x_in_and_active_only(monkeypatch):
    """Both stages of a two-stage chain, driven by the runtime: a hop on
    which streams join carries ``(x_in, active)`` and nothing of the slot
    table's width — the joiners' parked caches went into their rows first —
    and join hops and steady hops run one compiled hop program."""
    hops = {0: [], 1: []}   # stage -> [(rows written before it, lowered)]
    joined = {}             # stage -> rows written since its last hop
    ticks = {}              # stage -> the hop program
    write_rows = ModelServeStageElement.write_rows

    def spy_write(elem, state, rows):
        joined[elem.stage] = len(rows)
        return write_rows(elem, state, rows)

    serve_tick = ExecutionPlan.compiled_serve_tick

    def spy_tick(plan, state, donate=None):
        fn = serve_tick(plan, state, donate)
        (elem,) = [op.elem for op in plan.ops
                   if getattr(op.elem, "is_stage_serve", False)]
        assert ticks.setdefault(elem.stage, fn) is fn

        def call(params, st, inputs):
            hops[elem.stage].append((joined.pop(elem.stage, 0),
                                     fn.lower(params, st, inputs)))
            return fn(params, st, inputs)
        return call

    monkeypatch.setattr(ModelServeStageElement, "write_rows", spy_write)
    monkeypatch.setattr(ExecutionPlan, "compiled_serve_tick", spy_tick)
    rt = Runtime(query_batch=8)
    for k, ps in enumerate(ms.staged_serve_pipelines(
            model=STAGED, slots=SLOTS, max_seq=32, n_stages=2)):
        dev = Device(f"stage{k}")
        dev.add_pipeline(ps, jit=False)
        rt.add_device(dev)
    for i in range(2):
        dev = Device(f"tv{i}")
        dev.add_pipeline(ms.client_pipeline(prompts=f"{i + 1},{i + 2}",
                                            gens="4"), jit=False)
        rt.add_device(dev)
    rt.run(6)

    for stage, mine in hops.items():
        joins = [lowered for n, lowered in mine if n]
        assert joins and len(joins) < len(mine)      # join and steady hops
        for lowered in joins:
            (_, _, inputs), _ = lowered.args_info
            (bundle,) = inputs.values()
            x_in, active = bundle.tensors
            assert x_in.shape[0] == SLOTS
            assert (active.shape, active.dtype) == ((SLOTS,), jnp.bool_)
            # and the frame's timestamp: nothing of the slot table's width
            assert len(jax.tree_util.tree_leaves(inputs)) == 3
        assert ticks[stage]._cache_size() == 1
