"""The staged hop chain against pp_serve's shard_map step (DESIGN.md §8).

Same layer split, two transports: the among-device stage hops of
``model_serve_stage`` and the intra-process pipeline-parallel decode of
``launch/pp_serve.py`` (``shard_map`` manual over the ``pod`` axis of a
(2, 2, 2) mesh).  Lives here because it needs the 8 host devices this
directory's conftest forges.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = [pytest.mark.multidevice, pytest.mark.ppstage]


@pytest.mark.slow
def test_staged_hops_match_shard_map_pp_step():
    """Same split, two transports: one decode step through the staged
    stage_prefill/stage_decode hop chain computes the tokens pp_serve's
    shard_map ppermute step does on the same params (the intra-process
    pipeline-parallel reference, pod axis = stage axis)."""
    from repro.launch.mesh import make_mesh
    from repro.launch.pp_serve import make_pp_serve_step, pp_applicable
    from repro.models import ModelConfig, build_model
    from repro.models import transformer as T
    cfg = ModelConfig(name="t", arch_type="dense", n_layers=4, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab=97,
                      dtype="float32")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    sp = m.stack_params(params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 12), 0, 97)
    lp, cache = m.prefill_stacked(sp, {"tokens": toks}, max_seq=20)
    nxt = jnp.argmax(lp, -1)
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    assert pp_applicable(m, mesh)
    with jax.set_mesh(mesh):
        tok_pp, _ = jax.jit(make_pp_serve_step(m, mesh))(sp, nxt, cache)
    # the among-device split of the same step: per-stage prefill chain on
    # the prompt, then one boundary-activation decode hop through both
    # stages
    n_stages = 2
    stage_p = [T.stage_params(params, cfg, k, n_stages)
               for k in range(n_stages)]
    x, caches = toks, []
    for k in range(n_stages):
        x, c = T.stage_prefill(stage_p[k], cfg, k, n_stages, x, 20)
        caches.append(c)
    assert np.array_equal(np.asarray(jnp.argmax(x, -1)),
                          np.asarray(nxt))
    y = nxt.astype(jnp.int32)
    for k in range(n_stages):
        y, caches[k] = T.stage_decode(stage_p[k], cfg, k, n_stages, y,
                                      caches[k])
    np.testing.assert_array_equal(np.asarray(jnp.argmax(y, -1)),
                                  np.asarray(tok_pp))
