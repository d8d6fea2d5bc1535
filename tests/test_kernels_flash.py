"""Pallas flash-attention kernel vs jnp oracle (shape sweeps, causal), plus
the SERVE-PATH trust anchors (DESIGN.md §7): flash prefill and cached-KV
decode-step attention vs the full-softmax references in kernels/ref.py —
the fp32 tolerance pin that must hold before the kernel sits under
model-serving traffic."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attn import flash_attention
from repro.kernels.ref import attn_decode_ref, attn_ref


def oracle(q, k, v, causal=True):
    s = jnp.einsum("bsd,btd->bst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * q.shape[-1] ** -0.5
    if causal:
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s = jnp.where(mask[None], s, -1e30)
    w = jax.nn.softmax(s, -1)
    return jnp.einsum("bst,btd->bsd", w, v.astype(jnp.float32)).astype(q.dtype)


@given(st.sampled_from([64, 128, 192, 256]), st.sampled_from([16, 32, 64]),
       st.sampled_from([16, 64]), st.sampled_from([32, 64]),
       st.integers(0, 2 ** 30))
@settings(max_examples=10, deadline=None)
def test_flash_matches_oracle(s, dk, dv, bq, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (2, s, dk))
    k = jax.random.normal(ks[1], (2, s, dk))
    v = jax.random.normal(ks[2], (2, s, dv))
    o = flash_attention(q, k, v, causal=True, bq=bq, bk=64)
    np.testing.assert_allclose(np.asarray(o), np.asarray(oracle(q, k, v)),
                               atol=2e-5, rtol=2e-5)


def test_non_causal():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 128, 32))
    k = jax.random.normal(ks[1], (1, 128, 32))
    v = jax.random.normal(ks[2], (1, 128, 32))
    o = flash_attention(q, k, v, causal=False, bq=64, bk=64)
    np.testing.assert_allclose(np.asarray(o),
                               np.asarray(oracle(q, k, v, causal=False)),
                               atol=2e-5, rtol=2e-5)


def test_first_token_attends_only_itself():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 64, 16))
    k = jax.random.normal(ks[1], (1, 64, 16))
    v = jax.random.normal(ks[2], (1, 64, 16))
    o = flash_attention(q, k, v, causal=True, bq=64, bk=64)
    np.testing.assert_allclose(np.asarray(o[0, 0]), np.asarray(v[0, 0]),
                               atol=1e-5)


class TestServePathPrefill:
    """flash_attention vs the kernels/ref.py full-softmax oracle — the
    PREFILL half of the serve path, including the GQA head grouping the
    model presets use."""

    @given(st.sampled_from([64, 128, 256]), st.sampled_from([1, 2, 4]),
           st.integers(0, 2 ** 30))
    @settings(max_examples=10, deadline=None)
    def test_matches_attn_ref_gqa(self, s, groups, seed):
        bh, dk, dv = 4, 32, 32
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (bh, s, dk))
        k = jax.random.normal(ks[1], (bh // groups, s, dk))
        v = jax.random.normal(ks[2], (bh // groups, s, dv))
        o = flash_attention(q, k, v, causal=True, bq=64, bk=64,
                            kv_groups=groups)
        ref = attn_ref(q, k, v, causal=True, kv_groups=groups)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_remainder_block_is_refused(self):
        """A length that is not a block multiple would leave its tail rows
        unwritten (NaN); the kernel refuses it instead."""
        q = jnp.zeros((2, 600, 16))
        with pytest.raises(ValueError, match="pad upstream"):
            flash_attention(q, q, q, causal=True, bq=512, bk=512)

    def test_unaligned_600_token_prefill(self):
        """A 600-token prompt through the served prefill (``attn_train``'s
        flash gate pads to the block and slices back) matches the
        full-softmax oracle on the same q/k/v and the ``_sdpa`` path on
        every row, the rows past 512 included."""
        import dataclasses
        from repro.configs import stablelm_1_6b
        from repro.models import layers as L
        s = 600
        cfg = stablelm_1_6b.config().smoke()
        p = L.attn_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, s, cfg.d_model))
        flash = L.attn_train(p, dataclasses.replace(cfg, use_flash_attn=True),
                             x, None)
        sdpa = L.attn_train(p, cfg, x, None)
        assert bool(jnp.all(jnp.isfinite(flash)))
        np.testing.assert_allclose(np.asarray(flash), np.asarray(sdpa),
                                   atol=2e-5, rtol=2e-5)
        pos = jnp.arange(s, dtype=jnp.int32)[None]
        q, k, v = L._qkv(p, cfg, x, pos)
        h, hd = cfg.n_heads, cfg.resolved_head_dim
        flat = lambda t: t.transpose(0, 2, 1, 3).reshape(-1, s, hd)  # noqa
        ref = attn_ref(flat(q), flat(k), flat(v), causal=True,
                       kv_groups=h // cfg.n_kv_heads)
        ref_y = ref.reshape(1, h, s, hd).transpose(0, 2, 1, 3).reshape(
            1, s, h * hd) @ p["wo"]
        np.testing.assert_allclose(np.asarray(flash), np.asarray(ref_y),
                                   atol=2e-5, rtol=2e-5)


def _serve_decode(q, k, v, pos, groups=1):
    """The serve path's decode attention as ``attn_decode`` runs it (the
    one-piece ``_sdpa`` over the cache in its [B, S_cache, KV, hd] layout,
    masked to the valid rows, float32 logits), on ``attn_decode_ref``'s
    flat layout: q [H, d], k/v [H // groups, Sk, d] -> [H, d]."""
    from repro.models import layers as L
    sk = k.shape[1]
    valid = jnp.arange(sk, dtype=jnp.int32) <= pos
    mask = jnp.broadcast_to(valid[None, None, :], (1, 1, sk))
    out = L._sdpa(q[None, None], k.transpose(1, 0, 2)[None],
                  v.transpose(1, 0, 2)[None], mask, None, q.shape[0],
                  k.shape[0], f32_logits=True)
    return out[0, 0]


class TestServePathDecodeStep:
    """The served decode attention vs attn_decode_ref — the cached-KV
    DECODE half: one query row against a partially filled cache, swept
    over fill levels, cache lengths and GQA groups."""

    @given(st.sampled_from([8, 31, 63, 64, 100, 127]),
           st.sampled_from([128, 160, 256]), st.sampled_from([1, 2, 4]),
           st.integers(0, 2 ** 30))
    @settings(max_examples=12, deadline=None)
    def test_matches_decode_ref(self, pos, sk, groups, seed):
        bh, d = 4, 32
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (bh, d))
        k = jax.random.normal(ks[1], (bh // groups, sk, d))
        v = jax.random.normal(ks[2], (bh // groups, sk, d))
        o = _serve_decode(q, k, v, jnp.int32(pos), groups)
        ref = attn_decode_ref(q, k, v, pos, kv_groups=groups)
        assert o.shape == (bh, d)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @given(st.integers(0, 62), st.integers(0, 2 ** 30))
    @settings(max_examples=10, deadline=None)
    def test_cache_beyond_pos_has_no_influence(self, pos, seed):
        """The mask property the ring cache depends on: garbage (or stale
        epoch data) in cache rows past ``pos`` must not move the output by
        one ulp."""
        bh, sk, dk = 2, 64, 16
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        q = jax.random.normal(ks[0], (bh, dk))
        k = jax.random.normal(ks[1], (bh, sk, dk))
        v = jax.random.normal(ks[2], (bh, sk, dk))
        o1 = _serve_decode(q, k, v, jnp.int32(pos))
        noise = 100.0 * jax.random.normal(ks[3], (bh, sk, dk))
        tail = (jnp.arange(sk) > pos)[None, :, None]
        k2 = jnp.where(tail, k + noise, k)
        v2 = jnp.where(tail, v + noise, v)
        o2 = _serve_decode(q, k2, v2, jnp.int32(pos))
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))

    def test_pos_zero_attends_only_first_row(self):
        bh, sk, dk = 2, 64, 16
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (bh, dk))
        k = jax.random.normal(ks[1], (bh, sk, dk))
        v = jax.random.normal(ks[2], (bh, sk, dk))
        o = _serve_decode(q, k, v, jnp.int32(0))
        np.testing.assert_allclose(np.asarray(o), np.asarray(v[:, 0]),
                                   atol=1e-5)

    def test_decode_step_agrees_with_prefill_last_row(self):
        """Cross-path consistency on a flash preset: ``attn_decode`` at
        position ``s - 1`` against a cache holding the first ``s - 1``
        keys (garbage beyond) equals the last row of ``attn_train``'s
        flash prefill over the same sequence — the handoff the serve path
        makes at admission."""
        import dataclasses
        from repro.configs import stablelm_1_6b
        from repro.models import layers as L
        s, size = 64, 96
        cfg = dataclasses.replace(stablelm_1_6b.config().smoke(),
                                  use_flash_attn=True)
        p = L.attn_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, s, cfg.d_model))
        pre = L.attn_train(p, cfg, x, None)
        pos = jnp.arange(s, dtype=jnp.int32)[None]
        _, k, v = L._qkv(p, cfg, x, pos)
        junk = jax.random.normal(jax.random.PRNGKey(2),
                                 (1, size) + k.shape[2:])
        cache = {"k": junk.at[:, :s - 1].set(k[:, :s - 1]),
                 "v": junk.at[:, :s - 1].set(v[:, :s - 1])}
        step, new = L.attn_decode(p, cfg, x[:, -1:], cache,
                                  jnp.int32(s - 1), None)
        np.testing.assert_array_equal(np.asarray(new["k"][:, s - 1]),
                                      np.asarray(k[:, s - 1]))
        np.testing.assert_allclose(np.asarray(pre[:, -1]),
                                   np.asarray(step[:, 0]),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("join", [False, True])
    def test_serve_tick_holds_no_loop(self, join):
        """The decode attention of a flash preset's compiled serve tick is
        straight-line code: no ``while`` in the lowered program, with or
        without a join bundle (a blockwise scan there ran as one device
        loop per layer)."""
        from repro.launch import model_serve as ms
        ps = ms.serve_pipeline(model="stablelm-smoke-flash", slots=4,
                               max_seq=64)
        ps.realize()
        elem = ps.elements["lm"]
        params = jax.eval_shape(ps.init, jax.random.PRNGKey(0))
        state = jax.eval_shape(ps.init_state)
        joiner = (0, 1, 3, jax.eval_shape(elem._cache_template))
        bundle = elem.build_admit([joiner]) if join else elem.empty_admit()
        src = ps.plan.query_sources[0].name
        tick = ps.plan.compiled_serve_tick(state)
        text = tick.lower(params, state, {src: bundle}).as_text()
        assert "while" not in text
