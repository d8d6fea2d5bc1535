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

from repro.kernels.flash_attn import flash_attention, flash_decode_step
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


class TestServePathDecodeStep:
    """flash_decode_step vs attn_decode_ref — the cached-KV DECODE half:
    one query row against a partially filled cache, swept over fill levels,
    block sizes and GQA groups."""

    @given(st.sampled_from([8, 31, 63, 64, 100, 127]),
           st.sampled_from([32, 128]), st.sampled_from([1, 2, 4]),
           st.integers(0, 2 ** 30))
    @settings(max_examples=12, deadline=None)
    def test_matches_decode_ref(self, pos, bk, groups, seed):
        bh, sk, dk, dv = 4, 128, 32, 48
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (bh, dk))
        k = jax.random.normal(ks[1], (bh // groups, sk, dk))
        v = jax.random.normal(ks[2], (bh // groups, sk, dv))
        o = flash_decode_step(q, k, v, jnp.int32(pos), bk=bk,
                              kv_groups=groups)
        ref = attn_decode_ref(q, k, v, pos, kv_groups=groups)
        assert o.shape == (bh, dv)
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
        o1 = flash_decode_step(q, k, v, jnp.int32(pos), bk=32)
        noise = 100.0 * jax.random.normal(ks[3], (bh, sk, dk))
        tail = (jnp.arange(sk) > pos)[None, :, None]
        k2 = jnp.where(tail, k + noise, k)
        v2 = jnp.where(tail, v + noise, v)
        o2 = flash_decode_step(q, k2, v2, jnp.int32(pos), bk=32)
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))

    def test_pos_zero_attends_only_first_row(self):
        bh, sk, dk = 2, 64, 16
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (bh, dk))
        k = jax.random.normal(ks[1], (bh, sk, dk))
        v = jax.random.normal(ks[2], (bh, sk, dk))
        o = flash_decode_step(q, k, v, jnp.int32(0), bk=32)
        np.testing.assert_allclose(np.asarray(o), np.asarray(v[:, 0]),
                                   atol=1e-5)

    def test_decode_step_agrees_with_prefill_last_row(self):
        """Cross-kernel consistency: decoding position ``pos`` against the
        cache equals the last row of a causal prefill over the same
        sequence — the handoff the serve path makes at admission."""
        bh, s, d = 4, 64, 32
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (bh, s, d))
        k = jax.random.normal(ks[1], (bh, s, d))
        v = jax.random.normal(ks[2], (bh, s, d))
        pre = flash_attention(q, k, v, causal=True, bq=64, bk=64)
        step = flash_decode_step(q[:, -1], k, v, jnp.int32(s - 1), bk=64)
        np.testing.assert_allclose(np.asarray(pre[:, -1]), np.asarray(step),
                                   atol=2e-5, rtol=2e-5)
