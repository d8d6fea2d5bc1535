"""Mamba-2 SSD block (state-space duality, arXiv:2405.21060).

Chunked SSD: the sequence is split into chunks of length Q; within a chunk
the *dual* (attention-like) quadratic form runs on the MXU, between chunks a
linear state recurrence runs via lax.scan (or one-step update at decode).

    h_t = exp(A·dt_t) h_{t-1} + dt_t · B_t ⊗ x_t         (state  [H, hd, N])
    y_t = C_t · h_t + D ⊙ x_t

Decode state is O(H·hd·N) — constant in sequence length, which is why the
SSM archs run the long_500k shape.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import commit_state, dense_init
from .sharding import shard


def _dims(cfg: ModelConfig):
    d_inner = cfg.ssm_expand * cfg.d_model
    n_heads = d_inner // cfg.ssm_head_dim
    return d_inner, n_heads, cfg.ssm_head_dim, cfg.ssm_state


def ssm_init(rng, cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    d_inner, h, hd, n = _dims(cfg)
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 4)
    # in_proj packs [z (gate), x, B, C, dt] as in mamba2
    d_in_proj = 2 * d_inner + 2 * n + h
    return {
        "w_in": dense_init(ks[0], d, d_in_proj, dt),
        "conv": (jax.random.normal(ks[1], (cfg.ssm_conv, d_inner + 2 * n))
                 * 0.1).astype(dt),
        "A_log": jnp.zeros((h,), jnp.float32),         # A = -exp(A_log) in (-1, 0)
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "w_out": dense_init(ks[2], d_inner, d, dt),
    }


def _split_proj(cfg: ModelConfig, proj: jnp.ndarray):
    d_inner, h, hd, n = _dims(cfg)
    z, xbc, dt_raw = jnp.split(proj, [d_inner, 2 * d_inner + 2 * n], axis=-1)
    return z, xbc, dt_raw


def _causal_conv(xbc: jnp.ndarray, w: jnp.ndarray, prev: jnp.ndarray = None):
    """Depthwise causal conv1d, window K.  xbc: [B,S,C]; w: [K,C];
    prev: [B,K-1,C] carried state for decode."""
    k = w.shape[0]
    if prev is None:
        pad = jnp.zeros((xbc.shape[0], k - 1, xbc.shape[2]), xbc.dtype)
    else:
        pad = prev
    xp = jnp.concatenate([pad, xbc], axis=1)                    # [B,S+K-1,C]
    out = sum(xp[:, i:i + xbc.shape[1]] * w[i] for i in range(k))
    return jax.nn.silu(out), xp[:, -(k - 1):]                   # new conv state


def _ssd_scan(cfg: ModelConfig, p: Dict, xh, B, C, dt, h0):
    """Chunked SSD scan.
    xh: [B,S,H,hd]; B,C: [B,S,N]; dt: [B,S,H] (softplus'd).
    Returns y [B,S,H,hd] (incl. D skip), final state [B,H,N,hd]."""
    b, s, h, hd = xh.shape
    n = B.shape[-1]
    q = min(cfg.ssm_chunk, s)
    pad = (-s) % q
    if pad:  # zero-dt padding: decay=1, update=0 -> state and outputs exact
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    s_orig, s = s, s + pad
    nc = s // q
    A = -jnp.exp(p["A_log"])                                    # [H], negative
    dA = dt * A[None, None, :]                                  # [B,S,H]
    dA_c = dA.reshape(b, nc, q, h)
    xh_c = xh.reshape(b, nc, q, h, hd)
    B_c = B.reshape(b, nc, q, n)
    C_c = C.reshape(b, nc, q, n)
    dt_c = dt.reshape(b, nc, q, h)

    cum = jnp.cumsum(dA_c, axis=2)                              # [B,nc,q,H]
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]          # [B,nc,q,q,H] i>=j
    causal = jnp.tril(jnp.ones((q, q), bool))
    L = jnp.where(causal[None, None, :, :, None], jnp.exp(seg), 0.0)

    # intra-chunk (dual quadratic form): y_intra[i] = Σ_j L[i,j] (C_i·B_j) dt_j x_j
    G = jnp.einsum("bcin,bcjn->bcij", C_c.astype(jnp.float32),
                   B_c.astype(jnp.float32))                     # [B,nc,q,q]
    M = G[..., None] * L                                        # [B,nc,q,q,H]
    y_intra = jnp.einsum("bcijh,bcjh,bcjhd->bcihd", M, dt_c,
                         xh_c.astype(jnp.float32))

    # chunk-final states: S_c = Σ_j exp(cum_last - cum_j) dt_j B_j ⊗ x_j
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)             # [B,nc,q,H]
    S_c = jnp.einsum("bcjh,bcjh,bcjn,bcjhd->bchnd",
                     decay_to_end, dt_c, B_c.astype(jnp.float32),
                     xh_c.astype(jnp.float32))                  # [B,nc,H,N,hd]

    # inter-chunk recurrence over chunk states
    chunk_decay = jnp.exp(jnp.sum(dA_c, axis=2))                # [B,nc,H]
    if h0 is None:
        h0 = jnp.zeros((b, h, n, hd), jnp.float32)

    def scan_fn(hprev, inp):
        dec, s_new = inp                                        # [B,H], [B,H,N,hd]
        hnext = hprev * dec[:, :, None, None] + s_new
        return hnext, hprev

    dec_t = jnp.moveaxis(chunk_decay, 1, 0)                     # [nc,B,H]
    s_t = jnp.moveaxis(S_c, 1, 0)                               # [nc,B,H,N,hd]
    h_final, h_starts = jax.lax.scan(scan_fn, h0, (dec_t, s_t))
    h_starts = jnp.moveaxis(h_starts, 0, 1)                     # [B,nc,H,N,hd]

    # inter-chunk contribution: y_inter[i] = C_i · (decay_to_i * h_start)
    decay_from_start = jnp.exp(cum)                             # [B,nc,q,H]
    y_inter = jnp.einsum("bcin,bcih,bchnd->bcihd",
                         C_c.astype(jnp.float32), decay_from_start, h_starts)

    y = (y_intra + y_inter).reshape(b, s, h, hd)
    y = y + p["D"][None, None, :, None] * xh.astype(jnp.float32)
    # running log-decay from sequence start (for cross-shard correction):
    # per-chunk cum + exclusive chunk-offset
    chunk_sum = jnp.sum(dA_c, axis=2)                           # [B,nc,H]
    offs = jnp.cumsum(chunk_sum, axis=1) - chunk_sum            # exclusive
    cum_total = (cum + offs[:, :, None, :]).reshape(b, s, h)
    return y[:, :s_orig], h_final, cum_total[:, :s_orig]


def ssm_train(p: Dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.ssm_seq_parallel:
        from .sharding import current_rules
        mesh = current_rules().get("__mesh__")
        if mesh is not None and "model" in getattr(mesh, "axis_names", ()) \
                and x.shape[1] % mesh.shape["model"] == 0:
            return ssm_train_seq_parallel(p, cfg, x, mesh)
    b, s, d = x.shape
    d_inner, h, hd, n = _dims(cfg)
    proj = x @ p["w_in"]
    z, xbc, dt_raw = _split_proj(cfg, proj)
    xbc, _ = _causal_conv(xbc, p["conv"])
    xs, B, C = jnp.split(xbc, [d_inner, d_inner + n], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # [B,S,H]
    xh = xs.reshape(b, s, h, hd)
    xh = shard(xh, "batch", "seq", "heads", None)
    y, _, _ = _ssd_scan(cfg, p, xh, B, C, dt, None)
    y = y.astype(x.dtype).reshape(b, s, d_inner)
    y = y * jax.nn.silu(z)
    out = y @ p["w_out"]
    return shard(out, "batch", "seq", None)


def ssm_cache_init(cfg: ModelConfig, batch: int) -> Dict:
    d_inner, h, hd, n = _dims(cfg)
    return {
        "h": jnp.zeros((batch, h, n, hd), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, d_inner + 2 * n),
                          jnp.dtype(cfg.dtype)),
    }


def ssm_decode(p: Dict, cfg: ModelConfig, x: jnp.ndarray, cache: Dict,
               commit=None) -> Tuple[jnp.ndarray, Dict]:
    """x: [B,1,d]; O(1) state update, kept old where the traced bool
    ``commit`` does not hold (``layers.commit_state``)."""
    b = x.shape[0]
    d_inner, h, hd, n = _dims(cfg)
    proj = x @ p["w_in"]
    z, xbc, dt_raw = _split_proj(cfg, proj)
    xbc, conv_state = _causal_conv(xbc, p["conv"], prev=cache["conv"])
    xs, B, C = jnp.split(xbc, [d_inner, d_inner + n], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])[:, 0]  # [B,H]
    xh = xs.reshape(b, h, hd).astype(jnp.float32)
    A = -jnp.exp(p["A_log"])
    dec = jnp.exp(dt * A[None, :])                              # [B,H]
    hs = shard(cache["h"], "batch", "heads", None, None)
    upd = jnp.einsum("bh,bn,bhd->bhnd", dt, B[:, 0].astype(jnp.float32), xh)
    hnew = hs * dec[:, :, None, None] + upd
    y = jnp.einsum("bn,bhnd->bhd", C[:, 0].astype(jnp.float32), hnew)
    y = y + p["D"][None, :, None] * xh
    y = (y.reshape(b, 1, d_inner).astype(x.dtype)) * jax.nn.silu(z)
    out = y @ p["w_out"]
    return shard(out, "batch", None, None), commit_state(
        {"h": hnew, "conv": conv_state}, cache, commit)


# ---------------------------------------------------------------------------
# Sequence-parallel SSD (§Perf beyond-paper optimization)
# ---------------------------------------------------------------------------
#
# mamba2-130m's channel dims (24 heads of 64) don't divide a 16-way model
# axis, so tensor parallelism either emits halo collective-permutes every
# layer (misaligned channel shards — the collective-bound baseline) or
# degenerates to replication (16× redundant compute).  The dimension that
# IS huge is the sequence (32k–512k): shard it over `model`.
#
# SSD's inter-chunk recurrence is associative over (decay, state) pairs:
#   (D1, S1) ∘ (D2, S2) = (D1·D2, S1·D2 + S2)
# so cross-shard states combine with a log2(model)-depth ppermute scan —
# 4 rounds of a [B,H,N,hd] message (~1.5 MB) instead of per-layer halos.
# The conv1d needs a 3-frame halo from the left neighbour (one tiny
# ppermute), and each position's output gains the h0 correction
# y += C_t · exp(cum_dA_t) · h0.

def ssm_train_seq_parallel(p: Dict, cfg: ModelConfig, x: jnp.ndarray, mesh
                           ) -> jnp.ndarray:
    from jax.sharding import PartitionSpec as P
    b, s, d = x.shape
    d_inner, h, hd, n = _dims(cfg)
    m = mesh.shape["model"]
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    import numpy as np
    dsize = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    if b % max(dsize, 1):
        dp = ()
    bspec = dp if len(dp) > 1 else (dp[0] if dp else None)
    perm_fwd = [(i, i + 1) for i in range(m - 1)]

    def body(xl, w_in, conv, A_log, D, dt_bias, w_out):
        bl, sl, _ = xl.shape
        idx = jax.lax.axis_index("model")
        proj = xl @ w_in
        z, xbc, dt_raw = _split_proj(cfg, proj)
        # conv halo: last K-1 frames from the left neighbour (zeros at shard 0)
        k = conv.shape[0]
        tail = xbc[:, -(k - 1):]
        prev = jax.lax.ppermute(tail, "model", perm_fwd)
        lp = {"conv": conv, "A_log": A_log, "D": D, "dt_bias": dt_bias}
        xbc_c, _ = _causal_conv(xbc, conv, prev=prev)
        xs, B, C = jnp.split(xbc_c, [d_inner, d_inner + n], axis=-1)
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + dt_bias)
        xh = xs.reshape(bl, sl, h, hd)
        # scan carry must carry the body's varying manual axes
        h_init = jax.lax.pcast(jnp.zeros((bl, h, n, hd), jnp.float32),
                               tuple(mesh.axis_names), to="varying")
        y0, h_loc, cum = _ssd_scan(cfg, lp, xh, B, C, dt, h_init)

        # cross-shard inclusive scan of (decay_prod, state)
        d_loc = jnp.exp(jnp.sum(dt * (-jnp.exp(A_log))[None, None, :], axis=1))
        d_acc, s_acc = d_loc, h_loc                    # [B,H], [B,H,N,hd]
        shift = 1
        while shift < m:
            pairs = [(i, i + shift) for i in range(m - shift)]
            d_in = jax.lax.ppermute(d_acc, "model", pairs)
            s_in = jax.lax.ppermute(s_acc, "model", pairs)
            has_left = (idx >= shift).astype(jnp.float32)
            # combine(left=(d_in,s_in), right=(d_acc,s_acc));
            # shards with no left neighbour keep their values (d_in=0 there,
            # so gate with has_left)
            d_new = jnp.where(has_left > 0, d_in * d_acc, d_acc)
            s_new = s_in * d_acc[:, :, None, None] * has_left + s_acc
            d_acc, s_acc = d_new, s_new
            shift *= 2
        # exclusive prefix: previous shard's inclusive state (zeros at shard 0)
        h0 = jax.lax.ppermute(s_acc, "model", perm_fwd)  # [B,H,N,hd]

        # correction: y += C_t · exp(cum_t) · h0
        y_corr = jnp.einsum("bsn,bsh,bhnd->bshd",
                            C.astype(jnp.float32), jnp.exp(cum), h0)
        y = (y0 + y_corr).astype(xl.dtype).reshape(bl, sl, d_inner)
        y = y * jax.nn.silu(z)
        return y @ w_out

    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, "model", None), P(), P(), P(), P(), P(), P()),
        out_specs=P(bspec, "model", None),
    )(x, p["w_in"], p["conv"], p["A_log"], p["D"], p["dt_bias"], p["w_out"])
    return out


def ssm_prefill(p: Dict, cfg: ModelConfig, x: jnp.ndarray
                ) -> Tuple[jnp.ndarray, Dict]:
    """Single-pass prefill: y plus the decode cache (final SSD state + conv
    tail) from ONE SSD computation.  The two-pass alternative (ssm_train +
    a separate cache pass) doubles compute and, under sequence sharding,
    all-gathers every chunk state in the duplicate GSPMD scan (§Perf H3)."""
    if cfg.ssm_seq_parallel:
        from .sharding import current_rules
        mesh = current_rules().get("__mesh__")
        if mesh is not None and "model" in getattr(mesh, "axis_names", ()) \
                and x.shape[1] % mesh.shape["model"] == 0:
            return _ssm_prefill_seq_parallel(p, cfg, x, mesh)
    b, s, d = x.shape
    d_inner, h, hd, n = _dims(cfg)
    proj = x @ p["w_in"]
    z, xbc, dt_raw = _split_proj(cfg, proj)
    xbc_c, conv_state = _causal_conv(xbc, p["conv"])
    xs, B, C = jnp.split(xbc_c, [d_inner, d_inner + n], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    xh = xs.reshape(b, s, h, hd)
    y, h_final, _ = _ssd_scan(cfg, p, xh, B, C, dt, None)
    y = (y.astype(x.dtype).reshape(b, s, d_inner)) * jax.nn.silu(z)
    out = y @ p["w_out"]
    return shard(out, "batch", "seq", None), \
        {"h": h_final, "conv": conv_state}


def _ssm_prefill_seq_parallel(p: Dict, cfg: ModelConfig, x: jnp.ndarray, mesh
                              ) -> Tuple[jnp.ndarray, Dict]:
    from jax.sharding import PartitionSpec as P
    import numpy as np
    b, s, d = x.shape
    d_inner, h, hd, n = _dims(cfg)
    m = mesh.shape["model"]
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dsize = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    if b % max(dsize, 1):
        dp = ()
    bspec = dp if len(dp) > 1 else (dp[0] if dp else None)
    perm_fwd = [(i, i + 1) for i in range(m - 1)]

    def body(xl, w_in, conv, A_log, D, dt_bias, w_out):
        bl, sl, _ = xl.shape
        idx = jax.lax.axis_index("model")
        proj = xl @ w_in
        z, xbc, dt_raw = _split_proj(cfg, proj)
        k = conv.shape[0]
        tail = xbc[:, -(k - 1):]
        prev = jax.lax.ppermute(tail, "model", perm_fwd)
        lp = {"conv": conv, "A_log": A_log, "D": D, "dt_bias": dt_bias}
        xbc_c, conv_tail = _causal_conv(xbc, conv, prev=prev)
        xs, B, C = jnp.split(xbc_c, [d_inner, d_inner + n], axis=-1)
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + dt_bias)
        xh = xs.reshape(bl, sl, h, hd)
        h_init = jax.lax.pcast(jnp.zeros((bl, h, n, hd), jnp.float32),
                               tuple(mesh.axis_names), to="varying")
        y0, h_loc, cum = _ssd_scan(cfg, lp, xh, B, C, dt, h_init)

        d_loc = jnp.exp(jnp.sum(dt * (-jnp.exp(A_log))[None, None, :], axis=1))
        d_acc, s_acc = d_loc, h_loc
        shift = 1
        while shift < m:
            pairs = [(i, i + shift) for i in range(m - shift)]
            d_in = jax.lax.ppermute(d_acc, "model", pairs)
            s_in = jax.lax.ppermute(s_acc, "model", pairs)
            has_left = (idx >= shift).astype(jnp.float32)
            d_new = jnp.where(has_left > 0, d_in * d_acc, d_acc)
            s_new = s_in * d_acc[:, :, None, None] * has_left + s_acc
            d_acc, s_acc = d_new, s_new
            shift *= 2
        h0 = jax.lax.ppermute(s_acc, "model", perm_fwd)
        y_corr = jnp.einsum("bsn,bsh,bhnd->bshd",
                            C.astype(jnp.float32), jnp.exp(cum), h0)
        y = (y0 + y_corr).astype(xl.dtype).reshape(bl, sl, d_inner)
        y = y * jax.nn.silu(z)
        out = y @ w_out
        # cache: global final state = last shard's inclusive state; conv tail
        # = last shard's trailing K-1 frames.  mask + psum broadcasts them.
        is_last = (idx == m - 1).astype(jnp.float32)
        h_final = jax.lax.psum(s_acc * is_last, "model")
        conv_final = jax.lax.psum(
            conv_tail.astype(jnp.float32) * is_last, "model"
        ).astype(conv_tail.dtype)
        return out, h_final, conv_final

    out, h_final, conv_final = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, "model", None), P(), P(), P(), P(), P(), P()),
        out_specs=(P(bspec, "model", None), P(bspec, None, None, None),
                   P(bspec, None, None)),
    )(x, p["w_in"], p["conv"], p["A_log"], p["D"], p["dt_bias"], p["w_out"])
    return out, {"h": h_final, "conv": conv_final}
