"""Among-device serving across the pod axis: pipeline-parallel decode.

This is the paper's Fig. 2 realized at pod scale: the "client" pod owns the
first half of the model's layers, the "server" pod the second half; the
residual stream is the query payload, shipped by ``ppermute`` across the
`pod` axis (the ICI link standing in for the paper's TCP/MQTT-hybrid data
plane).  Microbatches pipeline GPipe-style so both pods do useful work in
the steady state (bubble = (P-1)/(2P-1) for one decode step).

Implementation: shard_map manual over {"pod"} only — data/model stay under
GSPMD (auto axes), so each stage's layers still run tensor-parallel inside
the pod.  The stacked layer dim is pod-sharded: params.stack [R, ...] →
[R/P, ...] per pod; decode caches likewise.

Restrictions (checked): decoder-only, single-period layer pattern, no
prefix/tail, repeats % n_pods == 0 — i.e. the uniform dense archs
(qwen, granite, stablelm, internvl2-LM).
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models import layers as L
from ..models.model import Model
from ..models.sharding import sharding_rules
from ..models.transformer import block_decode, layer_plan
from . import shardings as SH


def pp_applicable(model: Model, mesh) -> bool:
    cfg = model.cfg
    if "pod" not in mesh.axis_names or cfg.enc_dec:
        return False
    prefix, period, repeats, tail = layer_plan(cfg)
    return (not prefix and not tail and period == 1
            and repeats % mesh.shape["pod"] == 0)


def make_pp_serve_step(model: Model, mesh, shard_kv_seq: bool = False
                       ) -> Callable:
    cfg = model.cfg
    assert pp_applicable(model, mesh)
    n_pods = mesh.shape["pod"]
    kind = cfg.kind(0)
    # batch shards over `data` only — `pod` is the stage axis here
    rules = SH.activation_rules(cfg, mesh, shard_kv_seq=shard_kv_seq)
    rules["batch"] = "data"
    rules["__mesh__"] = mesh

    def _pad(spec_len):
        return P(*([None] * spec_len))

    def serve_step(params, token, cache):
        with sharding_rules(**rules):
            b = token.shape[0]
            mb = b // n_pods
            pos = cache["pos"]
            # one-hot embed: XLA's gather partitioner CHECK-fails under the
            # partial-manual pod submesh; a dot partitions cleanly (decode is
            # one row per token — cost negligible)
            onehot_tok = jax.nn.one_hot(token[:, None], cfg.vocab,
                                        dtype=params["embed"]["tok"].dtype)
            x = onehot_tok @ params["embed"]["tok"]              # [B,1,d]

            stack = params["stack"][0]
            groups = cache["groups"][0]

            def body(stack_l, caches_l, x_all, stage_ids):
                # stage id arrives as a pod-sharded iota input rather than
                # jax.lax.axis_index: under a partial-manual submesh the
                # latter lowers to PartitionId, which the SPMD partitioner
                # rejects (and old jax cannot express at all)
                stage = stage_ids[0]
                d = x_all.shape[-1]
                mbs = x_all.reshape(n_pods, mb, 1, d)
                outs = jnp.zeros_like(mbs)
                buf = jnp.zeros((mb, 1, d), x_all.dtype)
                buf = jax.lax.pcast(buf, ("pod",), to="varying")
                outs = jax.lax.pcast(outs, ("pod",), to="varying")
                new_caches = caches_l
                perm = [(i, i + 1) for i in range(n_pods - 1)]

                def scan_layers(c_slice, inp):
                    def unit(xc, scanned):
                        p_l, c_l = scanned
                        y, nc = block_decode(p_l, cfg, kind, xc, c_l, pos)
                        return y, nc
                    y, ncs = jax.lax.scan(unit, inp, (stack_l, c_slice))
                    return y, ncs

                for t in range(2 * n_pods - 1):
                    mb_idx = t - stage                  # traced
                    valid = (mb_idx >= 0) & (mb_idx < n_pods)
                    safe_idx = jnp.clip(mb_idx, 0, n_pods - 1)
                    # static microbatch index for stage 0 (t is a python int)
                    inp = jnp.where(stage == 0, mbs[min(t, n_pods - 1)], buf)

                    # STATIC slices + select: a traced-start dynamic-slice
                    # over the data-sharded batch dim makes GSPMD all-gather
                    # the whole cache (measured 2.9 TB/dev) — static starts
                    # partition cleanly
                    def slice_mb(c):
                        parts = [jax.lax.slice_in_dim(c, m2 * mb,
                                                      (m2 + 1) * mb, axis=1)
                                 for m2 in range(n_pods)]
                        out = parts[0]
                        for m2 in range(1, n_pods):
                            out = jnp.where(safe_idx == m2, parts[m2], out)
                        return out

                    c_slice = jax.tree_util.tree_map(slice_mb, new_caches)
                    y, nc = scan_layers(c_slice, inp)

                    def update_mb(old, new_s):
                        out = old
                        for m2 in range(n_pods):
                            upd = jax.lax.dynamic_update_slice_in_dim(
                                old, new_s, m2 * mb, 1)   # static start
                            out = jnp.where((safe_idx == m2) & valid, upd, out)
                        return out

                    new_caches = jax.tree_util.tree_map(update_mb,
                                                        new_caches, nc)
                    # mask-based accumulation (scatter with a traced index
                    # crashes XLA's partial-manual gather partitioner)
                    onehot = (jnp.arange(n_pods) == safe_idx)
                    sel = ((stage == n_pods - 1) & valid)
                    outs = outs + jnp.where(
                        (onehot & sel)[:, None, None, None], y[None], 0.0
                    ).astype(outs.dtype)
                    buf = jax.lax.ppermute(y, "pod", perm)
                # replicate the last stage's outputs to every pod
                outs = jax.lax.psum(
                    jnp.where(stage == n_pods - 1, outs, jnp.zeros_like(outs)),
                    "pod")
                return outs.reshape(b, 1, d), new_caches

            nd = {leaf.ndim for leaf in jax.tree_util.tree_leaves(stack)}
            stack_specs = jax.tree_util.tree_map(
                lambda leaf: P("pod", *([None] * (leaf.ndim - 1))), stack)
            cache_specs = jax.tree_util.tree_map(
                lambda leaf: P("pod", *([None] * (leaf.ndim - 1))), groups)
            x_out, new_groups = jax.shard_map(
                body, mesh=mesh,
                in_specs=(stack_specs, cache_specs, P(None, None, None),
                          P("pod")),
                out_specs=(P(None, None, None), cache_specs),
                axis_names={"pod"}, check_vma=False,
            )(stack, groups, x, jnp.arange(n_pods, dtype=jnp.int32))

            h = L.apply_norm(params["final_norm"], x_out, cfg)
            logits = L.unembed(params["embed"], cfg, h)[:, 0]
            next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            new_cache = {"pos": pos + 1, "prefix": cache["prefix"],
                         "groups": [new_groups], "tail": cache["tail"]}
        return next_token, new_cache

    return serve_step
