"""Distributed-path correctness on forged host devices (subprocess so
XLA_FLAGS takes effect before jax init — the main test process stays at 1
device, as required).

* shard_map MoE == dense-path MoE numerics on a real (2,4) mesh (EP and
  intra-expert-TP regimes).
* train/prefill/decode steps lower+compile on a small mesh for a dense and
  an MoE smoke arch (mini dry-run).
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(script: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env, timeout=560)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


@pytest.mark.slow
def test_shard_map_moe_matches_dense():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.models import ModelConfig
        from repro.models import moe as MOE
        from repro.models.sharding import sharding_rules
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = make_mesh((2, 4), ("data", "model"))
        for n_experts in (4, 8):   # 4 -> intra-expert TP, 8 -> EP
            cfg = ModelConfig(name="t", arch_type="moe", n_layers=1,
                              d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                              vocab=64, n_experts=n_experts, top_k=2,
                              d_ff_expert=32, dtype="float32",
                              capacity_factor=float(n_experts))  # no drops
            p = MOE.moe_init(jax.random.PRNGKey(0), cfg)
            x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))
            y_ref, aux_ref = MOE._apply_moe_dense(p, cfg, x)
            with jax.set_mesh(mesh):
                with sharding_rules(batch="data", __mesh__=mesh):
                    y_sm, aux_sm = jax.jit(
                        lambda p, x: MOE._apply_moe_shard_map(p, cfg, x, mesh)
                    )(p, x)
            np.testing.assert_allclose(np.asarray(y_sm), np.asarray(y_ref),
                                       rtol=2e-4, atol=2e-4)
            # aux is computed per data shard then averaged (GShard-style
            # per-group balance) — statistically close to the global value
            # but not bit-identical
            np.testing.assert_allclose(float(aux_sm), float(aux_ref), rtol=0.2)
            print("moe ok", n_experts)
    """)


@pytest.mark.slow
def test_mini_dryrun_lowers_on_small_mesh():
    _run("""
        import jax
        from dataclasses import replace
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config
        from repro.models.model import build_model
        from repro.launch import steps as ST, shardings as SH

        mesh = make_mesh((2, 4), ("data", "model"))
        for arch in ("gemma3-4b", "mixtral-8x22b", "mamba2-130m"):
            cfg = get_config(arch).smoke()
            cfg = replace(cfg, vocab=512)
            model = build_model(cfg)
            stacked = model.supports_stacked
            pshape = ST.eval_params_shape(model, stacked)
            pspec = SH.stacked_param_shardings(cfg, mesh, pshape)
            with jax.set_mesh(mesh):
                # train
                step = ST.make_train_step(model, mesh, stacked=stacked)
                oshape = ST.eval_opt_shape(pshape)
                ospec = ST.opt_shardings(mesh, pspec, oshape)
                import jax.numpy as jnp
                batch = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
                bspec = SH.batch_shardings(cfg, mesh, batch)
                jax.jit(step, in_shardings=(pspec, ospec, bspec)).lower(
                    pshape, oshape, batch).compile()
                # decode
                dstep = ST.make_decode_step(model, mesh, stacked=stacked)
                cshape = ST.eval_cache_shape(model, 8, 64, stacked)
                cspec = SH.cache_shardings(cfg, mesh, cshape)
                tok = jax.ShapeDtypeStruct((8,), jnp.int32)
                tspec = SH.batch_shardings(cfg, mesh, {"t": tok})["t"]
                jax.jit(dstep, in_shardings=(pspec, tspec, cspec)).lower(
                    pshape, tok, cshape).compile()
            print("lowered", arch)
    """)


@pytest.mark.slow
def test_seq_parallel_ssd_matches_reference():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from dataclasses import replace
        from repro.models import ModelConfig
        from repro.models import ssm as SSM
        from repro.models.sharding import sharding_rules

        cfg = ModelConfig(name="s", arch_type="ssm", n_layers=1, d_model=64,
                          n_heads=0, n_kv_heads=0, d_ff=0, vocab=64,
                          layer_pattern="S", ssm_state=16, ssm_head_dim=16,
                          ssm_chunk=8, dtype="float32")
        p = SSM.ssm_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
        y_ref = SSM.ssm_train(p, cfg, x)
        _, cache_ref = SSM.ssm_prefill(p, cfg, x)
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg_sp = replace(cfg, ssm_seq_parallel=True)
        with jax.set_mesh(mesh):
            with sharding_rules(batch="data", __mesh__=mesh):
                y_sp = jax.jit(lambda p, x: SSM.ssm_train(p, cfg_sp, x))(p, x)
                y_pf, cache_sp = jax.jit(
                    lambda p, x: SSM.ssm_prefill(p, cfg_sp, x))(p, x)
        np.testing.assert_allclose(np.asarray(y_sp), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(cache_sp["h"]),
                                   np.asarray(cache_ref["h"]),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(cache_sp["conv"]),
                                   np.asarray(cache_ref["conv"]),
                                   rtol=1e-4, atol=1e-4)
        print("seq-parallel prefill+train ok")
    """)


@pytest.mark.slow
def test_pp_pod_offload_serve():
    """Pipeline-parallel decode across the pod axis (Fig. 2 at pod scale):
    tokens and caches must match the plain stacked decode."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.models import ModelConfig, build_model
        from repro.launch.pp_serve import make_pp_serve_step, pp_applicable
        cfg = ModelConfig(name="t", arch_type="dense", n_layers=4, d_model=64,
                          n_heads=4, n_kv_heads=2, d_ff=128, vocab=97,
                          dtype="float32")
        m = build_model(cfg)
        sp = m.stack_params(m.init(jax.random.PRNGKey(0)))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 12), 0, 97)
        lp, cache = m.prefill_stacked(sp, {"tokens": toks}, max_seq=20)
        nxt = jnp.argmax(lp, -1)
        ld_ref, cref = m.decode_step_stacked(sp, nxt, cache)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        assert pp_applicable(m, mesh)
        with jax.set_mesh(mesh):
            tok_out, cpp = jax.jit(make_pp_serve_step(m, mesh))(sp, nxt, cache)
        np.testing.assert_array_equal(np.asarray(tok_out),
                                      np.asarray(jnp.argmax(ld_ref, -1)))
        np.testing.assert_allclose(np.asarray(cpp["groups"][0]["k"]),
                                   np.asarray(cref["groups"][0]["k"]),
                                   atol=1e-5)
        print("pp serve ok")
    """)
