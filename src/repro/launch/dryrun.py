import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: prove every (arch × shape × mesh) lowers and compiles
with coherent shardings — no real hardware, 512 placeholder host devices.
(The XLA_FLAGS assignment above MUST precede every jax import — jax locks
the device count at first init.)

    PYTHONPATH=src python -m repro.launch.dryrun --arch stablelm-1.6b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Per combo it records compiled memory_analysis (bytes/device — proves fit),
cost_analysis (FLOPs/bytes for §Roofline), and the collective schedule
(bytes per collective kind parsed from compiled HLO), appending JSON to
results/dryrun.json for the roofline report.

Roofline terms: XLA's HLO cost analysis counts a while-loop (lax.scan) body
ONCE, so the scanned production program under-reports FLOPs by ~n_layers×.
We therefore derive per-layer costs by compiling UNROLLED 1-unit and 2-unit
variants of each arch (identical shardings, per-layer remat) and
extrapolating layer-linearly:  total = C(1) + (units-1)·(C(2)-C(1)).
cost_analysis is per-device for SPMD executables (verified), so terms are
already per-chip.
"""
import argparse
import json
import time
import traceback
from dataclasses import replace
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..configs import ARCH_IDS, get_config
from ..models.model import build_model
from ..models.transformer import layer_plan
from . import hlo_analysis as HA
from . import shardings as SH
from . import steps as ST
from .mesh import make_production_mesh, mesh_axis_sizes

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "results", "dryrun.json")


def _build_lowered(cfg, model, shape_name: str, mesh, stacked: bool):
    """Lower the step for one combo; returns (lowered, meta)."""
    info = ST.SHAPES[shape_name]
    mode = info["mode"]
    seq = model.clamp_seq(info["seq"])
    batch = info["global_batch"]

    params_shape = ST.eval_params_shape(model, stacked)
    pspec = SH.stacked_param_shardings(cfg, mesh, params_shape) if stacked \
        else SH.param_shardings(cfg, mesh, params_shape)
    specs = ST.input_specs(model, shape_name)
    bspec = SH.batch_shardings(cfg, mesh, specs)

    if mode == "train":
        step = ST.make_train_step(model, mesh, stacked=stacked)
        opt_shape = ST.eval_opt_shape(params_shape)
        ospec = ST.opt_shardings(mesh, pspec, opt_shape)
        jitted = jax.jit(step, in_shardings=(pspec, ospec, bspec),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(params_shape, opt_shape, specs)
    elif mode == "prefill":
        step = ST.make_prefill_step(model, mesh, max_seq=seq, stacked=stacked)
        jitted = jax.jit(step, in_shardings=(pspec, bspec))
        lowered = jitted.lower(params_shape, specs)
    else:  # decode
        shard_kv = (shape_name == "long_500k")
        step = ST.make_decode_step(model, mesh, shard_kv_seq=shard_kv,
                                   stacked=stacked)
        cache_shape = ST.eval_cache_shape(model, batch, seq, stacked)
        cspec = SH.cache_shardings(cfg, mesh, cache_shape, shard_kv_seq=shard_kv)
        tok_spec = specs["token"]
        tspec = SH.batch_shardings(cfg, mesh, {"token": tok_spec})["token"]
        jitted = jax.jit(step, in_shardings=(pspec, tspec, cspec),
                         donate_argnums=(2,))
        lowered = jitted.lower(params_shape, tok_spec, cache_shape)
    return lowered, {"mode": mode, "seq": seq, "global_batch": batch}


def _cost_and_colls(compiled) -> Dict:
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    colls = HA.collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "colls": colls}


def _reduced_cfg(cfg, k: int):
    """cfg with k pattern-units of layers (prefix/tail preserved)."""
    prefix, period, repeats, tail = layer_plan(cfg)
    n_layers = len(prefix) + k * period + (cfg.n_layers - len(prefix)
                                           - repeats * period)
    kw = {"n_layers": n_layers}
    if cfg.enc_dec:
        kw["n_enc_layers"] = k
        kw["n_layers"] = k
    return replace(cfg, **kw), (cfg.n_enc_layers if cfg.enc_dec else repeats)


def extrapolated_roofline(arch: str, shape_name: str, multi_pod: bool,
                          n_chips: int, mesh,
                          overrides: Optional[Dict] = None) -> Dict:
    """Layer-linear extrapolation of the three roofline terms from unrolled
    1-unit and 2-unit compiles."""
    cfg = get_config(arch)
    if overrides:
        cfg = replace(cfg, **overrides)
    measures = {}
    for k in (1, 2):
        cfg_k, units = _reduced_cfg(cfg, k)
        model_k = build_model(cfg_k)
        lowered, _ = _build_lowered(cfg_k, model_k, shape_name, mesh,
                                    stacked=False)
        measures[k] = _cost_and_colls(lowered.compile())
    c1, c2 = measures[1], measures[2]
    _, units = _reduced_cfg(cfg, 1)

    def lin(a, b):
        return a + (units - 1) * (b - a)

    flops = lin(c1["flops"], c2["flops"])
    bytes_ = lin(c1["bytes"], c2["bytes"])
    colls = {k: int(lin(c1["colls"][k], c2["colls"][k])) for k in c1["colls"]}
    terms = HA.roofline_terms({"flops": flops, "bytes accessed": bytes_},
                              colls, n_chips)
    terms["dominant"] = HA.dominant_term(terms)
    terms["units_extrapolated"] = units
    return {"roofline": terms, "collectives": colls,
            "unit_costs": {str(k): m for k, m in measures.items()}}


def lower_combo(arch: str, shape_name: str, multi_pod: bool,
                compile_: bool = True, analysis: bool = True,
                overrides: Optional[Dict] = None, variant: str = "") -> Dict:
    """Full scanned lower+compile (sharding & memory proof) + extrapolated
    roofline terms (single-pod analysis).  ``overrides`` patches ModelConfig
    fields (perf-iteration variants, recorded under ``variant``)."""
    t0 = time.time()
    cfg = get_config(arch)
    if overrides:
        cfg = replace(cfg, **overrides)
    model = build_model(cfg)
    ok, why = ST.shape_applicable(cfg, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    stacked = model.supports_stacked

    with jax.set_mesh(mesh):
        lowered, meta = _build_lowered(cfg, model, shape_name, mesh, stacked)
        t_lower = time.time() - t0
        rec = {"arch": arch, "shape": shape_name,
               "mesh": "multi" if multi_pod else "single",
               "n_chips": n_chips, **meta, "lower_s": round(t_lower, 1),
               "status": "lowered"}
        if variant:
            rec["variant"] = variant
            rec["overrides"] = overrides
        if not compile_:
            return rec
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0 - t_lower, 1)
        mem = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
        rec["scanned_cost_raw"] = _cost_and_colls(compiled)
        rec["status"] = "compiled"

        if analysis:
            ana = extrapolated_roofline(arch, shape_name, multi_pod, n_chips,
                                        mesh, overrides)
            rec.update(ana)
            # MODEL_FLOPS = 6·N_active·D (train) / 2·N_active·D (serve)
            toks = meta["global_batch"] * (meta["seq"] if meta["mode"] != "decode" else 1)
            n_active = model.active_param_count()
            mf = (6.0 if meta["mode"] == "train" else 2.0) * n_active * toks
            rec["model_flops_total"] = mf
            hlo_total = rec["roofline"]["flops_per_device"] * n_chips
            rec["model_vs_hlo_flops"] = mf / hlo_total if hlo_total else None
        rec["analysis_s"] = round(time.time() - t0, 1)
        return rec


def append_result(rec: Dict, path: str = RESULTS):
    path = os.path.abspath(path)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    data = []
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    data = [r for r in data
            if not (r["arch"] == rec["arch"] and r["shape"] == rec["shape"]
                    and r["mesh"] == rec["mesh"]
                    and r.get("variant", "") == rec.get("variant", ""))]
    data.append(rec)
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(ST.SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-compile", action="store_true")
    ap.add_argument("--no-analysis", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="ModelConfig override field=value (perf variants)")
    ap.add_argument("--variant", default="",
                    help="label for this perf variant in results json")
    ap.add_argument("--out", default=RESULTS)
    args = ap.parse_args()
    overrides = {}
    for kv in args.set:
        k, _, v = kv.partition("=")
        overrides[k] = {"0": False, "1": True, "true": True,
                        "false": False}.get(v.lower(), v)

    archs = ARCH_IDS if (args.all or not args.arch) else (args.arch,)
    shapes = tuple(ST.SHAPES) if (args.all or not args.shape) else (args.shape,)
    meshes = {"single": (False,), "multi": (True,), "both": (False, True)}[args.mesh]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                tag = f"{arch} × {shape} × {'multi' if multi else 'single'}"
                try:
                    rec = lower_combo(arch, shape, multi,
                                      compile_=not args.no_compile,
                                      analysis=not args.no_analysis and not multi,
                                      overrides=overrides or None,
                                      variant=args.variant)
                except Exception as e:
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "multi" if multi else "single",
                           "status": "FAILED", "error": f"{type(e).__name__}: {e}"}
                    failures += 1
                append_result(rec, args.out)
                status = rec["status"]
                extra = ""
                if "roofline" in rec:
                    r = rec["roofline"]
                    extra = (f" dominant={r['dominant']}"
                             f" compute={r['compute_s']:.2e}s"
                             f" mem={r['memory_s']:.2e}s"
                             f" coll={r['collective_s']:.2e}s"
                             f" model/hlo={rec.get('model_vs_hlo_flops', 0):.2f}")
                print(f"[dryrun] {tag}: {status}{extra}", flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
