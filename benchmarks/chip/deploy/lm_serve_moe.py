"""Deployment kind ``lm_serve_moe``: a language model with latent attention
and routed experts (DeepSeek-V2) behind ``model_serve``, one chip's share
of an expert-parallel deployment.

It is ``lm_serve``'s deployment with this model's weights and checks: the
same user entry points, warm-up, closed loop, sampled requests and
same-named host spans (imported from ``lm_serve``).  What differs:

* the served preset is checked against the configuration before any
  weights are made, so a program without the preset fails at once;
* the weights' conversion (published layout -> the served program's);
* ``correct`` holds the MEAN gap of the served tokens' logits below the
  float32 reference's best, over every sampled position, to the limit
  (``logit_gap_mean``), not the widest gap: the widest falls where a
  router near-tie sends a token to another expert in bf16 than in
  float32 (about a fifth of positions), and at such positions the bf16
  program's and the fp8 control's widest gaps overlap.  The widest gap is
  reported among the counters;
* traced runs add the routed experts' load of each prefill and serve tick
  to its span (``moe_held``, ``moe_load``), and record in a set-up span
  (``scope_ops``) the operations of the compiled no-join serve tick
  that carry each of :data:`SCOPES` in their HLO metadata, for the
  readers that time those operations in the device trace;
* a control run (``control.py``) also counts the sampled positions whose
  experts differ between the program and the reference (router near-ties)
  and where the widest logit gap falls.
"""
from __future__ import annotations

import re
import time
from typing import Dict, List

import numpy as np

from benchmarks.chip import harness, traffic as tf
from benchmarks.chip.deploy import lm_serve as base

#: ``jax.named_scope`` names of the program whose operations the readers time
SCOPES = ("moe_experts", "mla_decode_attn")
OPS_SPAN = "scope_ops"


# ---------------------------------------------------------------------------
# the preset and the weights
# ---------------------------------------------------------------------------

def check_preset(model_cfg, cfg: Dict):
    """The served preset must have the configuration's sizes, routing and
    rotary scaling."""
    rs = cfg["rope_scaling"]
    got = {"hidden_size": model_cfg.d_model,
           "intermediate_size": model_cfg.d_ff,
           "moe_intermediate_size": model_cfg.d_ff_expert,
           "num_hidden_layers": model_cfg.n_layers,
           "num_attention_heads": model_cfg.n_heads,
           "vocab_size": model_cfg.vocab,
           "kv_lora_rank": model_cfg.kv_lora_rank,
           "q_lora_rank": model_cfg.q_lora_rank,
           "qk_nope_head_dim": model_cfg.qk_nope_dim,
           "qk_rope_head_dim": model_cfg.qk_rope_dim,
           "v_head_dim": model_cfg.v_head_dim,
           "n_routed_experts": model_cfg.n_experts_held,
           "n_routed_experts_total": model_cfg.n_experts,
           "n_shared_experts": model_cfg.n_shared_experts,
           "num_experts_per_tok": model_cfg.top_k,
           "n_group": model_cfg.n_group,
           "topk_group": model_cfg.topk_group,
           "norm_topk_prob": model_cfg.norm_topk_prob,
           "routed_scaling_factor": model_cfg.routed_scaling_factor,
           "first_k_dense_replace": model_cfg.first_dense,
           "rope_theta": model_cfg.rope_theta}
    yarn = {"factor": model_cfg.yarn_factor,
            "original_max_position_embeddings":
                model_cfg.yarn_original_max_pos,
            "beta_fast": model_cfg.yarn_beta_fast,
            "beta_slow": model_cfg.yarn_beta_slow,
            "mscale": model_cfg.yarn_mscale,
            "mscale_all_dim": model_cfg.yarn_mscale_all_dim}
    bad = {k: (v, cfg[k]) for k, v in got.items() if v != cfg[k]}
    bad.update({f"rope_scaling.{k}": (v, rs[k]) for k, v in yarn.items()
                if v != rs[k]})
    if bad or not model_cfg.mla or model_cfg.norm != "rmsnorm" \
            or not model_cfg.mlp_glu or model_cfg.tie_embeddings:
        raise ValueError(f"preset {cfg['preset']!r} differs from the "
                         f"configuration: {bad}")


def preset_config(cfg: Dict):
    """The served preset's ModelConfig, checked; raises where the program
    has no such preset or it differs from the configuration."""
    from repro.launch import model_serve as ms
    model_cfg = ms.SERVE_MODELS[cfg["preset"]]()
    check_preset(model_cfg, cfg)
    return model_cfg


def to_program(w: Dict, cfg: Dict) -> Dict:
    """Published-layout weights -> the tree ``models/transformer.py``
    serves (list layout; MLA's up-projections split per head; the gate in
    float32 as the published code computes it).  The published rope layout
    (interleaved pairs, de-interleaved before rotate-half) is the program's
    interleaved rotation of the same pairs, so no column moves."""
    import jax.numpy as jnp
    h = cfg["num_attention_heads"]
    r, rq = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])

    def norm(p):
        return {"scale": p["weight"]}

    def mlp(p):
        return {"w_gate": p["gate_proj"]["weight"],
                "w_up": p["up_proj"]["weight"],
                "w_down": p["down_proj"]["weight"]}
    layers = []
    for p in w["layers"]:
        a = p["self_attn"]
        kv_b = a["kv_b_proj"]["weight"].reshape(r, h, dn + dv)
        layer = {
            "norm1": norm(p["input_layernorm"]),
            "attn": {"w_dq": a["q_a_proj"]["weight"],
                     "norm_q": norm(a["q_a_layernorm"]),
                     "w_uq": a["q_b_proj"]["weight"].reshape(rq, h, dn + dr),
                     "w_dkv": a["kv_a_proj_with_mqa"]["weight"],
                     "norm_kv": norm(a["kv_a_layernorm"]),
                     "w_uk": kv_b[..., :dn], "w_uv": kv_b[..., dn:],
                     "wo": a["o_proj"]["weight"]},
            "norm2": norm(p["post_attention_layernorm"]),
        }
        m = p["mlp"]
        if "gate" in m:
            ex = m["experts"]
            layer["moe"] = {
                "router": m["gate"]["weight"].astype(jnp.float32),
                "w_gate": ex["gate_proj"]["weight"],
                "w_up": ex["up_proj"]["weight"],
                "w_down": ex["down_proj"]["weight"],
                "shared": mlp(m["shared_experts"])}
        else:
            layer["mlp"] = mlp(m)
        layers.append(layer)
    return {"embed": {"tok": w["embed_tokens"], "head": w["lm_head"]},
            "layers": layers, "final_norm": norm(w["norm"])}


class Deployment(base.Deployment):
    """``lm_serve``'s deployment with this module's preset check."""

    def __init__(self, cfg: Dict, params):
        from repro.launch import model_serve as ms
        from repro.runtime import Device, Runtime
        self.cfg = cfg
        self.rt = Runtime()
        hub = Device("hub")
        ps = ms.serve_pipeline(model=cfg["preset"], slots=cfg["slots"],
                               max_seq=cfg["max_seq"])
        elem = ps.elements["lm"]
        check_preset(elem.cfg, cfg)
        elem.init_params = lambda rng: params
        hub.add_pipeline(ps, jit=False)
        del elem.init_params
        self.rt.add_device(hub)
        self.clients = []

    @property
    def element(self):
        return self.batcher._serve_elem()


# ---------------------------------------------------------------------------
# the compiled serve tick's operations by named scope (traced runs)
# ---------------------------------------------------------------------------

_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_INST = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_CALLS = re.compile(r"(?:calls|body|condition|to_apply|branch_computations)"
                    r"=\{?([%\w.\-, ]+)\}?")
_OPNAME = re.compile(r'op_name="([^"]*)"')


def scope_ops(hlo_text: str, scopes=SCOPES) -> Dict[str, List[str]]:
    """Per scope, the names of the operations of a compiled HLO module that
    run on the device under that ``jax.named_scope``: instructions outside
    fused computations whose metadata names the scope, or which fuse an
    instruction that does."""
    comps: Dict[str, List] = {}
    cur = None
    for line in hlo_text.splitlines():
        m = _INST.match(line)
        if m is None:
            m = _COMP.match(line)
            if m:
                cur = comps.setdefault(m.group(1), [])
        elif cur is not None:
            called = [c.strip().lstrip("%") for g in _CALLS.findall(m.group(2))
                      for c in g.split(",") if c.strip()]
            op = _OPNAME.search(line)
            cur.append((m.group(1), m.group(2), op.group(1) if op else "",
                        called))
    fused = {c for insts in comps.values() for _, text, _, called in insts
             if " fusion(" in text for c in called}
    memo: Dict[str, set] = {}

    def inner(comp: str) -> set:
        if comp not in memo:
            memo[comp] = set()
            found = set()
            for _, _, op_name, called in comps.get(comp, ()):
                found |= {s for s in scopes if s in op_name}
                for c in called:
                    found |= inner(c)
            memo[comp] = found
        return memo[comp]
    out: Dict[str, List[str]] = {s: [] for s in scopes}
    for comp, insts in comps.items():
        if comp in fused:
            continue
        for name, text, op_name, called in insts:
            here = {s for s in scopes if s in op_name}
            if " fusion(" in text:
                for c in called:
                    here |= inner(c)
            for s in here:
                out[s].append(name)
    return {s: sorted(set(v)) for s, v in out.items()}


def serve_tick_ops(dep: Deployment) -> Dict[str, List[str]]:
    """:func:`scope_ops` of the compiled no-join serve tick (the program the
    window's ticks without a join run)."""
    run = dep.batcher.run
    plan = run.pipe.plan
    src = plan.query_sources[0].name
    tick = plan.compiled_serve_tick(run.state)
    text = tick.lower(run.params, run.state,
                      {src: dep.element.empty_admit()}).compile().as_text()
    return scope_ops(text)


# ---------------------------------------------------------------------------
# spans (traced runs only)
# ---------------------------------------------------------------------------

def install_spans(spans: harness.Spans, dep: Deployment):
    """``lm_serve``'s spans, with each prefill's assignments to held experts
    (``moe_held``) and each serve tick's per-layer, per-held-expert load
    (``moe_load``, the tick's load lane) added to the span's attributes."""
    import jax
    from repro.core.modelserve import ModelServeElement
    from repro.core.plan import ExecutionPlan
    base.install_spans(spans, dep)
    prefill = ModelServeElement.host_prefill

    def host_prefill(self, params, prompt):
        before = self.moe_counters.get("moe_routed_held", 0)
        out = prefill(self, params, prompt)
        spans.spans[-1].attrs["moe_held"] = \
            self.moe_counters.get("moe_routed_held", 0) - before
        return out
    spans.patch(ModelServeElement, "host_prefill", host_prefill)
    serve_tick = ExecutionPlan.compiled_serve_tick

    def compiled_serve_tick(plan, state, donate=None):
        fn = serve_tick(plan, state, donate)

        def serve(params, st, inputs):
            outputs, new = fn(params, st, inputs)
            lanes = next(iter(outputs.values())).tensors
            if len(lanes) > 3:
                spans.spans[-1].attrs["moe_load"] = \
                    np.asarray(jax.device_get(lanes[3])).tolist()
            return outputs, new
        return serve
    spans.patch(ExecutionPlan, "compiled_serve_tick", compiled_serve_tick)


# ---------------------------------------------------------------------------
# router near-ties (control runs)
# ---------------------------------------------------------------------------

def program_routes(dep: Deployment, picked, steps_pad: int
                   ) -> List[np.ndarray]:
    """For each picked request, the experts the program routes each served
    token to when it decodes them one at a time after its prompt:
    [n_moe, g - 1, k] for the inputs at positions p .. p + g - 2."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer
    elem = dep.element
    cfg = elem.cfg
    params = dep.batcher.run.params[elem.name]

    @jax.jit
    def steps(p, cache, toks):
        def one(c, t):
            _, c, routes = transformer.lm_decode(p, cfg, t[None], c,
                                                 with_routes=True)
            return c, routes[:, 0]
        return jax.lax.scan(one, cache, toks)[1]
    out = []
    for o in picked:
        g = o.req.gen
        _, cache = elem.host_prefill(params, o.req.prompt)
        toks = np.zeros((steps_pad,), np.int32)
        toks[:g - 1] = np.asarray(o.answer[:g - 1])
        r = np.asarray(steps(params, cache, jnp.asarray(toks)))
        out.append(np.moveaxis(r[:g - 1], 0, 1))
    return out


def position_gaps(ref, weights, cfg: Dict, picked, rows_pad: int,
                  control: bool = False) -> List:
    """Per picked request, the gap by which each served token's logit lies
    below the float32 reference's best at its position (positions p - 1 ..
    p + g - 2), and with ``control`` the same gap of the token the fp8
    control puts first there."""
    import jax.numpy as jnp
    out = []
    for o in picked:
        p, g = len(o.req.prompt), o.req.gen
        ans = [int(t) for t in o.answer]
        seq = np.zeros((cfg["max_seq"],), np.int32)
        seq[:p + g - 1] = o.req.prompt + ans[:-1]
        rows = np.full((rows_pad,), p + g - 2, np.int32)
        rows[:g] = np.arange(p - 1, p - 1 + g)
        seq, rows = jnp.asarray(seq), jnp.asarray(rows)
        lg = np.asarray(ref.logits_at(weights, cfg, seq, rows))[:g]
        best = lg.max(-1)
        ctl = None
        if control:
            top = np.asarray(ref.logits_at(weights, cfg, seq, rows,
                                           fp8=True))[:g].argmax(-1)
            ctl = best - lg[np.arange(g), top]
        out.append((best - lg[np.arange(g), ans], ctl))
    return out


def route_differences(ref, weights, cfg: Dict, picked, routes,
                      gaps) -> Dict:
    """Positions whose expert sets differ between the program and the
    reference in any expert layer, and where the widest logit gap falls:
    at such a position or not, and the widest away from them."""
    import jax.numpy as jnp
    differ = compared = 0
    widest = (0.0, False)
    widest_agreeing = 0.0
    for o, prog, (gap, _) in zip(picked, routes, gaps):
        p, g = len(o.req.prompt), o.req.gen
        seq = np.zeros((cfg["max_seq"],), np.int32)
        seq[:p + g - 1] = o.req.prompt + [int(t) for t in o.answer][:-1]
        ref_r = np.asarray(ref.routes_at(weights, cfg, jnp.asarray(seq)))
        ref_r = ref_r[:, p:p + g - 1]                    # [n_moe, g-1, k]
        diff = np.zeros((g,), bool)
        diff[1:] = np.any(np.sort(ref_r, -1) != np.sort(prog, -1),
                          axis=(0, 2))
        differ += int(diff.sum())
        compared += g - 1
        i = int(np.argmax(gap))
        if gap[i] > widest[0]:
            widest = (float(gap[i]), bool(diff[i]))
        if (~diff).any():
            widest_agreeing = max(widest_agreeing, float(gap[~diff].max()))
    return {"route_differ_positions": differ, "route_compared": compared,
            "widest_gap_at_route_difference": widest[1],
            "widest_gap_where_routes_agree": widest_agreeing}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(ctx) -> Dict:
    import jax
    cfg, mix = ctx.cfg, ctx.mix
    preset_config(cfg)                  # before any weights: fail at once
    ref = harness.load_reference(cfg)
    vocab = cfg["vocab_size"]
    init = jax.jit(lambda k: to_program(ref.init_weights(cfg, k), cfg))
    params = init(base.weights_key(ctx.seed))
    dep = Deployment(cfg, params)
    del params
    base.warm_up(dep, tf.lengths_used(mix), vocab)
    if ctx.spans is not None:
        t = time.perf_counter()
        ctx.spans.spans.append(harness.Span(OPS_SPAN, t, t,
                                            serve_tick_ops(dep)))
        install_spans(ctx.spans, dep)
    cycles = tf.closed_loop_cycles(mix, ctx.seed, vocab)
    outcomes, t0, t1 = base.drive_closed_loop(ctx, dep, cycles,
                                              mix["drain_s"])
    tokens = ctx.tokens_at_close - ctx.tokens_at_open
    ctx.close_window(t1)
    answered = sum(1 for o in outcomes if o.answer is not None)
    counters = {"tokens_window": tokens, "ticks": dep.rt.ticks,
                "answered": answered,
                "stats": dep.rt.stats()["query_batching"]}
    metrics = {"lm_tokens_per_s": tokens / (t1 - t0)}
    ctx.read_memory()
    picked = base.sample(outcomes, ctx.seed, mix["check_tokens"])
    bad_len = base.wrong_lengths(outcomes)
    failed = sum(1 for o in outcomes if o.failed and o.due <= t1)
    rows_pad = max(g for g, _ in mix["gen_lengths"])
    routes = program_routes(dep, picked, rows_pad) if ctx.control else None
    del dep, outcomes
    harness.free_program()
    weights = jax.jit(lambda k: ref.init_weights(cfg, k))(
        base.weights_key(ctx.seed))
    gaps = position_gaps(ref, weights, cfg, picked, rows_pad, ctx.control)
    served = np.concatenate([g for g, _ in gaps]) if gaps else np.zeros(0)
    counters["logit_gap_widest"] = float(served.max()) if served.size else 0.0
    if routes is not None:
        counters.update(route_differences(ref, weights, cfg, picked, routes,
                                          gaps))
    checks = [("logit_gap_mean", float(served.mean()) if served.size else 0.0,
               cfg["limits"]["logit_gap_mean"]),
              ("wrong_lengths", bad_len, 0),
              ("unchecked", int(served.size == 0), 0)]
    out = {"attempted": answered + failed, "failed": failed,
           "metrics": metrics, "checks": checks, "counters": counters,
           "span_names": base.SPAN_NAMES}
    if ctx.control:
        ctl = np.concatenate([c for _, c in gaps]) if gaps else np.zeros(1)
        counters["logit_gap_widest_control"] = float(ctl.max())
        out["control_checks"] = harness.control_checks(
            checks, "logit_gap_mean", float(ctl.mean()))
        harness.log(f"control counters {counters}")
    return out
