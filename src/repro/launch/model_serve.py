"""Model-serving launch helpers: real networks behind the query fabric.

Registers the tier-1 serve presets (SERVE_MODELS keys the ``model_serve``
element resolves) and provides the gst-launch-style builders tests and
benchmarks share, plus the per-request SEQUENTIAL decode reference the
continuous-batching parity pins compare against (DESIGN.md §7).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional

import jax
import jax.numpy as jnp

from ..core import parse_launch
from ..core.modelserve import SERVE_MODELS, register_serve_model
from ..models.config import ModelConfig

__all__ = ["serve_pipeline", "client_pipeline", "sequential_decode",
           "stage_pipeline", "staged_serve_pipelines", "SERVE_MODELS",
           "three_tier_qos"]


def _stablelm_smoke_flash() -> ModelConfig:
    """Small dense transformer with the Pallas flash prefill (attn_train's
    flash gate); decode attends in one piece (``_sdpa``), as every preset's."""
    from ..configs import stablelm_1_6b
    return dataclasses.replace(stablelm_1_6b.config().smoke(),
                               use_flash_attn=True)


def _stablelm_smoke() -> ModelConfig:
    from ..configs import stablelm_1_6b
    return stablelm_1_6b.config().smoke()


def _recurrentgemma_smoke() -> ModelConfig:
    """rGLRU hybrid (R,R,L pattern): recurrent state + windowed-attention
    ring caches as plan state — the SSM-side pin of the stateful contract."""
    from ..configs import recurrentgemma_9b
    return recurrentgemma_9b.config().smoke()


def _stablelm_smoke_4l() -> ModelConfig:
    """4-layer smoke variant: the pipeline-parallel staging testbed — its
    layer count divides evenly into 2 and 4 stages (DESIGN.md §8)."""
    from ..configs import stablelm_1_6b
    return dataclasses.replace(stablelm_1_6b.config().smoke(), n_layers=4)


def _stablelm_1_6b() -> ModelConfig:
    """stablelm-2-1.6b at its published widths (24 layers, d 2048, 32 heads
    × 64, ffn 5632, vocab 100352, bf16), with the Pallas flash prefill on
    the served path — the preset that fills one v5e chip."""
    from ..configs import stablelm_1_6b
    return dataclasses.replace(stablelm_1_6b.config(), use_flash_attn=True)


def _deepseek_v2_ep8() -> ModelConfig:
    """deepseek-v2-236b as one chip of an 8-way expert-parallel deployment:
    every width as published (MLA with YaRN, 160-way group-limited router,
    top-6, 2 shared experts, dense first layer, bf16), 20 of each layer's
    160 routed experts held here, and 5 of the 60 layers (the dense one and
    four expert layers; the rest would be further pipeline stages)."""
    from ..configs import deepseek_v2_236b
    return dataclasses.replace(deepseek_v2_236b.config(), n_layers=5,
                               experts_held=20)


def _deepseek_v2_smoke() -> ModelConfig:
    """Small deepseek-v2 for the CPU: 3 layers (dense, then two expert
    layers), 16 experts in 4 groups with the best 2 kept, top-4, 8 held
    here, the published routing and YaRN settings, float32."""
    from ..configs import deepseek_v2_236b
    return dataclasses.replace(deepseek_v2_236b.config().smoke(), n_layers=3,
                               n_experts=16, n_group=4, topk_group=2, top_k=4,
                               experts_held=8, n_shared_experts=2)


register_serve_model("stablelm-1.6b", _stablelm_1_6b)
register_serve_model("deepseek-v2-236b-ep8", _deepseek_v2_ep8)
register_serve_model("deepseek-v2-smoke", _deepseek_v2_smoke)
register_serve_model("stablelm-smoke-flash", _stablelm_smoke_flash)
register_serve_model("stablelm-smoke", _stablelm_smoke)
register_serve_model("recurrentgemma-smoke", _recurrentgemma_smoke)
register_serve_model("stablelm-smoke-4l", _stablelm_smoke_4l)


def serve_pipeline(operation: str = "lm", model: str = "stablelm-smoke-flash",
                   slots: int = 8, max_seq: int = 32):
    """Server pipeline: serversrc ! model_serve ! serversink, sink paired."""
    ps = parse_launch(
        f"tensor_query_serversrc operation={operation} name=ssrc ! "
        f"model_serve model={model} slots={slots} max_seq={max_seq} "
        f"name=lm ! tensor_query_serversink name=ssink")
    ps.elements["ssink"].pair_with(ps.elements["ssrc"])
    return ps


def stage_pipeline(operation: str = "lm", model: str = "stablelm-smoke-4l",
                   slots: int = 8, max_seq: int = 32, stage: int = 0,
                   n_stages: int = 2):
    """ONE hop of an among-device pipeline-parallel chain (DESIGN.md §8).

    Stage 0 serves the client-facing operation topic (clients need no idea
    the model is staged); downstream stages serve ``{operation}/s{k}`` —
    the topic the coordinator's per-stage bindings subscribe, with
    ``stage`` declared as a ranking spec so a wildcard never binds a hop
    to the wrong layer slice."""
    topic = operation if stage == 0 else f"{operation}/s{stage}"
    ps = parse_launch(
        f"tensor_query_serversrc operation={topic} stage={stage} "
        f"name=ssrc ! "
        f"model_serve_stage model={model} slots={slots} max_seq={max_seq} "
        f"stage={stage} n_stages={n_stages} name=lm ! "
        f"tensor_query_serversink name=ssink")
    ps.elements["ssink"].pair_with(ps.elements["ssrc"])
    return ps


def staged_serve_pipelines(operation: str = "lm",
                           model: str = "stablelm-smoke-4l",
                           slots: int = 8, max_seq: int = 32,
                           n_stages: int = 2):
    """The full N-hop chain: one ``stage_pipeline`` per layer slice.

    Deploy each on its own Device: stage k's per-slot boundary activations
    stream to stage k+1 over the same query fabric clients use, so broker
    discovery ranks stages, leases detect stage death, and §6 reconfig
    covers stage swap — among-device hops, not intra-process shards."""
    return [stage_pipeline(operation, model, slots, max_seq, k, n_stages)
            for k in range(n_stages)]


def client_pipeline(operation: str = "lm", prompts: str = "1,2,3",
                    gens: str = "4", codec: str = "none",
                    tenant: Optional[str] = None):
    """Streaming client: one prompt request per frame, cycling prompts/gens.

    ``tenant`` tags every request with a tenant id so the serve side's
    admission layer can schedule it under that tenant's QoS contract;
    ``None`` keeps the pre-QoS wire format byte-identical."""
    tenant_prop = f" tenant={tenant}" if tenant is not None else ""
    return parse_launch(
        f"token_prompt_src prompts={prompts} gens={gens} ! "
        f"tensor_query_client operation={operation} codec={codec}"
        f"{tenant_prop} name=qc ! appsink name=res")


def three_tier_qos(rate: Optional[int] = None,
                   deadline_ticks: Optional[int] = None,
                   max_queue: Optional[int] = None,
                   serve_per_tick: Optional[int] = None):
    """The canonical three-tenant serving contract (DESIGN.md §9).

    * ``realtime``    — priority 0, strict per-tick deadline, never sheds
      for rate (interactive traffic is assumed pre-shaped upstream);
    * ``standard``    — priority 1, rate-limited to ``rate`` req/tick with
      a matching burst, bounded queue;
    * ``best-effort`` — priority 2, same rate budget, shortest deadline and
      smallest queue: the tier that sheds FIRST under overload, explicitly.

    Unknown tenant ids fall into ``best-effort`` (the ``default`` spec), so
    an unregistered tenant can never crowd out paying tiers."""
    from ..core.admission import QoSConfig, TenantSpec
    best_effort = TenantSpec("best-effort", priority=2, rate=rate,
                             deadline_ticks=deadline_ticks, max_queue=max_queue)
    return QoSConfig(
        tenants=(
            TenantSpec("realtime", priority=0,
                       deadline_ticks=deadline_ticks),
            TenantSpec("standard", priority=1, rate=rate,
                       deadline_ticks=(None if deadline_ticks is None
                                       else 2 * deadline_ticks),
                       max_queue=(None if max_queue is None
                                  else 2 * max_queue)),
            best_effort,
        ),
        default=best_effort,
        serve_per_tick=serve_per_tick)


@functools.lru_cache(maxsize=16)
def _sequential_fns(cfg: ModelConfig, max_seq: int):
    """The jitted b=1 prefill and decode step of one (config, cache length),
    built once: every reference call after the first reuses their traces
    and compiled executables (a full-width model compiles for tens of
    seconds)."""
    from ..models import transformer

    @jax.jit
    def prefill(p, toks):
        logits, cache = transformer.lm_prefill(p, cfg, toks[None], max_seq)
        return jnp.argmax(logits[0], axis=-1).astype(jnp.int32), cache

    @jax.jit
    def decode(p, tok, cache):
        logits, cache = transformer.lm_decode(p, cfg, tok[None], cache)
        return jnp.argmax(logits[0], axis=-1).astype(jnp.int32), cache

    return prefill, decode


def sequential_decode(params, cfg: ModelConfig, prompt, gen: int,
                      max_seq: int) -> List[int]:
    """Per-request sequential greedy decode — the parity reference.

    One jitted b=1 prefill then ``gen - 1`` jitted b=1 decode steps: the
    exact program each slot of the continuous batch runs, dispatched the
    pre-batching way.  Continuous-batched serving must reproduce this
    token-for-token (bitwise) for every request, whatever the join/leave
    interleaving."""
    prefill, decode = _sequential_fns(cfg, max_seq)
    tok, cache = prefill(params, jnp.asarray(prompt, jnp.int32))
    out = [int(tok)]
    for _ in range(max(0, gen - 1)):
        tok, cache = decode(params, tok, cache)
        out.append(int(tok))
    return out
