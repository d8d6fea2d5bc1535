"""Operations and HBM bytes of the work a model with latent attention and
routed experts (DeepSeek-V2) needs, computed from shapes and from the
routed counts the program reports.

Counts follow the algorithm at valid positions, as ``counts.py`` does: a
decode step's latent attention reads the cached latents of positions
``0 .. ctx`` (``n = ctx + 1``), not the whole ``max_seq`` slot; a routed
expert's work is the tokens routed to it, and its weights are read once
per layer and tick when it gets any token.  Active parameters only: the
held experts a token is routed to, the shared experts, MLA, the dense
layer and the head; the embedding is a row gather.  A multiply-add is two
operations.  ``cfg`` is the configuration's JSON object.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

BF16 = 2


def _mla(cfg: Dict) -> Tuple[int, int, int, int, int, int]:
    return (cfg["num_attention_heads"], cfg["kv_lora_rank"],
            cfg["q_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def mla_params(cfg: Dict) -> int:
    """Weights one token multiplies through in one latent-attention layer:
    q_a, q_b, kv_a, kv_b (the absorbed decode multiplies W_uk and W_uv
    once each, the same count) and o."""
    d = cfg["hidden_size"]
    h, r, rq, dn, dr, dv = _mla(cfg)
    return d * rq + rq * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) \
        + h * dv * d


def expert_params(cfg: Dict) -> int:
    """One routed expert's gated MLP."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def n_moe_layers(cfg: Dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def dense_token_params(cfg: Dict) -> int:
    """Weights every token multiplies through, whatever the router picks:
    MLA in every layer, the dense layers' MLP, the shared experts of every
    expert layer (the head is counted apart)."""
    d = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"]
    return (cfg["num_hidden_layers"] * mla_params(cfg)
            + dense * 3 * d * cfg["intermediate_size"]
            + n_moe_layers(cfg) * cfg["n_shared_experts"] * expert_params(cfg))


def mla_decode_call(cfg: Dict, ctx: int) -> Tuple[int, int]:
    """(flops, bytes) of one layer's absorbed latent attention for one
    sequence whose input sits at position ``ctx``: scores against the
    ``n = ctx + 1`` cached latents and rope keys, and the weighted sum of
    the latents; the bf16 latents and rope keys read once."""
    h, r, _, _, dr, _ = _mla(cfg)
    n = ctx + 1
    return 2 * h * (r + dr) * n + 2 * h * r * n, BF16 * (r + dr) * n


def expert_call(cfg: Dict, load: Sequence[int]) -> Tuple[int, int]:
    """(flops, bytes) of one expert layer's held experts for one call whose
    per-expert token counts are ``load``: each routed token through its
    expert, each expert with any token reading its bf16 weights once, and
    each routed token's row read in and written out."""
    d = cfg["hidden_size"]
    routed = sum(load)
    hit = sum(1 for c in load if c)
    return (2 * expert_params(cfg) * routed,
            BF16 * (expert_params(cfg) * hit + 2 * d * routed))


def decode_token_flops(cfg: Dict, ctx: int) -> int:
    """One decoded token at position ``ctx`` without its routed experts:
    every layer's dense work, absorbed attention over ``ctx + 1`` positions
    and the head."""
    layers = cfg["num_hidden_layers"]
    return (2 * dense_token_params(cfg)
            + 2 * cfg["hidden_size"] * cfg["vocab_size"]
            + layers * mla_decode_call(cfg, ctx)[0])


def prefill_flops(cfg: Dict, n: int) -> int:
    """A prompt of ``n`` tokens without its routed experts: every position
    through every layer's dense work, the naive-form causal attention
    (scores over q/k head dims, the weighted sum of values) and the head
    at the last position only."""
    h, _, _, dn, dr, dv = _mla(cfg)
    pairs = n * (n + 1) // 2
    return (2 * n * dense_token_params(cfg)
            + 2 * cfg["hidden_size"] * cfg["vocab_size"]
            + cfg["num_hidden_layers"] * 2 * h * (dn + dr + dv) * pairs)


def routed_flops(cfg: Dict, held_assignments: int) -> int:
    """The routed experts' work for ``held_assignments`` token-to-held-
    expert assignments."""
    return 2 * expert_params(cfg) * held_assignments
