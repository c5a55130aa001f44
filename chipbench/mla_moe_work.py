"""Operations and bytes of the DeepSeek-V3 block (latent attention,
routed and shared experts) from a configuration's published keys: the
yardstick of ``decode_roofline.moonlight`` and ``mfu.moonlight``.

Keys as in the model's ``config.json``; ``n_routed_experts`` is the
experts of a layer held on this chip, ``n_routed_experts_published``
the router's width.  Matrix parameters only, as in ``work.py``.
"""

from __future__ import annotations


def _moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def attn_params(cfg: dict) -> int:
    """One layer's q, kv_a, kv_b and o projections."""
    d, h, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d


def expert_params(cfg: dict) -> int:
    """One routed expert's SwiGLU."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _fixed_params(cfg: dict) -> int:
    """What every token multiplies, routed experts aside: attention, the
    dense layers, the shared experts and the router, and the head."""
    d = cfg["hidden_size"]
    shared = 3 * d * cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    router = d * cfg["n_routed_experts_published"]
    return (cfg["num_hidden_layers"] * attn_params(cfg)
            + cfg["first_k_dense_replace"] * 3 * d * cfg["intermediate_size"]
            + _moe_layers(cfg) * (shared + router)
            + d * cfg["vocab_size"])


def held_routed_per_token(cfg: dict) -> float:
    """Routed experts held here that a token reaches in one layer, in
    expectation: its ``num_experts_per_tok`` picks x the held share."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["n_routed_experts_published"])


def params_per_token(cfg: dict) -> float:
    """N here: the parameters one token multiplies on this chip, held
    experts at their expected share."""
    return (_fixed_params(cfg) + _moe_layers(cfg)
            * held_routed_per_token(cfg) * expert_params(cfg))


def weight_params(cfg: dict) -> int:
    """Every matrix this chip holds, the embedding aside."""
    return (_fixed_params(cfg)
            + _moe_layers(cfg) * cfg["n_routed_experts"] * expert_params(cfg))


def latent_bytes_per_token(cfg: dict) -> int:
    """One position's cached latent row over every layer, bf16."""
    return (cfg["num_hidden_layers"] * 2
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]))


def attn_context_flops(cfg: dict, n_pairs: int) -> float:
    """Absorbed attention over ``n_pairs`` (query, cached row) pairs, all
    layers: scores against the latent and rope dims, then the weighted
    sum of latents."""
    r = cfg["kv_lora_rank"]
    return (2.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * (2 * r + cfg["qk_rope_head_dim"]) * n_pairs)


def forward_flops_per_token(cfg: dict) -> float:
    """2 N here (attention over the context not included)."""
    return 2.0 * params_per_token(cfg)


def decode_work(cfg: dict, contexts: list[int]) -> tuple[float, float]:
    """(flops, bytes) of one decode step for live slots whose caches hold
    ``contexts[i]`` positions before the new token: every held weight
    read once, the live latent rows read, the new rows written."""
    b = len(contexts)
    pairs = sum(c + 1 for c in contexts)
    flops = 2.0 * params_per_token(cfg) * b + attn_context_flops(cfg, pairs)
    lat = latent_bytes_per_token(cfg)
    nbytes = (2.0 * (weight_params(cfg) + b * cfg["hidden_size"])
              + lat * sum(contexts) + lat * b)
    return flops, nbytes
