"""Moonlight-16B-A3B [moe + MLA; hf moonshotai/Moonlight-16B-A3B]: the
DeepSeek-V3 block (arXiv:2412.19437; MLA from arXiv:2405.04434).

27L, d_model=2048, 16 heads, vocab=163840, RMSNorm eps 1e-5, RoPE theta
50,000.  MLA with no q compression: q is 128 nope + 64 rope dims per head;
kv_a 2048 -> 512 latent (RMS-normed) + 64 rope dims shared by every head;
kv_b 512 -> 16 x (128 k_nope + 128 v).  Layer 0 is a dense SwiGLU of
width 11264; layers 1-26 route each token to 6 of 64 experts of width
1408 (sigmoid scores, top 6 of score + selection bias, gates normalized
and x 2.446) beside 2 shared experts (one SwiGLU of width 2816).

This chip holds 8 of each layer's 64 routed experts (offset 0): one
chip of 8-way expert parallelism, attention and the rest replicated.
"""

from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv=16,
    d_head=128,
    d_ff=11264,            # dense first layer width
    vocab=163840,
    n_experts=64,
    topk=6,
    d_ff_expert=1408,
    n_shared_experts=2,
    first_dense=1,
    router="sigmoid",
    routed_scale=2.446,
    experts_held=8,
    expert_offset=0,
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    rope_theta=50000.0,
    norm_eps=1e-5,
)
