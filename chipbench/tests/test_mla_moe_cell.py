"""The MLA + MoE LLM cell at a toy size on the CPU: the float32
reference agrees with ``LLMExecutor``'s paged prefill-then-decode within
the limits, the float8 control misses them, and the per-layer readers
find what they read in a traced-like run."""

from chipbench import mla_moe_work
from chipbench.tests import tiny

# published keys at toy widths: 1 dense + 2 routed layers, 2 of 8
# experts held (offset 2), 2 chosen per token, one shared expert
MLA_MOE = {"family": "mla_moe_llm", "num_hidden_layers": 3,
           "first_k_dense_replace": 1, "hidden_size": 64,
           "num_attention_heads": 4, "num_key_value_heads": 4,
           "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
           "v_head_dim": 16, "intermediate_size": 128,
           "moe_intermediate_size": 32, "n_shared_experts": 1,
           "n_routed_experts": 2, "n_routed_experts_published": 8,
           "expert_offset": 2, "num_experts_per_tok": 2,
           "routed_scaling_factor": 2.446, "vocab_size": 300,
           "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
           "tie_word_embeddings": False}
# the limit: bf16 weights, activations and latent cache put most served
# logits within a few hundredths of the float32 reference's; a token whose
# router scores tie within that rounding can take another expert than in
# the reference, which moves its logits (and its successors') by tenths,
# so the widest gap is not a limit; the mean gap, which many small errors
# set, catches float8 (sound 0.004-0.019, control 0.09-0.20)
TRAFFIC = dict(tiny.LLM_TRAFFIC, logit_gap_mean_limit=0.05)


def test_served_tokens_agree_with_the_reference():
    out = tiny.run(tiny.cell(MLA_MOE, TRAFFIC, [("tokens_per_s", "tokens/s")]),
                   seconds=2.0)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 8
    assert set(out["checks"]) == {"logit_gap_mean", "answers_missing"}
    assert out["checks"]["logit_gap_mean"]["value"] <= 0.05
    assert out["metrics"]["tokens_per_s"]["value"] > 0


def test_float8_control_fails_the_comparison():
    """The float8 control misses the limit; the reference's bf16 twin,
    free and with its routing pinned to the float32 reference's, passes
    it, and reads the program's widest gap beside it."""
    out = tiny.run(tiny.cell(MLA_MOE, TRAFFIC), seconds=1.0, control=True)
    got = out["control"]
    assert got["logit_gap_mean"] > 0.05
    assert got["bf16_logit_gap_mean"] <= 0.05
    assert got["bf16_pinned_logit_gap_mean"] <= 0.05
    assert got["program_logit_gap_mean"] == \
        out["checks"]["logit_gap_mean"]["value"]
    assert 0 <= got["bf16_flip_share"] <= 1
    assert got["program_worst_top2_margin"] >= 0


def test_work_counts_held_experts_at_their_share():
    cfg = MLA_MOE
    n = mla_moe_work.params_per_token(cfg)
    expert = 3 * 64 * 32
    # 2 routed layers x 2 picks x 2/8 held x one expert
    assert n - mla_moe_work._fixed_params(cfg) == 2 * 2 * (2 / 8) * expert
    flops, nbytes = mla_moe_work.decode_work(cfg, [10, 20])
    assert nbytes == (2 * (mla_moe_work.weight_params(cfg) + 2 * 64)
                      + 3 * 2 * 40 * (30 + 2))
    assert flops > 2 * n * 2


def test_held_expert_tokens_reads_the_routing_totals():
    """Over a window, the routing totals the reader takes count each live
    token's picks in each routed layer once, the held ones among them."""
    import numpy as np

    from chipbench import harness
    from chipbench import load as load_mod

    seed = 2**40 + 11
    drv = harness.family_class("mla_moe_llm")(MLA_MOE, TRAFFIC, seed,
                                               log=lambda *a: None)
    load = load_mod.Load(TRAFFIC, seed)
    drv.setup(load)
    win = harness.Window(drv, load, contexts=True)
    t0, t1 = win.run(1.0)
    assert all(t <= win.steps[-1].start for t, _ in drv.routed)
    steps = [s for s in win.steps if t0 < s.end <= t1]
    got = drv.held_expert_tokens(steps)
    before = [a for t, a in drv.routed if t <= steps[0].start][-1]
    after = drv.engine.registry[drv.model].moe_assignments   # no step since
    moved = np.asarray(after) - np.asarray(before)
    live = sum(s.live for s in steps)
    assert moved[0].sum() == live * 2 * 2        # picks x routed layers
    assert got == moved[0, 0] / (len(steps) * 2 * 2)
    assert 0 < got < live / len(steps)
