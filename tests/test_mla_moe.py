"""Moonlight-16B-A3B's mechanisms at a small size on the CPU: latent
attention over the paged latent cache, the sigmoid-routed held share of
the experts, and the routing counter, against the float32 reference
(`repro.models.mla_moe_ref`) on seeded weights.

Tolerances, with their reasons:

* logits, served against the reference: the program keeps weights,
  activations and the latent cache in bf16 (relative rounding 2^-8), so
  over three small layers most positions agree to a few hundredths of
  logits of size ~3 (``CLOSE``).  A token whose router scores tie within
  that rounding can take another expert than in the reference; that
  moves its logits, and its successors', by up to ~1.5.  So nine in ten
  positions must lie within ``CLOSE`` and every one within ``FLIP``.
* the held shares against the uncut layer: the same bf16 rounding on one
  layer, with routing decided in float32 on identical inputs in both.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.models import mla_moe_ref as ref
from repro.models import mlp, moe
from repro.models import transformer as TF
from repro.models.config import reduce_for_smoke
from repro.serving import CutieEngine, LLMExecutor, ServerConfig
from repro.serving.blocks import KVPagedStore

CLOSE, FLIP = 0.1, 2.0


def _cfg(**kw):
    """One dense and two routed layers; 2 of 16 experts held (ids 4, 5),
    4 chosen per token."""
    base = reduce_for_smoke(configs.get("moonlight_16b_a3b"))
    return base.replace(**{**dict(n_layers=3, n_experts=16, topk=4,
                                  experts_held=2, expert_offset=4), **kw})


def _params(cfg, seed=0):
    p = TF.init_params(cfg, jax.random.PRNGKey(seed))
    bias = p["layers"]["moe"]["router_bias"]
    p["layers"]["moe"]["router_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), bias.shape)
    return p


def _dims(cfg):
    return ref.Dims(cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_dim,
                    cfg.qk_rope_dim, cfg.v_head_dim, cfg.vocab,
                    cfg.rope_theta, cfg.norm_eps, cfg.topk, cfg.routed_scale,
                    cfg.expert_offset)


class _Recording(LLMExecutor):
    """Keeps every logits row the executor samples from."""

    def _sample(self, lg):
        self.seen.append(np.asarray(lg, np.float32))
        return super()._sample(lg)


def _serve(params, cfg, prompts, steps, n_slots=2, **kw):
    """Prefill ``prompts`` into slots 0.., decode ``steps`` tokens; returns
    per prompt (its tokens, the logits each was sampled from)."""
    ex = _Recording(params, cfg, ServerConfig(
        n_slots=n_slots, max_len=64, max_new_tokens=steps + 1, block_size=8,
        **kw))
    ex.seen = []
    toks, lgs, hits = [], [], []
    for uid, pr in enumerate(prompts):
        res = ex.prefill(uid, np.asarray(pr, np.int32))
        toks.append([res.first_token])
        lgs.append([ex.seen[-1][0]])
        hits.append(res.prefix.common_prefix_tokens)
    for _ in range(steps):
        nxt = np.asarray(ex.decode())
        for i in range(len(prompts)):
            toks[i].append(int(nxt[i]))
            lgs[i].append(ex.seen[-1][i])
    return [(t, np.stack(g)[:, :cfg.vocab]) for t, g in zip(toks, lgs)], \
        hits


def _reference(params, cfg, prompt, toks):
    seq = np.concatenate([prompt, toks[:-1]]).astype(np.int32)
    padded = np.zeros(ref.bucket(len(seq), 64), np.int32)
    padded[:len(seq)] = seq
    rows = np.arange(len(prompt) - 1, len(seq))
    return np.asarray(ref.logits_at(params, jnp.asarray(padded),
                                    jnp.asarray(rows), _dims(cfg)))


def _assert_close(got, want):
    err = np.abs(got - want).max(-1)
    assert np.mean(err <= CLOSE) >= 0.9, err
    assert err.max() <= FLIP, err


def test_prefill_then_paged_decode_matches_the_reference():
    cfg = _cfg()
    params = _params(cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, n) for n in (13, 30)]
    served, _ = _serve(params, cfg, prompts, steps=8)
    for pr, (toks, got) in zip(prompts, served):
        _assert_close(got, _reference(params, cfg, pr, toks))


def test_prefix_hit_reads_the_cached_latent_rows():
    cfg = _cfg()
    params = _params(cfg, seed=3)
    rng = np.random.default_rng(1)
    shared = rng.integers(0, cfg.vocab, 16)
    prompts = [np.concatenate([shared, rng.integers(0, cfg.vocab, n)])
               for n in (3, 7)]
    served, hits = _serve(params, cfg, prompts, steps=6)
    assert hits == [0, 16]
    for pr, (toks, got) in zip(prompts, served):
        _assert_close(got, _reference(params, cfg, pr, toks))


@pytest.mark.parametrize("held", [2, 16])
def test_held_shares_add_up_to_the_uncut_layer(held):
    """Shares of ``held`` experts each (2: every held expert over every
    token; 16, the whole layer: grouped matmuls), the shared expert
    counted once, give what the reference gives for the layer with all
    16."""
    cfg = _cfg(experts_held=held)
    whole = cfg.replace(experts_held=0, expert_offset=0)
    p = moe.init(jax.random.PRNGKey(5), whole)
    p["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(6), (16,))
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 5, cfg.d_model),
                          jnp.float32).astype(jnp.bfloat16)
    shared = mlp.apply(p["shared"], x, cfg).astype(jnp.float32)
    total = -(16 // held - 1) * shared
    for off in range(0, 16, held):
        part = {**p, **{n: p[n][off:off + held]
                        for n in ("gate_proj", "up_proj", "down_proj")}}
        y, _ = moe.apply_held(part, x, cfg.replace(expert_offset=off))
        total = total + y.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.moe_layer(x.reshape(15, -1).astype(jnp.float32), p,
                             _dims(whole))
    got = np.asarray(total).reshape(15, -1)
    scale = np.abs(np.asarray(want)).max()
    np.testing.assert_allclose(got, np.asarray(want), atol=0.03 * scale)


@pytest.mark.parametrize("held", [2, 16])
def test_logits_do_not_depend_on_batch_mates(held):
    """2 held: every held expert over every token; 16: grouped."""
    cfg = _cfg(experts_held=held, expert_offset=4 if held == 2 else 0)
    params = _params(cfg, seed=8)
    rng = np.random.default_rng(2)
    mine = rng.integers(0, cfg.vocab, 21)
    others = [rng.integers(0, cfg.vocab, n) for n in (9, 33, 17)]
    (alone,), _ = _serve(params, cfg, [mine], steps=6, n_slots=4)
    crowded, _ = _serve(params, cfg, [mine] + others, steps=6, n_slots=4)
    assert alone[0] == crowded[0][0]
    np.testing.assert_array_equal(alone[1], crowded[0][1])


def test_selection_bias_moves_the_choice_not_the_gates():
    cfg = _cfg()
    p = moe.init(jax.random.PRNGKey(9), cfg)
    xt = jax.random.normal(jax.random.PRNGKey(10), (6, cfg.d_model))
    gates, idx, _, scores = moe.route(p, xt, cfg)
    # a shift common to every expert chooses the same, at the same gates
    shifted = {**p, "router_bias": p["router_bias"] + 0.3}
    g2, i2, _, _ = moe.route(shifted, xt, cfg)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(gates), np.asarray(g2))
    # a large bias on expert 7 makes every token choose it; its gate is
    # still its unbiased score over the chosen scores' sum
    boost = {**p, "router_bias": p["router_bias"].at[7].set(10.0)}
    g3, i3, _, _ = moe.route(boost, xt, cfg)
    assert (np.asarray(i3) == 7).any(-1).all()
    s = np.asarray(scores)
    chosen = np.take_along_axis(s, np.asarray(i3), -1)
    want = chosen / chosen.sum(-1, keepdims=True) * cfg.routed_scale
    np.testing.assert_allclose(np.asarray(g3), want, rtol=1e-6)


def test_latent_pages_hold_one_row_per_position():
    full = configs.get("moonlight_16b_a3b")
    st = KVPagedStore(full.n_layers, 2, 16, full.n_kv, full.d_head,
                      latent_dim=full.latent_dim)
    assert st.pages["lat"].shape == (27, 2, 16, 576)
    assert st.bytes_per_block() == 16 * 31104       # 27 x 576 x 2 B
    with pytest.raises(ValueError, match="MLA"):
        KVPagedStore(2, 2, 16, 4, 16, codec_name="trit", latent_dim=40)


def test_routing_counter_counts_every_live_pick():
    cfg = _cfg()
    params = _params(cfg, seed=11)
    eng = CutieEngine("fcfs")
    ex = LLMExecutor(params, cfg, ServerConfig(
        n_slots=2, max_len=64, max_new_tokens=5, block_size=8))
    eng.register("llm", ex)
    rng = np.random.default_rng(3)
    for n in (10, 19, 26):
        eng.submit(rng.integers(0, cfg.vocab, n), model="llm")
    eng.run()
    series = eng.obs.metrics.snapshot()["moe_assignments_total"]["series"]

    def count(site, share):
        return series[f'{{model="llm",share="{share}",site="{site}"}}']

    picks = cfg.topk * (cfg.n_layers - cfg.first_dense)
    assert count("prefill", "held") + count("prefill", "elsewhere") == \
        (10 + 19 + 26) * picks
    # 4 decoded tokens per request (the first comes from its prefill)
    assert count("decode", "held") + count("decode", "elsewhere") == \
        3 * 4 * picks
    assert 0 < count("decode", "held") < count("decode", "elsewhere")
