"""The MLA + MoE LLM family: the DeepSeek-V3 block (latent attention,
sigmoid-routed experts beside shared ones) at one chip's share of the
routed experts, served greedily through ``CutieEngine`` over
``LLMExecutor(paged=True)``, as ``dense_llm`` serves its models.

The configuration file holds the model's published ``config.json`` keys;
``n_routed_experts`` is the experts of each MoE layer held here (ids
``expert_offset ..``), ``n_routed_experts_published`` the router's
width.  Weights are made on the device from the seed as ``dense_llm``
makes them, plus the router's selection bias (``SELECTION_BIAS_STD``).
The comparison is ``dense_llm``'s, against ``mla_moe_llm_ref``, with the
mean gap as its one limit (``check``).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import mla_moe_work, seeding
from chipbench.families import dense_llm
from chipbench.families import mla_moe_llm_ref as ref

SELECTION_BIAS_STD = 0.05


def arch(cfg: dict):
    from repro.models.config import ArchConfig

    return ArchConfig(
        name="bench", family="moe", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv=cfg["num_key_value_heads"], d_head=cfg["v_head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        n_experts=cfg["n_routed_experts_published"],
        topk=cfg["num_experts_per_tok"],
        d_ff_expert=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        first_dense=cfg["first_k_dense_replace"], router="sigmoid",
        routed_scale=cfg["routed_scaling_factor"],
        experts_held=cfg["n_routed_experts"],
        expert_offset=cfg["expert_offset"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"])


def dims(cfg: dict) -> ref.Dims:
    return ref.Dims(
        n_heads=cfg["num_attention_heads"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        vocab=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
        eps=float(cfg["rms_norm_eps"]), topk=cfg["num_experts_per_tok"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        expert_offset=cfg["expert_offset"])


def make_params(shapes, key):
    """``dense_llm.make_params``, and each router's selection bias
    N(0, ``SELECTION_BIAS_STD``^2)."""
    kp, kb = jax.random.split(key)
    params = _without_bias(shapes, kp)
    bias = shapes["layers"]["moe"]["router_bias"]
    params["layers"]["moe"]["router_bias"] = (
        SELECTION_BIAS_STD * jax.random.normal(kb, bias.shape)
    ).astype(bias.dtype)
    return params


def _without_bias(shapes, key):
    """``dense_llm.make_params`` over every leaf but the router bias (a
    vector, which has no fan-in)."""
    moe = dict(shapes["layers"]["moe"])
    moe.pop("router_bias")
    layers = dict(shapes["layers"], moe=moe)
    return dense_llm.make_params(dict(shapes, layers=layers), key)


class Family(dense_llm.Family):
    def __init__(self, config: dict, traffic: dict, seed: int, log=print):
        # not ``super().__init__``: dense_llm reads its own key names for
        # its dims; its prompts draw ids below ``vocab``
        self.cfg = dict(config, vocab=config["vocab_size"])
        self.traffic, self.seed, self.log = traffic, seed, log
        self.engine = None
        self.dims = dims(config)
        self.routed: list = []      # (host time, the executor's totals)

    def setup(self, load) -> None:
        from repro.models import transformer as TF
        from repro.serving import CutieEngine, LLMExecutor, ServerConfig

        cfg = arch(self.cfg)
        eng = self.traffic["engine"]
        shapes = jax.eval_shape(lambda k: TF.init_params(cfg, k),
                                jax.random.PRNGKey(0))
        self.params = jax.jit(lambda k: make_params(shapes, k))(
            seeding.key(self.seed))
        self.scfg = ServerConfig(
            max_len=eng["max_len"], n_slots=eng["n_slots"],
            max_new_tokens=eng["max_new_tokens"],
            block_size=eng["block_size"], paged=True, prefix_caching=True)
        self.engine = CutieEngine(eng["scheduler"], trace=False,
                                  history=eng["history"])
        self.engine.register(self.model,
                             LLMExecutor(self.params, cfg, self.scfg))
        warm = self.warm_requests(load)
        for k, (group, prefix, size) in enumerate(warm):
            own = seeding.rng(self.seed, dense_llm.WARM_STREAM, k).integers(
                0, self.cfg["vocab"], size)
            self.engine.submit(np.concatenate(
                [self.prefix(group, prefix), own]).astype(np.int32),
                model=self.model)
        self.log(f"warming prefills of (group, prefix, size) {warm}")
        self.engine.run()
        for h in self.engine.stream(max_steps=0):
            if h.request.error is not None:
                raise RuntimeError(f"warm-up failed: {h.request.error!r}")
        self.engine.evict_completed()

    def resident_contexts(self) -> list:
        """As ``dense_llm``'s, and the executor's device-side routing
        totals at this moment, kept unread (traced runs only)."""
        ex = self.engine.registry[self.model]
        self.routed.append((time.monotonic(), ex.moe_assignments))
        return super().resident_contexts()

    def held_expert_tokens(self, steps: list):
        """Mean routed tokens per held expert per MoE layer per decode
        step over ``steps`` (harness ``Step``s, in order), from the
        executor's totals before the first and after the last; None
        without both."""
        decoding = [s for s in steps if s.live > 0]
        if not decoding:
            return None
        before = [a for t, a in self.routed if t <= steps[0].start]
        after = [a for t, a in self.routed if t >= steps[-1].end]
        if not before:
            return None
        end = after[0] if after else \
            self.engine.registry[self.model].moe_assignments
        held = int(np.asarray(end)[0, 0]) - int(np.asarray(before[-1])[0, 0])
        per = (len(decoding) * self.cfg["n_routed_experts"]
               * (self.cfg["num_hidden_layers"]
                  - self.cfg["first_k_dense_replace"]))
        return held / per

    def _forward(self, req, served, prec, force=None):
        """``ref.forward`` over ``req``'s prompt and served tokens: (logits
        at the positions that chose each served token, every position's
        routed ids, those positions)."""
        prompt = self.prompt(req)
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        padded = np.zeros(ref.bucket(len(seq)), np.int32)
        padded[:len(seq)] = seq
        rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
        logits, ids = ref.forward(self.params, jnp.asarray(padded),
                                  jnp.asarray(rows), self.dims, prec, force)
        return np.asarray(logits), ids, rows

    def _logits(self, req, served, low=False):
        return self._forward(req, served, "f8" if low else "f32")[0]

    def check(self, done: list) -> list:
        """The mean gap decides; the widest is logged, not gated: a
        bug-free bf16 computation reads it as wide as the float8 control
        does (``witness``)."""
        got = self.readings(self.gaps(done))
        self.log(f"reading logit_gap_max: {got['logit_gap_max']!r} "
                 "(not a limit)")
        return [("logit_gap_mean", got["logit_gap_mean"],
                 float(self.traffic["logit_gap_mean_limit"]))]

    def control_readings(self, done: list) -> dict:
        return {**super().control_readings(done), **self.witness(done)}

    def witness(self, done: list) -> dict:
        """What bf16 rounding alone does to the gaps, on the compared
        answers: the reference's bf16 twin (no cache, no absorbed decode,
        no batching) free, and with each routed layer pinned to the
        float32 reference's choice; the share of compared positions where
        the twin chose another expert set in some routed layer; and at the
        program's widest gap, the reference's margin between its two best
        logits and the routed layers where the twin chose otherwise."""
        gaps = {"program": [], "bf16": [], "bf16_pinned": []}
        flips, worst = [], (-1.0, 0.0, 0)
        for req, served in self.sample(done):
            want, ids, rows = self._forward(req, served, "f32")
            twin, twin_ids, _ = self._forward(req, served, "bf16")
            pinned, _, _ = self._forward(req, served, "bf16", ids)
            at, best = np.arange(len(served)), want.max(-1)
            for name, tok in (("program", np.asarray(served)),
                              ("bf16", twin.argmax(-1)),
                              ("bf16_pinned", pinned.argmax(-1))):
                gaps[name].append(best - want[at, tok])
            differ = (np.sort(np.asarray(ids)[:, rows], -1)
                      != np.sort(np.asarray(twin_ids)[:, rows], -1)
                      ).any(-1).sum(0)
            flips.append(differ)
            j = int(gaps["program"][-1].argmax())
            if gaps["program"][-1][j] > worst[0]:
                top = np.sort(want[j])
                worst = (float(gaps["program"][-1][j]),
                         float(top[-1] - top[-2]), int(differ[j]))
        out = {}
        for name, g in gaps.items():
            got = self.readings(np.concatenate([np.zeros(0)] + g))
            out.update({f"{name}_{k}": v for k, v in got.items()})
        flips = np.concatenate([np.zeros(0)] + flips)
        out["bf16_flip_share"] = float((flips > 0).mean()) if len(flips) \
            else None
        out["program_worst_top2_margin"] = worst[1]
        out["program_worst_bf16_flip_layers"] = worst[2]
        return out

    def decode_needed_s(self, contexts: list, peaks: dict) -> float:
        flops, nbytes = mla_moe_work.decode_work(self.cfg, contexts)
        return max(flops / peaks["bf16_flops_per_s"],
                   nbytes / peaks["hbm_bytes_per_s"])
