"""Model assembly for every assigned architecture family.

Families:
  dense   — decoder-only GQA transformer (internlm2 / llama3.2 / codeqwen /
            qwen2.5; also the llava backbone),
  moe     — dense attention + MoE FFN (deepseek-moe w/ leading dense layers
            and shared experts; qwen3-moe w/ qk-norm),
  ssm     — mamba2 SSD stack,
  hybrid  — zamba2: mamba2 backbone + ONE shared attention+MLP block applied
            every `attn_every` layers (weight re-use is the point of zamba),
  encdec  — whisper: audio encoder (frontend stub: precomputed frames) +
            causal text decoder with cross-attention,
  vlm     — llava: vision stub (precomputed patch embeddings) + mm projector
            + mistral-style dense backbone.

All stacks run under `maybe_scan` (lax.scan over stacked layer params, or a
trace-time unroll for the dry-run cost pass).  Remat policy per block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import common as C
from repro.models import losses, mamba2, mla, mlp, moe
from repro.models.common import BATCH, MODEL, maybe_scan, shard
from repro.models.config import ArchConfig

Array = jax.Array


def vocab_padded(cfg: ArchConfig) -> int:
    return -(-cfg.vocab // 256) * 256


# ---------------------------------------------------------------------------
# Per-family block init/apply
# ---------------------------------------------------------------------------


def _norm_init(cfg, d=None):
    d = d or cfg.d_model
    return (C.rmsnorm_init(d) if cfg.norm == "rmsnorm"
            else C.layernorm_init(d))


def _norm(cfg, p, x):
    if cfg.norm == "rmsnorm":
        return C.rmsnorm(p, x, eps=cfg.norm_eps, bf16_mul=cfg.norm_bf16_mul)
    return C.layernorm(p, x)


def _attn_init(key, cfg):
    """A block's self-attention: latent (MLA) or GQA."""
    return mla.init(key, cfg) if cfg.kv_lora_rank else attn.init(key, cfg)


def dense_block_init(key, cfg):
    ks = jax.random.split(key, 2)
    return {"ln1": _norm_init(cfg), "attn": _attn_init(ks[0], cfg),
            "ln2": _norm_init(cfg), "mlp": mlp.init(ks[1], cfg)}


def _self_attention(p, x, cfg, positions, unroll, causal=True, rope=True):
    if cfg.kv_lora_rank:
        return mla.attention(p, x, cfg, positions=positions,
                             unroll=unroll)[0]
    return attn.attention(p, x, cfg, positions=positions, causal=causal,
                          rope=rope, unroll=unroll)[0]


def dense_block(p, x, cfg, positions, *, unroll=False, causal=True,
                rope=True):
    x = x + _self_attention(p["attn"], _norm(cfg, p["ln1"], x), cfg,
                            positions, unroll, causal, rope)
    x = x + mlp.apply(p["mlp"], _norm(cfg, p["ln2"], x), cfg)
    return x


def moe_block_init(key, cfg):
    ks = jax.random.split(key, 2)
    return {"ln1": _norm_init(cfg), "attn": _attn_init(ks[0], cfg),
            "ln2": _norm_init(cfg), "moe": moe.init(ks[1], cfg)}


def moe_block(p, x, cfg, positions, *, unroll=False):
    x = x + _self_attention(p["attn"], _norm(cfg, p["ln1"], x), cfg,
                            positions, unroll)
    y, aux = moe.apply(p["moe"], _norm(cfg, p["ln2"], x), cfg)
    return x + y, aux


def ssm_block_init(key, cfg):
    return {"ln": _norm_init(cfg), "mixer": mamba2.init(key, cfg)}


def ssm_block(p, x, cfg, *, unroll=False):
    return x + mamba2.apply(p["mixer"], _norm(cfg, p["ln"], x), cfg,
                            unroll=unroll)


def shared_attn_block_init(key, cfg):
    """Zamba2's single shared transformer block (attn + MLP)."""
    ks = jax.random.split(key, 2)
    return {"ln1": _norm_init(cfg), "attn": attn.init(ks[0], cfg),
            "ln2": _norm_init(cfg), "mlp": mlp.init(ks[1], cfg)}


# ---------------------------------------------------------------------------
# Parameter init (whole model)
# ---------------------------------------------------------------------------


def _stack_init(fn, key, n):
    return jax.vmap(fn)(jax.random.split(key, n))


def init_params(cfg: ArchConfig, key) -> dict:
    ks = jax.random.split(key, 8)
    vp = vocab_padded(cfg)
    p: dict = {"embed": C.embed_init(ks[0], (vp, cfg.d_model)),
               "ln_f": _norm_init(cfg)}
    if not cfg.tie_embeddings:
        p["head"] = C.dense_init(ks[1], (cfg.d_model, vp))

    if cfg.family in ("dense", "vlm"):
        p["layers"] = _stack_init(
            lambda k: dense_block_init(k, cfg), ks[2], cfg.n_layers)
        if cfg.family == "vlm":
            mm1 = C.linear_init(ks[3], cfg.d_vision, cfg.d_model)
            mm2 = C.linear_init(ks[4], cfg.d_model, cfg.d_model)
            p["mm_proj"] = {"fc1": mm1, "fc2": mm2}
    elif cfg.family == "moe":
        if cfg.first_dense:
            dense_cfg = cfg.replace(d_ff=cfg.d_ff or 4 * cfg.d_model)
            p["dense_layers"] = _stack_init(
                lambda k: dense_block_init(k, dense_cfg), ks[3],
                cfg.first_dense)
        p["layers"] = _stack_init(
            lambda k: moe_block_init(k, cfg), ks[2],
            cfg.n_layers - cfg.first_dense)
    elif cfg.family == "ssm":
        p["layers"] = _stack_init(
            lambda k: ssm_block_init(k, cfg), ks[2], cfg.n_layers)
    elif cfg.family == "hybrid":
        p["layers"] = _stack_init(
            lambda k: ssm_block_init(k, cfg), ks[2], cfg.n_layers)
        p["shared_attn"] = shared_attn_block_init(ks[3], cfg)
    elif cfg.family == "encdec":
        enc_cfg = cfg
        p["enc_pos"] = C.embed_init(ks[5], (cfg.enc_seq, cfg.d_model))
        p["dec_pos"] = None  # decoder uses rope-free learned pos below
        p["enc_layers"] = _stack_init(
            lambda k: dense_block_init(k, enc_cfg), ks[3], cfg.enc_layers)
        p["ln_enc"] = _norm_init(cfg)

        def dec_init(k):
            k1, k2, k3 = jax.random.split(k, 3)
            return {"ln1": _norm_init(cfg), "attn": attn.init(k1, cfg),
                    "lnx": _norm_init(cfg), "xattn": attn.init(k2, cfg),
                    "ln2": _norm_init(cfg), "mlp": mlp.init(k3, cfg)}

        p["layers"] = _stack_init(dec_init, ks[2], cfg.n_layers)
    else:
        raise ValueError(cfg.family)
    return p


def head_weight(p, cfg):
    return p["embed"].T if cfg.tie_embeddings else p["head"]


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------


def _remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    policy = (None if cfg.remat == "full"
              else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn, policy=policy) if policy else jax.checkpoint(fn)


def _embed(p, tokens, cfg):
    x = p["embed"][tokens]          # gather over vocab-sharded table
    return shard(x.astype(jnp.bfloat16), BATCH, None, None)


def backbone(p, x, cfg, positions, *, unroll=False, collect_aux=True):
    """Run the layer stack.  Returns (hidden, aux_losses)."""
    aux0 = {"lb_loss": jnp.float32(0), "z_loss": jnp.float32(0)}

    if cfg.family in ("dense", "vlm"):
        def body(carry, lp):
            y = _remat(cfg, functools.partial(
                dense_block, cfg=cfg, positions=positions,
                unroll=unroll))(lp, carry)
            return y, None
        x, _ = maybe_scan(lambda c, lp: body(c, lp), x, p["layers"],
                          unroll=unroll)
        return x, aux0

    if cfg.family == "moe":
        if cfg.first_dense:
            dense_cfg = cfg.replace(d_ff=cfg.d_ff or 4 * cfg.d_model)

            def dbody(carry, lp):
                return _remat(cfg, functools.partial(
                    dense_block, cfg=dense_cfg, positions=positions,
                    unroll=unroll))(lp, carry), None
            x, _ = maybe_scan(dbody, x, p["dense_layers"], unroll=unroll)

        def mbody(carry, lp):
            x, aux = carry
            y, a = _remat(cfg, functools.partial(
                moe_block, cfg=cfg, positions=positions,
                unroll=unroll))(lp, x)
            aux = jax.tree.map(jnp.add, aux, a)
            return (y, aux), None
        (x, aux), _ = maybe_scan(mbody, (x, aux0), p["layers"],
                                 unroll=unroll)
        return x, aux

    if cfg.family == "ssm":
        def body(carry, lp):
            return _remat(cfg, functools.partial(
                ssm_block, cfg=cfg, unroll=unroll))(lp, carry), None
        x, _ = maybe_scan(body, x, p["layers"], unroll=unroll)
        return x, aux0

    if cfg.family == "hybrid":
        period = cfg.attn_every
        ssm_fn = _remat(cfg, functools.partial(
            ssm_block, cfg=cfg, unroll=unroll))
        attn_fn = _remat(cfg, functools.partial(
            dense_block, cfg=cfg, positions=positions, unroll=unroll))

        if unroll:
            for i in range(cfg.n_layers):
                lp = C.tree_index(p["layers"], i)
                x = ssm_fn(lp, x)
                if (i + 1) % period == 0:
                    x = attn_fn(p["shared_attn"], x)
            return x, aux0

        def body(carry, inp):
            i, lp = inp
            x = ssm_fn(lp, carry)
            x = jax.lax.cond((i + 1) % period == 0,
                             lambda h: attn_fn(p["shared_attn"], h),
                             lambda h: h, x)
            return x, None
        idx = jnp.arange(cfg.n_layers)
        x, _ = maybe_scan(body, x, (idx, p["layers"]), unroll=False)
        return x, aux0

    raise ValueError(cfg.family)


def encode(p, frames, cfg, *, unroll=False):
    """Whisper encoder over precomputed conv-frontend frames (stub input)."""
    x = frames.astype(jnp.bfloat16) + p["enc_pos"][None, : frames.shape[1]]
    x = shard(x, BATCH, None, None)
    positions = jnp.arange(frames.shape[1])[None]

    def body(carry, lp):
        y = _remat(cfg, functools.partial(
            dense_block, cfg=cfg, positions=positions, unroll=unroll,
            causal=False, rope=False))(lp, carry)
        return y, None
    x, _ = maybe_scan(body, x, p["enc_layers"], unroll=unroll)
    return _norm(cfg, p["ln_enc"], x)


def decode_stack_encdec(p, x, enc_out, cfg, positions, *, unroll=False):
    def body(carry, lp):
        def blk(lp, h):
            a, _ = attn.attention(lp["attn"], _norm(cfg, lp["ln1"], h), cfg,
                                  positions=positions, causal=True,
                                  rope=True, unroll=unroll)
            h = h + a
            # cross-attention: kv from encoder output
            kvh = _xattn_kv(lp["xattn"], enc_out, cfg)
            a, _ = attn.attention(lp["xattn"], _norm(cfg, lp["lnx"], h), cfg,
                                  positions=positions, causal=False,
                                  rope=False, kv_override=kvh,
                                  unroll=unroll)
            h = h + a
            return h + mlp.apply(lp["mlp"], _norm(cfg, lp["ln2"], h), cfg)
        return _remat(cfg, blk)(lp, carry), None

    x, _ = maybe_scan(body, x, p["layers"], unroll=unroll)
    return x


def _xattn_kv(pattn, enc_out, cfg):
    b, t, _ = enc_out.shape
    hk, dh = cfg.n_kv, cfg.d_head
    k = C.linear(pattn["wk"], enc_out, quant=cfg.quant).reshape(b, t, hk, dh)
    v = C.linear(pattn["wv"], enc_out, quant=cfg.quant).reshape(b, t, hk, dh)
    return k, v


def forward_loss(p, batch, cfg, *, unroll=False):
    """Training forward -> (scalar loss, metrics).  ``batch`` fields depend
    on the family (tokens/labels, + frames for encdec, + patches for vlm)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = jnp.arange(s)[None]
    mask = None

    if cfg.family == "encdec":
        enc_out = encode(p, batch["frames"], cfg, unroll=unroll)
        x = _embed(p, tokens, cfg)
        x = decode_stack_encdec(p, x, enc_out, cfg, positions,
                                unroll=unroll)
        aux = {"lb_loss": jnp.float32(0), "z_loss": jnp.float32(0)}
    elif cfg.family == "vlm":
        img = batch["patches"].astype(jnp.bfloat16)
        img = C.linear(p["mm_proj"]["fc1"], img)
        img = C.linear(p["mm_proj"]["fc2"], jax.nn.gelu(img))
        x = jnp.concatenate([img, _embed(p, tokens, cfg)], axis=1)
        x = shard(x, BATCH, None, None)
        s_full = x.shape[1]
        positions = jnp.arange(s_full)[None]
        x, aux = backbone(p, x, cfg, positions, unroll=unroll)
        # loss only on text positions
        x = x[:, img.shape[1]:]
    else:
        x = _embed(p, tokens, cfg)
        x, aux = backbone(p, x, cfg, positions, unroll=unroll)

    x = _norm(cfg, p["ln_f"], x)
    labels = batch["labels"]
    loss, cnt = losses.chunked_xent(
        x, head_weight(p, cfg), labels, chunk=cfg.loss_chunk,
        unroll=unroll, mask=mask)
    total = loss + 1e-2 * aux["lb_loss"] + 1e-3 * aux["z_loss"]
    return total, {"xent": loss, **aux, "tokens": cnt}


def forward_logits(p, batch, cfg, *, unroll=False):
    """Prefill forward -> last-position logits (serving path)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = jnp.arange(s)[None]
    if cfg.family == "encdec":
        enc_out = encode(p, batch["frames"], cfg, unroll=unroll)
        x = _embed(p, tokens, cfg)
        x = decode_stack_encdec(p, x, enc_out, cfg, positions,
                                unroll=unroll)
    else:
        x = _embed(p, tokens, cfg)
        x, _ = backbone(p, x, cfg, positions, unroll=unroll)
    x = _norm(cfg, p["ln_f"], x[:, -1:])
    logits = x @ head_weight(p, cfg)
    return shard(logits, BATCH, None, MODEL)
