"""Serving paths: prefix-aware prefill and single-token decode steps.

Cache layout: stacked over layers, (L, B, T, Hk, Dh), with the sequence
axis sharded over `model` (flash-decoding; see models.attention).  MLA
archs (``cfg.kv_lora_rank``) cache one latent row per position instead,
``{"lat": (L, B, T, kv_lora_rank + qk_rope_dim)}`` (models.mla).  SSM
archs carry (L, B, H, P, N) states + conv buffers instead — constant in
sequence length, which is what makes the long_500k cell tractable.

MoE layers run the held-share, no-drop dispatch (``moe.apply_held``);
prefill, and the decode step with ``with_routes``, also return the
expert ids that each token chose in each routed layer,
``(L_moe, B, S, k)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import common as C
from repro.models import mamba2, mla, mlp, moe
from repro.models import transformer as TF
from repro.models.common import BATCH, MODEL, maybe_scan, shard
from repro.models.config import ArchConfig


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------


def init_caches(cfg: ArchConfig, batch: int, max_len: int):
    hk, dh = cfg.n_kv, cfg.d_head
    kdt = jnp.dtype(cfg.kv_dtype)
    kv = lambda n: {  # noqa: E731
        "k": jnp.zeros((n, batch, max_len, hk, dh), kdt),
        "v": jnp.zeros((n, batch, max_len, hk, dh), kdt),
    }
    if cfg.kv_lora_rank:
        return {"kv": {"lat": jnp.zeros(
            (cfg.n_layers, batch, max_len, cfg.latent_dim), kdt)}}
    if cfg.family in ("dense", "vlm", "moe"):
        return {"kv": kv(cfg.n_layers)}
    stack = lambda st: jax.tree.map(  # noqa: E731
        lambda a: jnp.broadcast_to(a, (cfg.n_layers, *a.shape)), st)
    if cfg.family == "ssm":
        return {"ssm": stack(mamba2.init_state(cfg, batch))}
    if cfg.family == "hybrid":
        n_attn = cfg.n_layers // cfg.attn_every
        return {"ssm": stack(mamba2.init_state(cfg, batch)),
                "kv": kv(n_attn)}
    if cfg.family == "encdec":
        enc = {
            "k": jnp.zeros((cfg.n_layers, batch, cfg.enc_seq, hk, dh), kdt),
            "v": jnp.zeros((cfg.n_layers, batch, cfg.enc_seq, hk, dh), kdt),
        }
        return {"kv": kv(cfg.n_layers), "cross": enc}
    raise ValueError(cfg.family)


def cache_pspecs(cfg: ArchConfig):
    """PartitionSpecs matching init_caches (seq over model)."""
    from jax.sharding import PartitionSpec as P
    kvspec = {"k": P(None, BATCH, MODEL, None, None),
              "v": P(None, BATCH, MODEL, None, None)}
    if cfg.kv_lora_rank:
        return {"kv": {"lat": P(None, BATCH, MODEL, None)}}
    if cfg.family in ("dense", "vlm", "moe"):
        return {"kv": kvspec}
    ssm_spec = {"conv_x": P(None, BATCH, None, MODEL),
                "conv_b": P(None, BATCH, None, None),
                "conv_c": P(None, BATCH, None, None),
                "ssm": P(None, BATCH, MODEL, None, None)}
    if cfg.family == "ssm":
        return {"ssm": ssm_spec}
    if cfg.family == "hybrid":
        return {"ssm": ssm_spec, "kv": kvspec}
    if cfg.family == "encdec":
        return {"kv": kvspec, "cross": kvspec}
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# Decode step (one token)
# ---------------------------------------------------------------------------


def decode_step(p, token, caches, pos, cfg: ArchConfig, *, unroll=False,
                frames_cache=None, with_routes=False):
    """token (B, 1) int32; pos scalar int32.  Returns (logits, new caches),
    and with ``with_routes`` the routed expert ids (L_moe, B, 1, k) last
    (attention families only)."""
    x = TF._embed(p, token, cfg)
    routes = None

    if cfg.family in ("dense", "vlm", "moe"):
        x, kv, routes = _decode_attn_stack(p, x, caches["kv"], pos, cfg,
                                           unroll)
        new_caches = {"kv": kv}
    elif cfg.family == "ssm":
        x, st = _decode_ssm_stack(p, x, caches["ssm"], cfg, unroll)
        new_caches = {"ssm": st}
    elif cfg.family == "hybrid":
        x, st, kv = _decode_hybrid_stack(
            p, x, caches["ssm"], caches["kv"], pos, cfg, unroll)
        new_caches = {"ssm": st, "kv": kv}
    elif cfg.family == "encdec":
        x, kv = _decode_encdec_stack(
            p, x, caches["kv"], caches["cross"], pos, cfg, unroll)
        new_caches = {"kv": kv, "cross": caches["cross"]}
    else:
        raise ValueError(cfg.family)

    x = TF._norm(cfg, p["ln_f"], x)
    logits = x @ TF.head_weight(p, cfg)
    out = shard(logits, BATCH, None, MODEL), new_caches
    return out + (routes,) if with_routes else out


def _ffn(lp, xn, cfg):
    """A block's FFN: ``(y, routed expert ids)``, the ids None for a
    dense layer."""
    if "moe" in lp:
        return moe.apply_held(lp["moe"], xn, cfg)
    return mlp.apply(lp["mlp"], xn, cfg), None


def _run_stacks(body, x, p, cfg, caches, unroll):
    """Scan ``body(carry, (layer params, layer caches))`` over every layer
    stack; returns ``(x, per-layer outputs concatenated over the stacks,
    routes of the last stack)``.  A MoE model's leading dense layers use
    the first cache slots; each stack indexes the whole cache per layer
    rather than scanning a sliced copy of it."""
    nd = cfg.first_dense if cfg.family == "moe" else 0
    if not nd:
        x, (out, routes) = maybe_scan(body, x, (p["layers"], caches),
                                      unroll=unroll)
        return x, out, routes

    def indexed(carry, inp):
        lp, layer = inp
        return body(carry, (lp, jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False),
            caches)))

    outs = []
    for layers, first in ((p["dense_layers"], 0), (p["layers"], nd)):
        n = jax.tree.leaves(layers)[0].shape[0]
        x, (out, routes) = maybe_scan(
            indexed, x, (layers, first + jnp.arange(n)), unroll=unroll)
        outs.append(out)
    return x, jax.tree.map(lambda *a: jnp.concatenate(a), *outs), routes


def _decode_attn_stack(p, x, kv, pos, cfg, unroll):
    """-> (x, new kv, routes).  GQA layers return their updated caches;
    MLA layers return only their new row, set into the cache after."""
    def body(carry, inp):
        lp, c = inp
        xn = TF._norm(cfg, lp["ln1"], carry)
        if cfg.kv_lora_rank:
            a, row = mla.decode_attention(lp["attn"], xn, cfg, c["lat"],
                                          pos)
            new = {"lat": row}
        else:
            a, new = attn.decode_attention(lp["attn"], xn, cfg, c, pos)
        h = carry + a
        y, routes = _ffn(lp, TF._norm(cfg, lp["ln2"], h), cfg)
        return h + y, (new, routes)

    x, new, routes = _run_stacks(body, x, p, cfg, kv, unroll)
    if cfg.kv_lora_rank:
        rows = jnp.arange(x.shape[0])
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), rows.shape)
        new = {"lat": kv["lat"].at[:, rows, pos].set(
            new["lat"].astype(kv["lat"].dtype))}
    return x, new, routes


_SSM_KEYS = ("conv_x", "conv_b", "conv_c", "ssm")


def _decode_ssm_stack(p, x, st, cfg, unroll):
    def body(carry, inp):
        h = carry
        lp = inp[0]
        layer_st = dict(zip(_SSM_KEYS, inp[1:]))
        y, ns = mamba2.decode_step(
            lp["mixer"], TF._norm(cfg, lp["ln"], h), cfg, layer_st)
        return h + y, tuple(ns[k] for k in _SSM_KEYS)

    x, outs = maybe_scan(
        body, x, (p["layers"], *[st[k] for k in _SSM_KEYS]), unroll=unroll)
    return x, dict(zip(_SSM_KEYS, outs))


def _decode_hybrid_stack(p, x, st, kv, pos, cfg, unroll):
    period = cfg.attn_every
    shared = p["shared_attn"]

    def attn_blk(h, ck, cv):
        a, newc = attn.decode_attention(
            shared["attn"], TF._norm(cfg, shared["ln1"], h), cfg,
            {"k": ck, "v": cv}, pos)
        h = h + a
        y = mlp.apply(shared["mlp"], TF._norm(cfg, shared["ln2"], h), cfg)
        return h + y, newc

    if unroll:
        kvk, kvv = kv["k"], kv["v"]
        new_st = []
        for i in range(cfg.n_layers):
            lp = C.tree_index(p["layers"], i)
            layer_st = {k: st[k][i] for k in _SSM_KEYS}
            y, ns = mamba2.decode_step(
                lp["mixer"], TF._norm(cfg, lp["ln"], x), cfg, layer_st)
            x = x + y
            new_st.append(tuple(ns[k] for k in _SSM_KEYS))
            if (i + 1) % period == 0:
                j = (i + 1) // period - 1
                x, newc = attn_blk(x, kvk[j], kvv[j])
                kvk = kvk.at[j].set(newc["k"])
                kvv = kvv.at[j].set(newc["v"])
        outs = jax.tree.map(lambda *a: jnp.stack(a), *new_st)
        return x, dict(zip(_SSM_KEYS, outs)), {"k": kvk, "v": kvv}

    def body(carry, inp):
        h, kvk, kvv = carry
        i, lp = inp[0], inp[1]
        layer_st = dict(zip(_SSM_KEYS, inp[2:]))
        y, ns = mamba2.decode_step(
            lp["mixer"], TF._norm(cfg, lp["ln"], h), cfg, layer_st)
        h = h + y
        j = (i + 1) // period - 1

        def do_attn(args):
            h, kvk, kvv = args
            ck = jax.lax.dynamic_index_in_dim(kvk, j, keepdims=False)
            cv = jax.lax.dynamic_index_in_dim(kvv, j, keepdims=False)
            h, newc = attn_blk(h, ck, cv)
            kvk = jax.lax.dynamic_update_index_in_dim(
                kvk, newc["k"], j, axis=0)
            kvv = jax.lax.dynamic_update_index_in_dim(
                kvv, newc["v"], j, axis=0)
            return h, kvk, kvv

        h, kvk, kvv = jax.lax.cond(
            (i + 1) % period == 0, do_attn, lambda a: a, (h, kvk, kvv))
        return (h, kvk, kvv), tuple(ns[k] for k in _SSM_KEYS)

    idx = jnp.arange(cfg.n_layers)
    (x, kvk, kvv), outs = maybe_scan(
        body, (x, kv["k"], kv["v"]),
        (idx, p["layers"], *[st[k] for k in _SSM_KEYS]), unroll=False)
    return x, dict(zip(_SSM_KEYS, outs)), {"k": kvk, "v": kvv}


def _decode_encdec_stack(p, x, kv, cross, pos, cfg, unroll):
    def body(carry, inp):
        h = carry
        lp, ck, cv, xk, xv = inp
        a, newc = attn.decode_attention(
            lp["attn"], TF._norm(cfg, lp["ln1"], h), cfg,
            {"k": ck, "v": cv}, pos, rope=True)
        h = h + a
        a, _ = attn.decode_attention(
            lp["xattn"], TF._norm(cfg, lp["lnx"], h), cfg,
            {"k": xk, "v": xv}, pos, rope=False, cross=True)
        h = h + a
        y = mlp.apply(lp["mlp"], TF._norm(cfg, lp["ln2"], h), cfg)
        return h + y, (newc["k"], newc["v"])

    x, (k, v) = maybe_scan(
        body, x, (p["layers"], kv["k"], kv["v"], cross["k"], cross["v"]),
        unroll=unroll)
    return x, {"k": k, "v": v}


# ---------------------------------------------------------------------------
# Prefix-aware prefill (paged serving runtime)
# ---------------------------------------------------------------------------


def empty_prefix(cfg: ArchConfig, batch: int = 1) -> dict:
    """A cached prefix of length 0, in the layout of ``prefix_kv``."""
    if cfg.kv_lora_rank:
        return {"lat": jnp.zeros((cfg.n_layers, batch, 0, cfg.latent_dim),
                                 jnp.bfloat16)}
    return {n: jnp.zeros((cfg.n_layers, batch, 0, cfg.n_kv, cfg.d_head),
                         jnp.bfloat16) for n in ("k", "v")}


def _suffix_attn(lp, xa, prefix, positions, n_cached, cfg, unroll):
    """GQA over *suffix* positions against the cached prefix KV.

    ``prefix["k"/"v"] (B, C, Hk, Dh)`` hold the post-rope rows for
    absolute positions ``0..C-1`` (exactly what the cache stores), so
    attention over ``concat(prefix, suffix)`` with ``q_offset=C``
    reproduces the full-prompt computation for every suffix row — the
    suffix queries see identical keys at identical positions.
    """
    q, k, v = attn._project_qkv(lp["attn"], xa, cfg, positions, True)
    kf = jnp.concatenate([prefix["k"].astype(q.dtype), k], axis=1)
    vf = jnp.concatenate([prefix["v"].astype(q.dtype), v], axis=1)
    g = cfg.n_heads // cfg.n_kv
    kr = jnp.repeat(kf, g, axis=2) if g > 1 else kf
    vr = jnp.repeat(vf, g, axis=2) if g > 1 else vf
    mode = attn.attn_mode(cfg.n_heads, cfg.n_kv)
    qs, kr, vr = attn._shard_qkv(q, kr, vr, mode, kv_shardable=True)
    out = attn.flash_attention(
        qs, kr, vr, causal=True, q_chunk=cfg.attn_q_chunk,
        kv_chunk=cfg.attn_kv_chunk, unroll=unroll, q_offset=n_cached,
        bf16_scores=cfg.attn_bf16_scores)
    if mode == "heads":
        out = shard(out, BATCH, None, MODEL, None)
    else:
        out = shard(out, BATCH, MODEL, None, None)
    b, s = out.shape[:2]
    a = C.linear(lp["attn"]["wo"], out.reshape(b, s, -1), quant=cfg.quant)
    return (shard(a, BATCH, None, None),
            {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)})


def prefill_with_prefix(p, tokens, prefix_kv, cfg: ArchConfig, *,
                        unroll=False):
    """Prefill only the *suffix* of a prompt whose first ``C`` tokens'
    KV rows were served by the prefix cache.

    tokens (B, S) are the suffix tokens at absolute positions
    ``C .. C+S-1``; ``prefix_kv`` is the gathered cached prefix (C may be
    0): ``{"k"/"v": (L, B, C, Hk, Dh)}``, or ``{"lat": (L, B, C, R)}``
    for MLA.  Returns ``(logits (B, S, V), suffix kv, routes)``: the kv
    in the same layout with S rows, all suffix logits (so bucket-padded
    callers can pick row ``n_real - 1``), and the routed expert ids
    ``(L_moe, B, S, k)`` (None for a model with no routed layer).
    """
    if cfg.family not in ("dense", "vlm", "moe"):
        raise NotImplementedError(
            f"prefix prefill is attention-family only, got {cfg.family}")
    n_cached = jax.tree.leaves(prefix_kv)[0].shape[2]
    positions = n_cached + jnp.arange(tokens.shape[1])[None]
    x = TF._embed(p, tokens, cfg)

    def body(carry, inp):
        lp, prefix = inp
        xa = TF._norm(cfg, lp["ln1"], carry)
        if cfg.kv_lora_rank:
            a, rows = mla.attention(lp["attn"], xa, cfg, positions=positions,
                                    prefix=prefix["lat"], unroll=unroll)
            new = {"lat": rows.astype(jnp.bfloat16)}
        else:
            a, new = _suffix_attn(lp, xa, prefix, positions, n_cached, cfg,
                                  unroll)
        h = carry + a
        y, routes = _ffn(lp, TF._norm(cfg, lp["ln2"], h), cfg)
        return h + y, (new, routes)

    x, kv, routes = _run_stacks(body, x, p, cfg, prefix_kv, unroll)
    x = TF._norm(cfg, p["ln_f"], x)
    logits = x @ TF.head_weight(p, cfg)
    return shard(logits, BATCH, None, MODEL), kv, routes


def ssm_prefill(p, tokens, caches, cfg: ArchConfig, start_pos=0):
    """Prefill an SSM/hybrid model by scanning the decode step.

    tokens (B, S); ``caches`` is a decode cache pytree (possibly restored
    from a prefix snapshot covering positions ``< start_pos``).  Returns
    ``(logits (B, S, V), final caches)``.  One jitted variant per S; the
    scan keeps compile time flat in S.
    """
    def step(carry, inp):
        caches = carry
        i, tok = inp
        logits, caches = decode_step(p, tok[:, None], caches,
                                     start_pos + i, cfg)
        return caches, logits[:, 0]

    s = tokens.shape[1]
    caches, logits = jax.lax.scan(
        step, caches, (jnp.arange(s), jnp.moveaxis(tokens, 1, 0)))
    return jnp.moveaxis(logits, 0, 1), caches


def ssm_prefill_states(p, tokens, caches, cfg: ArchConfig, start_pos=0):
    """:func:`ssm_prefill` that also returns every intermediate state.

    Speculative verification needs to roll a recurrent cache back to the
    state *after j accepted tokens* — for attention that is free (KV rows
    are positional), for an SSM the per-step states must be kept.  Same
    scan as :func:`ssm_prefill`, but each step's post-update cache pytree
    is stacked into the scan output.

    Returns ``(logits (B, S, V), states)`` where every leaf of ``states``
    has a leading step axis of length S: ``states[...][i]`` is the cache
    after consuming ``tokens[:, i]``.  Bit-identical to sequential
    ``decode_step`` by construction.
    """
    def step(carry, inp):
        caches = carry
        i, tok = inp
        logits, caches = decode_step(p, tok[:, None], caches,
                                     start_pos + i, cfg)
        return caches, (logits[:, 0], caches)

    s = tokens.shape[1]
    _, (logits, states) = jax.lax.scan(
        step, caches, (jnp.arange(s), jnp.moveaxis(tokens, 1, 0)))
    return jnp.moveaxis(logits, 0, 1), states
