"""GQA attention: flash-style chunked train/prefill path + cached decode.

Distribution:
  * "heads" TP — q-heads sharded over `model` (Megatron), kv-heads sharded
    when divisible, else replicated (GQA duplication, e.g. qwen3 kv=4).
  * "seq" SP  — when n_heads doesn't divide the model axis (qwen2.5's 40
    heads on a 16-way axis), the *query sequence* is sharded over `model`
    instead (context parallelism); kv is replicated per layer.
  * decode    — the KV cache is sharded over `model` on the *sequence* dim
    (flash-decoding): XLA partitions the softmax max/sum and the weighted
    sum into per-shard partials + small all-reduces.  This keeps 32k-500k
    caches flat across the mesh regardless of head divisibility.

The train/prefill path is an online-softmax (flash) computed with
`maybe_scan` over q-chunks and kv-chunks, so the (S, S) score matrix is
never materialized.  In unrolled (cost-extraction) mode, fully-masked
causal chunk pairs are skipped at trace time — matching what a production
fused kernel does on TPU — while the scanned mode masks instead.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import common as C
from repro.models.common import BATCH, MODEL, shard

NEG_INF = -1e30


def attn_mode(n_heads: int, n_kv: int, tp: int = 16) -> str:
    return "heads" if n_heads % tp == 0 else "seq"


def init(key, cfg, d_model=None, prefix_dtype=jnp.bfloat16):
    d = d_model or cfg.d_model
    h, hk, dh = cfg.n_heads, cfg.n_kv, cfg.d_head
    ks = jax.random.split(key, 6)
    p = {
        "wq": C.linear_init(ks[0], d, h * dh, bias=cfg.qkv_bias,
                            dtype=prefix_dtype, quant=cfg.quant),
        "wk": C.linear_init(ks[1], d, hk * dh, bias=cfg.qkv_bias,
                            dtype=prefix_dtype, quant=cfg.quant),
        "wv": C.linear_init(ks[2], d, hk * dh, bias=cfg.qkv_bias,
                            dtype=prefix_dtype, quant=cfg.quant),
        "wo": C.linear_init(ks[3], h * dh, d, dtype=prefix_dtype,
                            quant=cfg.quant),
    }
    if cfg.qk_norm:
        p["q_norm"] = C.rmsnorm_init(dh, prefix_dtype)
        p["k_norm"] = C.rmsnorm_init(dh, prefix_dtype)
    return p


def _project_qkv(p, x, cfg, positions, rope: bool):
    b, s, _ = x.shape
    h, hk, dh = cfg.n_heads, cfg.n_kv, cfg.d_head
    q = C.linear(p["wq"], x, quant=cfg.quant).reshape(b, s, h, dh)
    k = C.linear(p["wk"], x, quant=cfg.quant).reshape(b, s, hk, dh)
    v = C.linear(p["wv"], x, quant=cfg.quant).reshape(b, s, hk, dh)
    if cfg.qk_norm:
        q = C.rmsnorm(p["q_norm"], q)
        k = C.rmsnorm(p["k_norm"], k)
    if rope:
        q = C.apply_rope(q, positions, cfg.rope_theta)
        k = C.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _shard_qkv(q, k, v, mode: str, kv_shardable: bool):
    if mode == "heads":
        q = shard(q, BATCH, None, MODEL, None)
        kspec = MODEL if kv_shardable else None
        k = shard(k, BATCH, None, kspec, None)
        v = shard(v, BATCH, None, kspec, None)
    else:  # seq: shard q positions over model; kv replicated
        q = shard(q, BATCH, MODEL, None, None)
        k = shard(k, BATCH, None, None, None)
        v = shard(v, BATCH, None, None, None)
    return q, k, v


def flash_attention(q, k, v, *, causal: bool, q_chunk: int, kv_chunk: int,
                    unroll: bool = False, q_offset: int = 0,
                    bf16_scores: bool = False):
    """Online-softmax attention, MHA layout: q,k,v (B,S|T,H,D).

    GQA callers repeat kv to the full head count first (the standard TP
    duplication when tp > n_kv) — a grouped (hk, g) head split would break
    the 16-way head sharding at the reshape.
    """
    b, s, h, d = q.shape
    t, dv = k.shape[1], v.shape[-1]          # dv may differ from d (MLA)
    assert k.shape[2] == h, (q.shape, k.shape)
    cq = min(q_chunk, s)
    ck = min(kv_chunk, t)
    # Ragged lengths (e.g. image+text concat) are padded up to the chunk
    # grid; padded kv columns are masked, padded q rows sliced off below.
    s_pad, t_pad = -(-s // cq) * cq, -(-t // ck) * ck
    t_valid = t
    if s_pad != s:
        q = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
    if t_pad != t:
        k = jnp.pad(k, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
    s_orig, s, t = s, s_pad, t_pad
    nq, nk = s // cq, t // ck
    scale = d ** -0.5
    mask_tail = t_valid != t

    qc = jnp.moveaxis(q.reshape(b, nq, cq, h, d), 1, 0)
    kc = jnp.moveaxis(k.reshape(b, nk, ck, h, d), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, nk, ck, h, dv), 1, 0)

    def q_body(_, q_in):
        qi, qblk = q_in                            # qblk (B, Cq, H, D)
        qpos = q_offset + qi * cq + jnp.arange(cq)

        def kv_body(carry, kv_in, *, need_mask: bool = True):
            m, l, acc = carry
            ki, kblk, vblk = kv_in
            kpos = ki * ck + jnp.arange(ck)
            sc_dtype = jnp.bfloat16 if bf16_scores else jnp.float32
            sc = jnp.einsum("bqhd,bkhd->bhqk", qblk, kblk,
                            preferred_element_type=sc_dtype) * scale
            # a fused TPU kernel only masks the diagonal tiles; fully-live
            # tiles skip the mask pass entirely (need_mask=False from the
            # unrolled schedule below)
            if causal and need_mask:
                mask = qpos[:, None] >= kpos[None, :]
                if mask_tail:
                    mask = mask & (kpos < t_valid)[None, :]
                sc = jnp.where(mask[None, None], sc, NEG_INF)
            elif mask_tail and need_mask:
                sc = jnp.where((kpos < t_valid)[None, None, None, :],
                               sc, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1).astype(jnp.float32))
            alpha = jnp.exp(m - m_new)
            # the score tile never round-trips through f32: subtract the
            # (broadcast) max in tile dtype, exponentiate in tile dtype —
            # exp of a max-subtracted score is in (0, 1], bf16-safe.
            pexp = jnp.exp(sc - m_new[..., None].astype(sc_dtype))
            l_new = l * alpha + jnp.sum(pexp, axis=-1,
                                        dtype=jnp.float32)
            pv = jnp.einsum("bhqk,bkhd->bhqd", pexp.astype(vblk.dtype),
                            vblk, preferred_element_type=jnp.float32)
            acc_new = acc * alpha[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, cq), jnp.float32)
        a0 = jnp.zeros((b, h, cq, dv), jnp.float32)

        if unroll:
            # Trace-time causal skipping: only live kv chunks are emitted,
            # like a fused TPU kernel would schedule.  qi is a Python int.
            n_live = nk if not causal else min(
                nk, (q_offset + (qi + 1) * cq + ck - 1) // ck)
            carry = (m0, l0, a0)
            for kidx in range(n_live):
                # fully-live tile: every (qpos, kpos) pair is causal-valid
                full = (not causal or
                        (kidx + 1) * ck - 1 <= q_offset + qi * cq) and \
                    not (mask_tail and kidx == nk - 1)
                carry, _ = kv_body(carry, (kidx, kc[kidx], vc[kidx]),
                                   need_mask=not full)
            m, l, acc = carry
        else:
            (m, l, acc), _ = jax.lax.scan(
                kv_body, (m0, l0, a0), (jnp.arange(nk), kc, vc))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)           # (B, H, Cq, D)

    if unroll:
        outs = []
        for i in range(nq):
            _, o = q_body(None, (i, qc[i]))
            outs.append(o)
        out = jnp.stack(outs)
    else:
        _, out = jax.lax.scan(q_body, None, (jnp.arange(nq), qc))
    # (Nq, B, H, Cq, D) -> (B, S, H, D)
    out = jnp.moveaxis(out, 0, 1).transpose(0, 1, 3, 2, 4)
    return out.reshape(b, s, h, dv)[:, :s_orig]


def attention(p, x, cfg, *, positions, causal=True, rope=True,
              kv_override=None, unroll=False):
    """Full-sequence attention (train / prefill).  Returns (y, (k, v)).

    The returned (k, v) keep the compact n_kv head count (cache layout);
    the flash path repeats them to n_heads so head sharding survives.
    """
    mode = attn_mode(cfg.n_heads, cfg.n_kv)
    if kv_override is not None:
        q, _, _ = _project_qkv(p, x, cfg, positions, rope)
        k, v = kv_override
    else:
        q, k, v = _project_qkv(p, x, cfg, positions, rope)
    g = cfg.n_heads // cfg.n_kv
    kr = jnp.repeat(k, g, axis=2) if g > 1 else k
    vr = jnp.repeat(v, g, axis=2) if g > 1 else v
    q, kr, vr = _shard_qkv(q, kr, vr, mode, kv_shardable=True)
    out = flash_attention(
        q, kr, vr, causal=causal, q_chunk=cfg.attn_q_chunk,
        kv_chunk=cfg.attn_kv_chunk, unroll=unroll,
        bf16_scores=cfg.attn_bf16_scores)
    if mode == "heads":
        out = shard(out, BATCH, None, MODEL, None)
    else:
        out = shard(out, BATCH, MODEL, None, None)
    b, s, _, _ = out.shape
    y = C.linear(p["wo"], out.reshape(b, s, -1), quant=cfg.quant)
    y = shard(y, BATCH, None, None)
    return y, (k, v)


# ---------------------------------------------------------------------------
# Decode path (single new token against a cache)
# ---------------------------------------------------------------------------


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    hk, dh = cfg.n_kv, cfg.d_head
    return {
        "k": jnp.zeros((batch, max_len, hk, dh), dtype),
        "v": jnp.zeros((batch, max_len, hk, dh), dtype),
    }


def cache_specs():
    """KV cache sharding: sequence over `model` (flash-decoding layout)."""
    from jax.sharding import PartitionSpec as P
    return {"k": P(BATCH, MODEL, None, None), "v": P(BATCH, MODEL, None, None)}


def decode_attention(p, x, cfg, cache, pos, *, rope=True, cross=False):
    """x (B, 1, D); pos (B,) int32 per-row write/read positions.

    The cache holds T entries, sharded over `model` on T.  Returns
    (y, new_cache).  For cross-attention (whisper decode) the cache is the
    static encoder projection and is not updated.
    """
    b = x.shape[0]
    h, hk, dh = cfg.n_heads, cfg.n_kv, cfg.d_head
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    positions = pos[:, None]
    q = C.linear(p["wq"], x, quant=cfg.quant).reshape(b, 1, h, dh)
    if cfg.qk_norm:
        q = C.rmsnorm(p["q_norm"], q)
    if rope:
        q = C.apply_rope(q, positions, cfg.rope_theta)

    if cross:
        k, v = cache["k"], cache["v"]
        new_cache = cache
    else:
        knew = C.linear(p["wk"], x, quant=cfg.quant).reshape(b, 1, hk, dh)
        vnew = C.linear(p["wv"], x, quant=cfg.quant).reshape(b, 1, hk, dh)
        if cfg.qk_norm:
            knew = C.rmsnorm(p["k_norm"], knew)
        if rope:
            knew = C.apply_rope(knew, positions, cfg.rope_theta)
        rows = jnp.arange(b)
        # in-place scatter into the donated cache; the output inherits the
        # operand sharding (re-constraining here would add a copy, §Perf B3)
        k = cache["k"].at[rows, pos].set(
            knew[:, 0].astype(cache["k"].dtype))
        v = cache["v"].at[rows, pos].set(
            vnew[:, 0].astype(cache["v"].dtype))
        new_cache = {"k": k, "v": v}

    t = k.shape[1]
    g = h // hk
    qg = q.reshape(b, 1, hk, g, dh)
    # low-precision cache storage (fp8/int8, §Perf): decode casts next to
    # the dot — HBM reads the narrow format, MXU sees bf16.
    ke = k.astype(qg.dtype) if k.dtype != qg.dtype else k
    sc = jnp.einsum("bqhgd,bkhd->bhgqk", qg, ke,
                    preferred_element_type=jnp.float32) * dh ** -0.5
    if not cross:
        live = jnp.arange(t)[None] <= pos[:, None]       # (B, T)
        sc = jnp.where(live[:, None, None, None], sc, NEG_INF)
    # Softmax over the model-sharded T axis: XLA partitions max/sum into
    # per-shard partials + all-reduce (the flash-decoding combine).
    w = jax.nn.softmax(sc.astype(jnp.float32), axis=-1)
    ve = v.astype(x.dtype) if v.dtype != x.dtype else v
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(ve.dtype), ve,
                     preferred_element_type=jnp.float32)
    out = out.reshape(b, 1, h * dh).astype(x.dtype)
    y = C.linear(p["wo"], out, quant=cfg.quant)
    return shard(y, BATCH, None, None), new_cache
