"""Multi-head latent attention (DeepSeek-V2/V3, arXiv:2405.04434), no q
compression.

Per position the cache holds one row of ``kv_lora_rank + qk_rope_dim``
values: the RMS-normed latent ``c`` and the roped ``k_pe`` that every
head shares.

* prefill (:func:`attention`) expands the latent: ``[k_nope; v] = c
  W_kvb`` per head, ``k = [k_nope; k_pe]``, then causal flash attention
  over ``[cached rows; new rows]`` at ``(qk_nope_dim + qk_rope_dim)^-0.5``;
* decode (:func:`decode_attention`) absorbs ``W_kvb`` instead: ``q_nope
  W_UK`` moves the query into latent space, scores are taken against the
  cached rows themselves, and the attended latent goes out through
  ``W_UV``, so the step reads each cached row once and never expands it.

RoPE rotates the rope dims in halves (``common.apply_rope``); the
published implementation de-interleaves them first, which is the same
map up to a fixed permutation of the rope columns of ``W_q`` and
``W_kva``, so with seeded weights the two are the same model.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import common as C

NEG_INF = attn.NEG_INF


def init(key, cfg):
    d, h = cfg.d_model, cfg.n_heads
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    ks = jax.random.split(key, 4)
    return {"wq": C.linear_init(ks[0], d, h * (dn + dr)),
            "wkv_a": C.linear_init(ks[1], d, r + dr),
            "wkv_a_norm": C.rmsnorm_init(r),
            "wkv_b": C.linear_init(ks[2], r, h * (dn + dv)),
            "wo": C.linear_init(ks[3], h * dv, d)}


def _latent_rows(p, x, cfg, positions):
    """x (B, S, D) -> cache rows (B, S, kv_lora_rank + qk_rope_dim)."""
    r = cfg.kv_lora_rank
    ckv = C.linear(p["wkv_a"], x)
    c = C.rmsnorm(p["wkv_a_norm"], ckv[..., :r], eps=cfg.norm_eps)
    k_pe = C.apply_rope(ckv[..., None, r:], positions, cfg.rope_theta)
    return jnp.concatenate([c, k_pe[..., 0, :]], axis=-1)


def _queries(p, x, cfg, positions):
    """-> q_nope (B, S, H, dn), roped q_pe (B, S, H, dr)."""
    b, s, _ = x.shape
    dn = cfg.qk_nope_dim
    q = C.linear(p["wq"], x).reshape(b, s, cfg.n_heads, -1)
    return q[..., :dn], C.apply_rope(q[..., dn:], positions, cfg.rope_theta)


def attention(p, x, cfg, *, positions, prefix=None, unroll=False):
    """Prefill of ``x (B, S, D)`` at ``positions``, after ``prefix (B, C,
    R)``, the cached rows of positions ``0..C-1`` (None: C = 0).
    Returns ``(y (B, S, D), rows (B, S, R))``, the new positions' rows."""
    b, s, _ = x.shape
    h, r, dn, dv = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_dim, \
        cfg.v_head_dim
    rows = _latent_rows(p, x, cfg, positions)
    full = rows if prefix is None else jnp.concatenate(
        [prefix.astype(rows.dtype), rows], axis=1)
    t = full.shape[1]
    kv = C.linear(p["wkv_b"], full[..., :r]).reshape(b, t, h, dn + dv)
    k_pe = jnp.broadcast_to(full[:, :, None, r:], (b, t, h, cfg.qk_rope_dim))
    k = jnp.concatenate([kv[..., :dn], k_pe], axis=-1)
    q_nope, q_pe = _queries(p, x, cfg, positions)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    out = attn.flash_attention(
        q, k, kv[..., dn:], causal=True, q_chunk=cfg.attn_q_chunk,
        kv_chunk=cfg.attn_kv_chunk, unroll=unroll, q_offset=t - s,
        bf16_scores=cfg.attn_bf16_scores)
    return C.linear(p["wo"], out.reshape(b, s, h * dv)), rows


def decode_attention(p, x, cfg, cache, pos):
    """One new token per sequence: ``x (B, 1, D)`` at ``pos (B,)``
    against ``cache (B, T, R)``, whose rows below ``pos`` are live.
    Returns ``(y (B, 1, D), row (B, R))``; the cache is not written."""
    b = x.shape[0]
    h, r, dn, dv = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_dim, \
        cfg.v_head_dim
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    positions = pos[:, None]
    row = _latent_rows(p, x, cfg, positions)[:, 0]
    q_nope, q_pe = _queries(p, x, cfg, positions)
    w_kvb = p["wkv_b"]["w"].reshape(r, h, dn + dv)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_kvb[..., :dn],
                       preferred_element_type=jnp.float32)
    q = jnp.concatenate([q_lat.astype(x.dtype), q_pe[:, 0]], axis=-1)
    scale = (dn + cfg.qk_rope_dim) ** -0.5
    sc = jnp.einsum("bhc,btc->bht", q, cache.astype(q.dtype),
                    preferred_element_type=jnp.float32) * scale
    live = jnp.arange(cache.shape[1])[None] < pos[:, None]       # (B, T)
    sc = jnp.where(live[:, None], sc, NEG_INF)
    s_new = jnp.einsum("bhc,bc->bh", q, row,
                       preferred_element_type=jnp.float32) * scale
    m = jnp.maximum(jnp.max(sc, axis=-1), s_new)
    e = jnp.exp(sc - m[..., None])
    e_new = jnp.exp(s_new - m)
    o = jnp.einsum("bht,btr->bhr", e.astype(x.dtype),
                   cache[..., :r].astype(x.dtype),
                   preferred_element_type=jnp.float32)
    o = (o + e_new[..., None] * row[:, None, :r].astype(jnp.float32)) \
        / (jnp.sum(e, axis=-1) + e_new)[..., None]
    out = jnp.einsum("bhr,rhv->bhv", o.astype(x.dtype), w_kvb[..., dn:],
                     preferred_element_type=jnp.float32)
    y = C.linear(p["wo"], out.reshape(b, 1, h * dv).astype(x.dtype))
    return y, row
