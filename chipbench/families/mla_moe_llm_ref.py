"""Plain reference of the DeepSeek-V3 block (Moonlight-16B-A3B layout):
latent attention and sigmoid-routed experts beside shared ones, float32
at ``highest`` matmul precision, no cache, no kernels, no batching.
Imports nothing of the program.

    x = embed[tokens]
    per layer:  a = rmsnorm(x) * ln1
                q = a Wq -> per head [q_nope; q_pe], RoPE on q_pe
                [c; k_pe] = a Wkva; c = rmsnorm(c) * wkv_a_norm; RoPE on k_pe
                [k_nope; v] = c Wkvb per head; k = [k_nope; k_pe]
                x += softmax(q k^T / sqrt(nope + rope), causal) v  Wo
                m = rmsnorm(x) * ln2
                x += SwiGLU(m)                           (leading dense)
                  or shared(m) + sum over held chosen i of g_i FFN_i(m)
    logits = (rmsnorm(x) * ln_f) Whead            (first ``vocab`` columns)

Routing: s = sigmoid(m Wrouter) over every expert; the top k of s + b
(b a selection-only bias) are chosen; g = s / sum of the chosen s, times
``routed_scale``.

Departures from the published model, each shared with the program:

* the layer holds the routed experts ``expert_offset ..`` that its
  parameters carry (one chip's share); what the absent experts would add
  is left out;
* RoPE rotates the rope dims in halves; the published code de-interleaves
  them first, the same map up to a fixed permutation of the rope columns
  of Wq and Wkva, which seeded weights do not tell apart;
* the weights are read as they were made (bf16 values, upcast per layer).

``prec`` is ``"f32"`` (the reference), ``"f8"`` (the control one
precision below the served bf16: every matrix product's input and weight
rounded to float8, e4m3, scaled per tensor, and per row for activations)
or ``"bf16"`` (a twin at the served precision: every product's input and
the residual stream rounded to bf16, the router's product in float32 on
bf16 input, as the program takes it).  ``force`` pins each routed
layer's choice to given expert ids, gates still from the layer's own
scores.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

F8_MAX = 448.0


class Dims(NamedTuple):
    n_heads: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    vocab: int
    rope_theta: float
    eps: float
    topk: int
    routed_scale: float
    expert_offset: int


def _f8(x, axis=None):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _round(x, prec: str, axis=-1):
    """An activation as a product takes it at ``prec``."""
    if prec == "f8":
        return _f8(x, axis=axis)
    return _bf16(x) if prec == "bf16" else x


def _mm(x, w, prec: str):
    w = w.astype(jnp.float32)
    if prec == "f8":
        w = _f8(w)
    return _round(x, prec) @ w


def _rms(x, scale, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def _rope(x, theta):
    """x (T, H, D) at positions 0..T-1, rotated in halves."""
    t, _, d = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mla(a, att, d: Dims, prec: str):
    t = a.shape[0]
    h, r, dn, dr, dv = (d.n_heads, d.kv_lora_rank, d.qk_nope_dim,
                        d.qk_rope_dim, d.v_head_dim)
    q = _mm(a, att["wq"]["w"], prec).reshape(t, h, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], d.rope_theta)], -1)
    ckv = _mm(a, att["wkv_a"]["w"], prec)
    c = _rms(ckv[:, :r], att["wkv_a_norm"]["scale"], d.eps)
    k_pe = _rope(ckv[:, None, r:], d.rope_theta)
    kv = _mm(c, att["wkv_b"]["w"], prec).reshape(t, h, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe, (t, h, dr))], -1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(dn + dr))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), kv[..., dn:])
    return _mm(o.reshape(t, h * dv), att["wo"]["w"], prec)


def _swiglu(m, w, prec: str):
    f = (jax.nn.silu(_mm(m, w["gate"]["w"], prec))
         * _mm(m, w["up"]["w"], prec))
    return _mm(f, w["down"]["w"], prec)


def _experts(m, mp, prec: str):
    """Every held expert's SwiGLU over every row: (E_held, T, D)."""
    wg, wu, wd = (mp[n].astype(jnp.float32)
                  for n in ("gate_proj", "up_proj", "down_proj"))
    if prec == "f8":
        wg, wu, wd = _f8(wg), _f8(wu), _f8(wd)
    m = _round(m, prec)
    f = (jax.nn.silu(jnp.einsum("td,edf->etf", m, wg))
         * jnp.einsum("td,edf->etf", m, wu))
    return jnp.einsum("etf,efd->etd", _round(f, prec), wd)


def _routing(m, mp, d: Dims, prec: str, force=None):
    """Each row's chosen experts (T, k) and their gates."""
    if prec == "bf16":
        s = jax.nn.sigmoid(_mm(_bf16(m), mp["router"], "f32"))
    else:
        s = jax.nn.sigmoid(_mm(m, mp["router"], prec))
    if force is None:
        _, idx = jax.lax.top_k(s + mp["router_bias"], d.topk)
    else:
        idx = force
    g = jnp.take_along_axis(s, idx, -1)
    return idx, g / jnp.sum(g, -1, keepdims=True) * d.routed_scale


def moe_layer(m, mp, d: Dims, prec: str = "f32", force=None):
    """The routed layer on rows ``m (T, D)``: (the shared experts plus the
    held experts' part of the gated sum, the chosen ids (T, k))."""
    idx, g = _routing(m, mp, d, prec, force)
    ids = d.expert_offset + jnp.arange(mp["gate_proj"].shape[0])
    held = jnp.sum(jnp.where(idx[..., None] == ids, g[..., None], 0.0), 1)
    return (_swiglu(m, mp["shared"], prec)
            + jnp.einsum("etd,te->td", _experts(m, mp, prec), held)), idx


@functools.partial(jax.jit, static_argnames=("dims", "prec"))
def forward(params, tokens, rows, dims: Dims, prec: str = "f32",
            force=None):
    """(logits (len(rows), vocab) at positions ``rows`` of one sequence,
    each routed layer's chosen ids at every position (L_moe, T, k)).
    Padding after the last real token changes nothing before it
    (causal, and routing is per row)."""
    d = dims
    keep = _bf16 if prec == "bf16" else (lambda x: x)
    with jax.default_matmul_precision("highest"):
        x = keep(params["embed"][tokens].astype(jnp.float32))

        def attend(x, lp):
            a = _mla(_rms(x, lp["ln1"]["scale"], d.eps), lp["attn"], d, prec)
            x = keep(x + a)
            return x, _rms(x, lp["ln2"]["scale"], d.eps)

        def dense(x, lp):
            x, m = attend(x, lp)
            return keep(x + _swiglu(m, lp["mlp"], prec)), None

        def routed(x, inp):
            lp, pin = inp
            x, m = attend(x, lp)
            y, idx = moe_layer(m, lp["moe"], d, prec, pin)
            return keep(x + y), idx

        x, _ = jax.lax.scan(dense, x, params["dense_layers"])
        x, ids = jax.lax.scan(routed, x, (params["layers"], force))
        x = _rms(x[rows], params["ln_f"]["scale"], d.eps)
        return _mm(x, params["head"][:, :d.vocab], prec), ids


def logits_at(params, tokens, rows, dims: Dims, low: bool = False):
    """The reference's logits at ``rows`` (the control's with ``low``)."""
    return forward(params, tokens, rows, dims, "f8" if low else "f32")[0]


def bucket(n: int, floor: int = 256) -> int:
    """Padded sequence length: one compiled program per power of two."""
    b = floor
    while b < n:
        b *= 2
    return b
