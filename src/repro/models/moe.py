"""Mixture-of-Experts with sort-based capacity dispatch + expert parallelism.

Design (deepseek-moe / qwen3-moe style):
  * router (bf16, never ternarized — mirrors the paper keeping thresholds
    full-precision) -> top-k experts per token + softmaxed gates,
  * dispatch: flatten (T, k) assignments, argsort by expert id, compute the
    position-within-expert via searchsorted, clamp to a static capacity
    C = ceil(T*k/E * capacity_factor) (tokens overflowing an expert are
    dropped — standard dropping-MoE semantics, deterministic shapes),
  * expert FFN: batched (E, C, D) SwiGLU einsum, experts sharded over the
    `model` axis (EP); XLA emits the token all-to-all at the
    data-sharded -> expert-sharded scatter boundary,
  * combine: weighted gather back to token order.

FLOPs are gather/scatter based (no one-hot einsum), so HLO compute matches
6 * N_active * D accounting for the roofline's MODEL_FLOPS ratio.

Aux losses: switch-style load-balance loss + router z-loss, returned to the
training loop.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import common as C
from repro.models.common import BATCH, MODEL, shard


def init(key, cfg, d_model=None):
    d = d_model or cfg.d_model
    e, f = cfg.n_experts, cfg.d_ff_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": C.dense_init(ks[0], (d, e), jnp.float32),
        "gate_proj": C.dense_init(ks[1], (e, d, f)),
        "up_proj": C.dense_init(ks[2], (e, d, f)),
        "down_proj": C.dense_init(ks[3], (e, f, d)),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        shared_cfg = cfg.replace(d_ff=fs)
        from repro.models import mlp
        p["shared"] = mlp.init(ks[4], shared_cfg, d_model=d, d_ff=fs)
    return p


def _capacity(n_tokens: int, cfg) -> int:
    c = int(n_tokens * cfg.topk * cfg.capacity_factor / cfg.n_experts)
    return max(128, -(-c // 128) * 128)            # 128-aligned, >= 128


def apply(p, x, cfg):
    """x (B, S, D) -> (y, aux) with aux = {lb_loss, z_loss}.

    Two dispatch implementations:
      * dense — global sort-based scatter/gather (baseline; simple, but the
        global-index scatter defeats SPMD partitioning: XLA replicates the
        (E*cap, D) buffers, exploding memory and all-reduce traffic),
      * ep    — shard_map expert parallelism (§Perf): tokens stay on their
        data shard, experts are local to their model shard; because x is
        replicated along `model`, dispatch is a *local* gather and the only
        collective is one (t_local, D) psum per layer.
    """
    mesh = C.get_mesh()
    if (cfg.moe_impl == "ep" and mesh is not None
            and "model" in mesh.axis_names
            and cfg.n_experts % mesh.shape["model"] == 0):
        return _apply_ep(p, x, cfg, mesh)
    return _apply_dense(p, x, cfg)


def _apply_dense(p, x, cfg):
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.topk
    cap = _capacity(t, cfg)
    xt = x.reshape(t, d)

    logits = (xt.astype(jnp.float32) @ p["router"])          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                     # (T, k)
    gates = gates / jnp.maximum(
        jnp.sum(gates, axis=-1, keepdims=True), 1e-9)

    # ---- aux losses (switch-transformer style) ----
    me = jnp.mean(probs, axis=0)                             # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=1), axis=0)
    lb_loss = e * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)

    # ---- sort-based dispatch ----
    flat_e = idx.reshape(-1)                                 # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    token_of = order // k
    start = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    pos = jnp.arange(t * k) - start[sorted_e]
    keep = pos < cap
    slot = jnp.where(keep, sorted_e * cap + pos, 0)

    buf = jnp.zeros((e * cap, d), x.dtype)
    src = jnp.where(keep[:, None], xt[token_of], 0).astype(x.dtype)
    buf = buf.at[slot].add(src)                              # scatter
    buf = buf.reshape(e, cap, d)
    buf = shard(buf, MODEL, None, None)                      # EP

    # ---- expert SwiGLU (batched over sharded experts) ----
    gate = jnp.einsum("ecd,edf->ecf", buf, p["gate_proj"])
    up = jnp.einsum("ecd,edf->ecf", buf, p["up_proj"])
    h = jax.nn.silu(gate) * up
    out = jnp.einsum("ecf,efd->ecd", h, p["down_proj"])
    out = shard(out, MODEL, None, None).reshape(e * cap, d)

    # ---- combine ----
    flat_gates = gates.reshape(-1)[order]
    contrib = out[slot] * (flat_gates * keep)[:, None].astype(x.dtype)
    y = jnp.zeros((t, d), x.dtype).at[token_of].add(contrib)
    y = shard(y.reshape(b, s, d), BATCH, None, None)

    if "shared" in p:
        from repro.models import mlp
        shared_cfg = cfg.replace(
            d_ff=cfg.d_ff_expert * cfg.n_shared_experts)
        y = y + mlp.apply(p["shared"], x, shared_cfg)

    return y, {"lb_loss": lb_loss, "z_loss": z_loss}


# ---------------------------------------------------------------------------
# shard_map expert parallelism (§Perf hillclimb; see EXPERIMENTS.md)
# ---------------------------------------------------------------------------


def _apply_ep(p, x, cfg, mesh):
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.topk
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    tp = mesh.shape["model"]
    e_local = e // tp

    def local_fn(xl, router_w, gate_w, up_w, down_w):
        # xl (b_l, S, D) — this data shard's tokens, replicated over model;
        # expert weights are the local slice (E/tp, D, F).
        bl = xl.shape[0]
        t = bl * s
        # per-(data-shard, expert) capacity, 128-aligned
        cap = max(128, -(-int(t * k * cfg.capacity_factor / e) // 128) * 128)
        xt = xl.reshape(t, d)
        m_idx = jax.lax.axis_index("model")

        logits = xt.astype(jnp.float32) @ router_w          # (t, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, k)
        gates = gates / jnp.maximum(
            jnp.sum(gates, axis=-1, keepdims=True), 1e-9)

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jnp.sum(
            jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=1), axis=0)
        lb = e * jnp.sum(me * ce)
        zl = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
        axes = batch_axes + ("model",)
        lb = jax.lax.pmean(lb, axes)
        zl = jax.lax.pmean(zl, axes)

        # position-within-expert over the GLOBAL expert ids (same for every
        # model shard since xl is replicated along model)
        flat_e = idx.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        token_of = order // k
        start = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
        pos = jnp.arange(t * k) - start[sorted_e]
        local = (sorted_e >= m_idx * e_local) \
            & (sorted_e < (m_idx + 1) * e_local)
        keep = (pos < cap) & local
        slot = jnp.where(keep, (sorted_e - m_idx * e_local) * cap + pos, 0)

        buf = jnp.zeros((e_local * cap, d), xl.dtype)
        src = jnp.where(keep[:, None], xt[token_of], 0).astype(xl.dtype)
        buf = buf.at[slot].add(src).reshape(e_local, cap, d)

        gate = jnp.einsum("ecd,edf->ecf", buf, gate_w)
        up = jnp.einsum("ecd,edf->ecf", buf, up_w)
        h = jax.nn.silu(gate) * up
        out = jnp.einsum("ecf,efd->ecd", h, down_w).reshape(e_local * cap, d)

        flat_gates = gates.reshape(-1)[order]
        contrib = out[slot] * (flat_gates * keep)[:, None].astype(xl.dtype)
        y = jnp.zeros((t, d), xl.dtype).at[token_of].add(contrib)
        y = jax.lax.psum(y, "model")          # row-parallel combine
        return y.reshape(bl, s, d), lb, zl

    bspec = P(batch_axes, None, None) if batch_axes else P(None, None, None)
    y, lb, zl = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(bspec, P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(bspec, P(), P()),
        check_vma=False,
    )(x, p["router"], p["gate_proj"], p["up_proj"], p["down_proj"])

    if "shared" in p:
        from repro.models import mlp
        shared_cfg = cfg.replace(
            d_ff=cfg.d_ff_expert * cfg.n_shared_experts)
        y = y + mlp.apply(p["shared"], x, shared_cfg)
    return y, {"lb_loss": lb, "z_loss": zl}
