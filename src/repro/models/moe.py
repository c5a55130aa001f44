"""Mixture-of-Experts: routing, the held share of the experts, and two
dispatches.

Routing (:func:`route`, over all ``n_experts``; the router takes bf16
input in f32 math and is never ternarized, as the paper keeps thresholds
full-precision):

* ``softmax`` (deepseek-moe / qwen3-moe): top-k of the softmaxed logits,
  gates renormalized over the chosen k;
* ``sigmoid`` (DeepSeek-V3 ``noaux_tc`` with one group): score s =
  sigmoid(logits); the top k of ``s + router_bias`` are chosen (the bias
  steers the choice only); gates are s over their sum;

then times ``routed_scale``.

A layer holds ``cfg.n_held`` of the routed experts, ids ``expert_offset
..`` (one chip's share under expert parallelism; all of them by
default), and computes only their part of sum_i g_i FFN_i(x), plus the
shared experts once.

* :func:`apply_held` — the serving path, with no capacity: every held
  expert over every token where the held share is small, else grouped
  matmuls over the assignments sorted by held expert, so the work stays
  near the T*k assignments' rows; no assignment is ever dropped, a
  token's output does not depend on its batch-mates, and each held
  expert's weights are read once per call.
* :func:`apply` — the training path: flatten the (T, k) assignments,
  argsort by expert id, position-within-expert via searchsorted, clamp
  to a static capacity C = ceil(T*k/E * capacity_factor) (assignments
  that overflow an expert are dropped — dropping-MoE semantics,
  deterministic shapes), a batched (E, C, D) SwiGLU einsum with experts
  sharded over the `model` axis (EP), a weighted gather back.  Its
  FLOPs are gather/scatter based (no one-hot einsum), so HLO compute
  matches 6 * N_active * D accounting.  Aux losses: switch-style load
  balance + router z-loss.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import common as C
from repro.models.common import BATCH, MODEL, shard


def init(key, cfg, d_model=None):
    d = d_model or cfg.d_model
    e, f = cfg.n_held, cfg.d_ff_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": C.dense_init(ks[0], (d, cfg.n_experts), jnp.float32),
        "gate_proj": C.dense_init(ks[1], (e, d, f)),
        "up_proj": C.dense_init(ks[2], (e, d, f)),
        "down_proj": C.dense_init(ks[3], (e, f, d)),
    }
    if cfg.router == "sigmoid":
        p["router_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        shared_cfg = cfg.replace(d_ff=fs)
        from repro.models import mlp
        p["shared"] = mlp.init(ks[4], shared_cfg, d_model=d, d_ff=fs)
    return p


def _capacity(n_tokens: int, cfg) -> int:
    c = int(n_tokens * cfg.topk * cfg.capacity_factor / cfg.n_experts)
    return max(128, -(-c // 128) * 128)            # 128-aligned, >= 128


def route(p, xt, cfg):
    """xt (T, D) -> (gates (T, k) f32, expert ids (T, k), logits (T, E),
    scores (T, E)) over all ``n_experts``.  The router's product runs in
    full float32 (a TPU otherwise rounds its operands to bf16): near-tied
    scores decide the choice."""
    logits = jnp.matmul(xt.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)  # (T, E)
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + p["router_bias"], cfg.topk)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(scores, cfg.topk)
    gates = gates / jnp.maximum(
        jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return gates * cfg.routed_scale, idx, logits, scores


def _shared(p, x, cfg):
    from repro.models import mlp
    return mlp.apply(p["shared"], x, cfg)


def apply_held(p, x, cfg):
    """x (B, S, D) -> (y, expert ids (B, S, k)): the shared experts plus
    the held experts' part of the routed sum, for every token, with no
    capacity.  The ids are every expert each token chose, held or not.

    Two ways, chosen by shape: every held expert over every token
    (n_held*T rows of expert work) where that is at most twice the call's
    T*k assignments, else the assignments sorted by held expert through
    grouped matmuls (T*k rows, and a sort and a gather each way).  At 8
    held of 64, k 6, the first takes 26 routed layers about 10 ms a
    32-slot decode step on a TPU v5e and the second about 15 ms; with all
    64 held the second does an eighth of the first's work (PERF.md)."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    gates, idx, _, _ = route(p, xt, cfg)
    if cfg.n_held <= 2 * cfg.topk:
        y = _every_held(p, xt, gates, idx, cfg)
    else:
        y = _grouped(p, xt, gates, idx, cfg)
    y = y.astype(x.dtype).reshape(b, s, d)
    if "shared" in p:
        y = y + _shared(p, x, cfg)
    return y, idx.reshape(b, s, cfg.topk)


def _every_held(p, xt, gates, idx, cfg):
    """Every held expert's SwiGLU over every token, each token taking the
    gated sum of those it chose: (T, D) f32."""
    # (T, n_held): each held expert's gate for each token, 0 if unchosen
    chose = idx[..., None] == cfg.expert_offset + jnp.arange(cfg.n_held)
    g = jnp.sum(jnp.where(chose, gates[..., None], 0.0), axis=1)
    gate = jnp.einsum("td,edf->etf", xt, p["gate_proj"])
    up = jnp.einsum("td,edf->etf", xt, p["up_proj"])
    out = jnp.einsum("etf,efd->etd", jax.nn.silu(gate) * up, p["down_proj"])
    return jnp.einsum("etd,te->td", out.astype(jnp.float32), g)


def _grouped(p, xt, gates, idx, cfg):
    """The T*k assignments sorted by held expert, one group of rows per
    held expert (``jax.lax.ragged_dot``); those of experts held
    elsewhere sort last, past every group, and add nothing: (T, D) f32."""
    t, k, eh = xt.shape[0], cfg.topk, cfg.n_held
    e = (idx - cfg.expert_offset).reshape(-1)
    e = jnp.where((e >= 0) & (e < eh), e, eh)
    order = jnp.argsort(e, stable=True)
    sizes = jnp.bincount(e, length=eh + 1)[:eh].astype(jnp.int32)
    rows = xt[order // k]
    gate = jax.lax.ragged_dot(rows, p["gate_proj"], sizes)
    up = jax.lax.ragged_dot(rows, p["up_proj"], sizes)
    out = jax.lax.ragged_dot(jax.nn.silu(gate) * up, p["down_proj"], sizes)
    out = jnp.where((e[order] < eh)[:, None], out, 0)
    # back to (token, choice) order, each row times its gate
    out = out[jnp.argsort(order)].reshape(t, k, -1)
    return jnp.einsum("tkd,tk->td", out.astype(jnp.float32), gates)


def apply(p, x, cfg):
    """x (B, S, D) -> (y, aux) with aux = {lb_loss, z_loss}.

    Two dispatch implementations:
      * dense — global sort-based scatter/gather (baseline; simple, but the
        global-index scatter defeats SPMD partitioning: XLA replicates the
        (E*cap, D) buffers, exploding memory and all-reduce traffic),
      * ep    — shard_map expert parallelism: tokens stay on their
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
    eh, off = cfg.n_held, cfg.expert_offset
    cap = _capacity(t, cfg)
    xt = x.reshape(t, d)

    gates, idx, logits, probs = route(p, xt, cfg)            # (T, k)

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
    keep = (pos < cap) & (sorted_e >= off) & (sorted_e < off + eh)
    slot = jnp.where(keep, (sorted_e - off) * cap + pos, 0)

    buf = jnp.zeros((eh * cap, d), x.dtype)
    src = jnp.where(keep[:, None], xt[token_of], 0).astype(x.dtype)
    buf = buf.at[slot].add(src)                              # scatter
    buf = buf.reshape(eh, cap, d)
    buf = shard(buf, MODEL, None, None)                      # EP

    # ---- expert SwiGLU (batched over sharded experts) ----
    gate = jnp.einsum("ecd,edf->ecf", buf, p["gate_proj"])
    up = jnp.einsum("ecd,edf->ecf", buf, p["up_proj"])
    h = jax.nn.silu(gate) * up
    out = jnp.einsum("ecf,efd->ecd", h, p["down_proj"])
    out = shard(out, MODEL, None, None).reshape(eh * cap, d)

    # ---- combine ----
    flat_gates = gates.reshape(-1)[order]
    contrib = out[slot] * (flat_gates * keep)[:, None].astype(x.dtype)
    y = jnp.zeros((t, d), x.dtype).at[token_of].add(contrib)
    y = shard(y.reshape(b, s, d), BATCH, None, None)

    if "shared" in p:
        y = y + _shared(p, x, cfg)

    return y, {"lb_loss": lb_loss, "z_loss": z_loss}


# ---------------------------------------------------------------------------
# shard_map expert parallelism (softmax routing, every expert on the mesh)
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
        y = y + _shared(p, x, cfg)
    return y, {"lb_loss": lb, "z_loss": zl}
