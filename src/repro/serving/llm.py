"""Autoregressive LLM serving: paged-state slot-resident executor.

The CUTIE ASIC serves autonomously from a layer FIFO with the host
asleep (paper Fig. 3); the framework analogue is a serving loop whose
inner decode is ONE jitted step for the whole slot batch.  This module
is that loop rebuilt on the paged-state subsystem
(:mod:`repro.serving.blocks`):

* decode memory is a fixed pool of physical blocks, not a per-slot
  contiguous cache — sequences *share* identical prompt-prefix blocks
  (content-hash chain over token blocks), freed blocks park in an LRU
  set ready for the next matching prompt, and forks are copy-on-write;
* prefill and decode are **explicitly separate jitted paths**
  (JetStream's `prefill() -> ExistingPrefix` / `decode()` split):
  :meth:`LLMExecutor.prefill` matches the prefix cache, gathers the
  cached prefix KV, and runs the model only over the *suffix* from the
  first novel block; :meth:`LLMExecutor.decode` advances every live
  slot one token, gathering per-slot blocks through the block table;
* SSM/mamba2 state slots draw from the same pool: a block holds one
  recurrent-state snapshot at a token-block boundary (the SSM analogue
  of a KV prefix), optionally packed 5 trits/byte via
  `repro.core.codec` for ternary states.

MoE models count, on the device inside the paged decode and prefill
programs, the routed (token, expert) assignments of live tokens by
whether this executor holds the expert
(:attr:`LLMExecutor.moe_assignments`); the totals reach the metrics
registry as ``moe_assignments_total{model, site, share}`` only when the
registry is read, so counting adds no blocking read to a step.

``ServerConfig(paged=False)`` keeps a contiguous cache but runs the
*same* prefill/decode math, so paged-vs-contiguous bit-exactness is
testable by construction (see tests/test_paged_state.py).  Exact
equality additionally wants ``cfg.attn_kv_chunk <= block_size`` so the
flash kv-chunk grid is identical for full-prompt and suffix prefill.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import decoding as DEC
from repro.models.config import ArchConfig
from repro.serving.blocks import (NULL_BLOCK, BlockPool, KVPagedStore,
                                  OutOfBlocks, PagedSequenceManager,
                                  PrefixCache, StatePagedStore, chain_hashes)
from repro.serving.executors import ExecutionReport, Executor

_ATTN_FAMILIES = ("dense", "vlm", "moe")


@dataclasses.dataclass(frozen=True)
class ServerConfig:
    max_len: int = 256
    n_slots: int = 4
    max_new_tokens: int = 32
    eos_id: int = -1              # -1: run to max_new_tokens
    temperature: float = 0.0      # 0 = greedy
    seed: int = 0
    # paged-state knobs
    paged: bool = True
    block_size: int = 16
    num_blocks: Optional[int] = None   # physical blocks incl. null; default
    #                                    (n_slots + 2) tables' worth + null
    kv_codec: str = "raw"              # "raw" | "trit" (lossy, opt-in)
    state_codec: str = "raw"           # "raw" | "trit" (exact for trits)
    prefix_caching: bool = True


@dataclasses.dataclass(frozen=True)
class ExistingPrefix:
    """How much of a prompt was served from the prefix cache
    (JetStream's `ExistingPrefix` shape: the reusable prefix plus its
    backing cache handle — here, physical block ids)."""

    common_prefix_tokens: int
    blocks: tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class PrefillResult:
    first_token: int
    prefix: ExistingPrefix
    prompt_len: int
    tokens_computed: int     # suffix tokens actually run (excl. padding)


_SITES, _SHARES = ("decode", "prefill"), ("held", "elsewhere")


def _route_counts(routes, live, cfg: ArchConfig):
    """``[held, elsewhere]`` int32: the routed assignments of the live
    tokens, ``routes (L, B, S, k)`` expert ids and ``live (B, S)``, by
    whether the expert is one of the ``cfg.n_held`` held here."""
    lo = cfg.expert_offset
    held = (routes >= lo) & (routes < lo + cfg.n_held)
    live = jnp.broadcast_to(live[None, :, :, None], routes.shape)
    n_held = jnp.sum(held & live, dtype=jnp.int32)
    return jnp.stack([n_held, jnp.sum(live, dtype=jnp.int32) - n_held])


def _bucket(n: int, floor: int) -> int:
    """Smallest power-of-2 >= n, floored — bounds prefill jit variants."""
    b = floor
    while b < n:
        b *= 2
    return b


class LLMExecutor(Executor):
    """Slot-resident continuous-batching decode loop over paged state."""

    def __init__(self, params, cfg: ArchConfig, scfg: ServerConfig):
        if cfg.family not in _ATTN_FAMILIES + ("ssm",):
            raise NotImplementedError(
                f"LLMExecutor serves {_ATTN_FAMILIES + ('ssm',)}, "
                f"got family={cfg.family!r}")
        if scfg.max_len % scfg.block_size:
            raise ValueError(
                f"max_len={scfg.max_len} must be a multiple of "
                f"block_size={scfg.block_size}")
        self.params, self.cfg, self.scfg = params, cfg, scfg
        self.is_ssm = cfg.family == "ssm"
        self.slots: list = [None] * scfg.n_slots       # resident Requests
        # each slot's next write position, kept on the host only: the
        # host sets every value, so reading one costs no device round trip
        self.pos = np.zeros((scfg.n_slots,), np.int32)
        self.cur_tok = jnp.zeros((scfg.n_slots, 1), jnp.int32)
        self._tokens: dict[int, list[int]] = {}        # uid -> output tokens
        self._prompts: dict[int, np.ndarray] = {}      # uid -> prompt tokens
        self._key = jax.random.PRNGKey(scfg.seed)
        self._prefill_fns: dict = {}                   # jit variant cache
        self.prefill_tokens = 0          # prompt tokens admitted
        self.prefill_tokens_computed = 0  # of those, actually run
        # routed assignments [site][share] (_SITES x _SHARES), summed on
        # the device by the paged programs; None for an unrouted model
        self.moe_assignments = (
            jnp.zeros((2, 2), jnp.int32)
            if cfg.family == "moe" and scfg.paged else None)
        self._published = np.zeros((2, 2), np.int64)

        bs = scfg.block_size
        self.blocks_per_seq = scfg.max_len // bs
        nb = scfg.num_blocks or 1 + (scfg.n_slots + 2) * self.blocks_per_seq
        self.cache = PrefixCache()
        self.pool = BlockPool(nb, on_evict=self._on_evict)

        if scfg.paged:
            self._init_paged(nb)
        else:
            self.caches = DEC.init_caches(cfg, scfg.n_slots, scfg.max_len)
            self._decode_fn = jax.jit(
                lambda p, t, c, pos: DEC.decode_step(p, t, c, pos, cfg))
        self._ssm_seg = jax.jit(
            lambda p, t, c, start: DEC.ssm_prefill(p, t, c, cfg, start))

    def _init_paged(self, num_blocks: int) -> None:
        cfg, scfg = self.cfg, self.scfg
        if self.is_ssm:
            one = DEC.init_caches(cfg, 1, scfg.max_len)
            template = jax.tree.map(lambda a: a[:, 0], one["ssm"])
            self.state_store = StatePagedStore(
                num_blocks, template, codec_name=scfg.state_codec)
            # one permanently-held working block per slot
            self._slot_bids = jnp.asarray(
                [self.pool.allocate() for _ in range(scfg.n_slots)],
                jnp.int32)
            store = self.state_store

            def step(p, tok, pages, bids, pos):
                st = store.read(pages, bids)       # leaves (B, L, ...)
                caches = {"ssm": jax.tree.map(
                    lambda a: jnp.moveaxis(a, 0, 1), st)}
                logits, new = DEC.decode_step(p, tok, caches, pos, cfg)
                per_seq = jax.tree.map(
                    lambda a: jnp.moveaxis(a, 0, 1), new["ssm"])
                return logits, store.write_batch(pages, bids, per_seq)

            self._decode_fn = jax.jit(step)
            return
        self.manager = PagedSequenceManager(self.pool, self.cache,
                                            scfg.block_size)
        self.kv_store = KVPagedStore(
            cfg.n_layers, num_blocks, scfg.block_size, cfg.n_kv,
            cfg.d_head, dtype=cfg.kv_dtype, codec_name=scfg.kv_codec,
            latent_dim=cfg.latent_dim)
        store = self.kv_store

        def step(p, tok, pages, tables, pos, *acc):
            kv = store.gather(pages, tables)
            out = DEC.decode_step(p, tok, {"kv": kv}, pos, cfg,
                                  with_routes=bool(acc))
            logits, new = out[:2]
            b = pos.shape[0]
            rows = {n: a[:, jnp.arange(b), pos]
                    for n, a in new["kv"].items()}
            pages = store.write_rows(pages, tables, pos, rows)
            if not acc:
                return logits, pages
            # an empty slot's table is all NULL_BLOCK
            live = (tables[:, 0] != NULL_BLOCK)[:, None]
            return logits, pages, acc[0].at[0].add(
                _route_counts(out[2], live, cfg))

        self._decode_fn = jax.jit(step)
        self._count_prefill = jax.jit(lambda acc, routes, n: acc.at[1].add(
            _route_counts(routes, (jnp.arange(routes.shape[2]) < n)[None],
                         cfg)))

    def _on_evict(self, bid: int, h: str) -> None:
        """LRU eviction callback: drop the cache mapping, leave a trace
        event so cache-pressure stalls are visible on the timeline."""
        self.cache.drop(bid, h)
        self.obs.trace.instant("prefix_evict", cat="prefix", block=bid)
        self.obs.metrics.counter(
            "prefix_evictions_total",
            "cached blocks evicted under pool pressure").inc()

    # -- observability -------------------------------------------------------

    def bind_obs(self, obs, model: str = "") -> None:
        super().bind_obs(obs, model)
        if self.moe_assignments is not None:
            obs.metrics.collect(f"moe_assignments:{model}",
                                self._publish_assignments)

    def _publish_assignments(self) -> None:
        """Bring the device-side totals into ``moe_assignments_total``:
        one blocking read, made only when the registry is read."""
        now = np.asarray(self.moe_assignments, np.int64)
        c = self.obs.metrics.counter(
            "moe_assignments_total", "routed (token, expert) assignments "
            "of live tokens, by site and by whether this executor holds "
            "the expert")
        for i, site in enumerate(_SITES):
            for j, share in enumerate(_SHARES):
                c.inc(float(now[i, j] - self._published[i, j]),
                      model=self.model, site=site, share=share)
        self._published = now

    # -- engine protocol ----------------------------------------------------

    def validate(self, prompt) -> np.ndarray:
        arr = np.asarray(prompt, np.int32)
        if arr.ndim != 1 or arr.size == 0:
            raise ValueError(f"expected a non-empty 1-D token prompt, "
                             f"got shape {arr.shape}")
        budget = self.scfg.max_len - self.scfg.max_new_tokens
        if arr.size > budget:
            raise ValueError(
                f"prompt of {arr.size} tokens cannot fit: prompt + "
                f"max_new_tokens ({self.scfg.max_new_tokens}) must stay "
                f"within max_len={self.scfg.max_len} "
                f"(prompt budget {budget})")
        return arr

    def free_capacity(self) -> int:
        free_slots = sum(r is None for r in self.slots)
        if not self.scfg.paged or self.is_ssm:
            return free_slots
        avail = self.pool.n_free + self.pool.n_cached
        return min(free_slots, avail // self.blocks_per_seq)

    def has_resident(self) -> bool:
        return any(r is not None for r in self.slots)

    def execute(self, requests) -> ExecutionReport:
        """Prefill newly admitted requests, advance all active slots one
        step (one token each here; possibly several under speculative
        decoding), release finished ones."""
        for req in requests:
            self._admit(req)
        live = sum(r is not None for r in self.slots)
        completions: list = []
        if live == 0:
            return ExecutionReport(completions, 0, self.scfg.n_slots,
                                   tokens_generated={})
        trace = self.obs.trace
        with trace.span("decode", cat="llm", live=live):
            step_tokens = self._step_tokens()
        trace.counter("blocks", {
            "active": self.pool.n_active, "cached": self.pool.n_cached,
            "free": self.pool.n_free})
        with trace.span("emit", cat="llm"):
            tokens_generated = self._emit(step_tokens, completions)
        return ExecutionReport(completions, live, self.scfg.n_slots,
                               tokens_generated=tokens_generated)

    def _emit(self, step_tokens: dict, completions: list) -> dict:
        """Append each live slot's new tokens, finish and release the
        slots that stopped (appending to ``completions``); returns the
        tokens emitted per request."""
        tokens_generated: dict[int, int] = {}
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            toks = self._tokens[req.uid]
            plen = len(self._prompts[req.uid])
            finished = False
            emitted = 0
            for tok in step_tokens.get(i, ()):
                toks.append(tok)
                emitted += 1
                # same stopping rule as one-token decode: `plen +
                # len(toks) - 1` is the position counter a plain decode
                # loop would hold after emitting this token, so a
                # multi-token step truncates exactly where the
                # sequential loop would have stopped
                if tok == self.scfg.eos_id or \
                        len(toks) >= self.scfg.max_new_tokens or \
                        plen + len(toks) - 1 >= self.scfg.max_len - 1:
                    finished = True
                    break
            tokens_generated[req.uid] = emitted
            if finished:
                completions.append((req.uid, self._tokens.pop(req.uid)))
                self._release(i)
        return tokens_generated

    def _step_tokens(self) -> dict[int, list[int]]:
        """One engine step's new tokens per live slot, brought to the
        host in one transfer.  The base decode loop emits exactly one;
        `SpecExecutor` overrides this with the propose/verify/accept
        cycle (1 .. k+1 tokens per slot)."""
        nxt = self.decode()
        with self.obs.trace.span("sync", cat="llm"):
            host = np.asarray(nxt)
            out = {i: [int(host[i])]
                   for i, r in enumerate(self.slots) if r is not None}
        self._count_reads("sync")
        return out

    def _count_reads(self, site: str, n: int = 1) -> None:
        """Count ``n`` blocking device-to-host reads made at ``site``."""
        self.obs.metrics.counter(
            "device_reads_total", "blocking device-to-host reads made "
            "by executors on the host path, by site").inc(
            n, model=self.model, site=site)

    def extra_stats(self) -> dict:
        """Paged-state accounting for ``engine.stats()``."""
        out = {
            "paged": self.scfg.paged,
            "block_size": self.scfg.block_size,
            "block_occupancy": self.pool.occupancy(),
            "blocks_active": self.pool.n_active,
            "blocks_cached": self.pool.n_cached,
            "blocks_free": self.pool.n_free,
            "evictions": self.pool.evictions,
            "prefix_hit_rate": self.cache.hit_rate,
            "prefix_entries": len(self.cache),
            "prefill_tokens": self.prefill_tokens,
            "prefill_tokens_computed": self.prefill_tokens_computed,
        }
        if not self.scfg.paged:
            out.update(block_occupancy=None, prefix_hit_rate=None)
        return out

    # -- prefill path --------------------------------------------------------

    def prefill(self, uid: int, tokens: np.ndarray) -> PrefillResult:
        """Run (only the novel part of) a prompt and make ``uid``
        resident in a free slot.  Returns the sampled first token and
        the :class:`ExistingPrefix` served from the cache."""
        slot = self.slots.index(None)
        plen = len(tokens)
        self._prompts[uid] = np.asarray(tokens, np.int64)
        self.prefill_tokens += plen
        with self.obs.trace.span("prefill", tid=uid, cat="request",
                                 prompt_len=plen) as end:
            if self.is_ssm:
                res = self._prefill_ssm(uid, slot, tokens)
            elif self.scfg.paged:
                res = self._prefill_paged(uid, slot, tokens)
            else:
                res = self._prefill_contiguous(uid, slot, tokens)
            cached = res.prefix.common_prefix_tokens
            end.update(cached=cached, computed=res.tokens_computed)
        self.prefill_tokens_computed += res.tokens_computed
        self.obs.trace.instant("prefix_hit" if cached else "prefix_miss",
                               tid=uid, cat="prefix", tokens=cached)
        self.obs.metrics.counter(
            "prefix_lookups_total", "prompt prefixes looked up in the "
            "block cache").inc(outcome="hit" if cached else "miss")
        self.obs.metrics.counter(
            "prefill_tokens_total", "prompt tokens by whether the prefix "
            "cache served them").inc(cached, source="cached")
        self.obs.metrics.counter(
            "prefill_tokens_total", "prompt tokens by whether the prefix "
            "cache served them").inc(res.tokens_computed, source="computed")
        self.pos[slot] = plen
        self.cur_tok = self.cur_tok.at[slot, 0].set(res.first_token)
        return res

    def _suffix_fn(self, n_cached: int, s_bucket: int):
        """One jit variant per (cached length, suffix bucket)."""
        key = ("kv", n_cached, s_bucket)
        if key not in self._prefill_fns:
            cfg = self.cfg
            self._prefill_fns[key] = jax.jit(
                lambda p, t, pkv: DEC.prefill_with_prefix(p, t, pkv, cfg))
        return self._prefill_fns[key]

    def _run_suffix(self, tokens: np.ndarray, n_cached: int, prefix_kv):
        """Shared paged/contiguous suffix prefill: bucket, run, slice."""
        suffix = np.asarray(tokens[n_cached:], np.int32)
        n_real = len(suffix)
        sb = _bucket(n_real, self.scfg.block_size)
        padded = np.zeros((1, sb), np.int32)
        padded[0, :n_real] = suffix
        fn = self._suffix_fn(n_cached, sb)
        logits, kv, routes = fn(self.params, jnp.asarray(padded), prefix_kv)
        if self.moe_assignments is not None:
            self.moe_assignments = self._count_prefill(
                self.moe_assignments, routes, jnp.int32(n_real))
        return logits[0, n_real - 1], kv, n_real

    def _prefill_paged(self, uid, slot, tokens) -> PrefillResult:
        scfg = self.scfg
        total = min(len(tokens) + scfg.max_new_tokens + 1, scfg.max_len)
        seq = self.manager.create(uid, tokens, total,
                                  probe=scfg.prefix_caching)
        c = seq.n_cached
        bs = scfg.block_size
        table_row = jnp.asarray(
            self.manager.table_array(uid, self.blocks_per_seq))
        prefix_kv = self.kv_store.gather(
            self.kv_store.pages, table_row[None, :c // bs]) if c else \
            DEC.empty_prefix(self.cfg)
        last_logits, kv, n_real = self._run_suffix(tokens, c, prefix_kv)
        self.kv_store.pages = self.kv_store.write_span(
            self.kv_store.pages, table_row, jnp.int32(c),
            jnp.int32(n_real), {n: a[:, 0] for n, a in kv.items()})
        if scfg.prefix_caching:
            self.manager.commit(uid)
        first = self._first_token(last_logits)
        self._tokens[uid] = [first]
        self.slots[slot] = _Resident(uid)
        return PrefillResult(first, ExistingPrefix(c, tuple(
            seq.table[:c // bs])), len(tokens), n_real)

    def _prefill_contiguous(self, uid, slot, tokens) -> PrefillResult:
        plen = len(tokens)
        last_logits, kv, n_real = self._run_suffix(
            tokens, 0, DEC.empty_prefix(self.cfg))
        self.caches["kv"] = {
            n: self.caches["kv"][n].at[:, slot, :plen].set(
                a[:, 0, :plen].astype(self.caches["kv"][n].dtype))
            for n, a in kv.items()}
        first = self._first_token(last_logits)
        self._tokens[uid] = [first]
        self.slots[slot] = _Resident(uid)
        return PrefillResult(first, ExistingPrefix(0, ()), plen, n_real)

    def _prefill_ssm(self, uid, slot, tokens) -> PrefillResult:
        """SSM prefill in block_size segments so recurrent state exists
        at every block boundary — those snapshots are what the prefix
        cache stores (the SSM analogue of cached KV rows)."""
        cfg, scfg = self.cfg, self.scfg
        bs = scfg.block_size
        toks = np.asarray(tokens, np.int64)
        plen = len(toks)
        k_max = (plen - 1) // bs
        c, state, hit_blocks = 0, None, ()
        hashes = chain_hashes(toks, bs)[:k_max]
        if scfg.paged and scfg.prefix_caching:
            _, matched = self.cache.match(toks, bs, max_blocks=k_max)
            if matched:
                bid = matched[-1]
                self.pool.retain(bid)
                state = jax.tree.map(
                    lambda a: a[0], self.state_store.read_([bid]))
                self.pool.release(bid)
                c, hit_blocks = len(matched) * bs, tuple(matched)
        if state is None:
            one = DEC.init_caches(cfg, 1, scfg.max_len)
            state = jax.tree.map(lambda a: a[:, 0], one["ssm"])

        def batched(st):
            return {"ssm": jax.tree.map(lambda a: a[:, None], st)}

        logits = None
        pos = c
        prev_h = hashes[c // bs - 1] if c else None
        for i in range(c // bs, k_max):
            seg = jnp.asarray(toks[None, i * bs:(i + 1) * bs], jnp.int32)
            logits, caches = self._ssm_seg(
                self.params, seg, batched(state), jnp.int32(pos))
            state = jax.tree.map(lambda a: a[:, 0], caches["ssm"])
            pos += bs
            prev_h = hashes[i]
            if scfg.paged and scfg.prefix_caching and \
                    self.cache.get(prev_h) is None:
                self._commit_snapshot(prev_h, state)
        if pos < plen:
            seg = jnp.asarray(toks[None, pos:plen], jnp.int32)
            logits, caches = self._ssm_seg(
                self.params, seg, batched(state), jnp.int32(pos))
            state = jax.tree.map(lambda a: a[:, 0], caches["ssm"])
        n_real = plen - c
        if self.scfg.paged:
            self.state_store.write_(int(self._slot_bids[slot]), state)
            self._count_reads("prefill")
        else:
            self.caches["ssm"] = jax.tree.map(
                lambda full, one: full.at[:, slot].set(one),
                self.caches["ssm"], state)
        first = self._first_token(logits[0, -1])
        self._tokens[uid] = [first]
        self.slots[slot] = _Resident(uid)
        return PrefillResult(first, ExistingPrefix(c, hit_blocks),
                             plen, n_real)

    def _first_token(self, last_logits) -> int:
        """Sample a prompt's first output token and read it back."""
        first = int(self._sample(last_logits[None])[0])
        self._count_reads("prefill")
        return first

    def _commit_snapshot(self, h: str, state) -> None:
        """Park one boundary snapshot in the cache; skip when the pool
        is under active pressure rather than failing the prefill."""
        try:
            bid = self.pool.allocate()
        except OutOfBlocks:
            return
        self.state_store.write_(bid, state)
        self.pool.set_hash(bid, h)
        self.cache.insert(h, bid)
        self.pool.release(bid)      # refcount 0 + hash -> parked (LRU)

    # -- decode path ---------------------------------------------------------

    def decode(self) -> jax.Array:
        """One jitted decode step for every slot; returns the sampled
        next token per slot (junk rows for empty slots)."""
        trace = self.obs.trace
        pos = self._device_pos()
        if not self.scfg.paged:
            with trace.span("dispatch", cat="llm"):
                logits, self.caches = self._decode_fn(
                    self.params, self.cur_tok, self.caches, pos)
        elif self.is_ssm:
            with trace.span("dispatch", cat="llm"):
                logits, self.state_store.pages = self._decode_fn(
                    self.params, self.cur_tok, self.state_store.pages,
                    self._slot_bids, pos)
        else:
            with trace.span("cow", cat="llm"):
                self._cow_for_decode()
            with trace.span("tables", cat="llm"):
                tables = jnp.asarray(np.stack([
                    self.manager.table_array(r.uid, self.blocks_per_seq)
                    if r is not None else
                    np.zeros((self.blocks_per_seq,), np.int32)
                    for r in self.slots]))
            args = (self.params, self.cur_tok, self.kv_store.pages, tables,
                    pos)
            with trace.span("dispatch", cat="llm"):
                if self.moe_assignments is None:
                    logits, self.kv_store.pages = self._decode_fn(*args)
                else:
                    logits, self.kv_store.pages, self.moe_assignments = \
                        self._decode_fn(*args, self.moe_assignments)
        with trace.span("sample", cat="llm"):
            nxt = self._sample(logits[:, -1])
            self.pos += 1
            self.cur_tok = nxt[:, None]
        return nxt

    def _cow_for_decode(self) -> None:
        """Make every live slot's write-target block exclusively owned
        (fires only after forks / prefix sharing into the write block)."""
        pairs = []
        for i, r in enumerate(self.slots):
            if r is None:
                continue
            pair = self.manager.ensure_writable(r.uid, int(self.pos[i]))
            if pair is not None:
                pairs.append(pair)
        self.kv_store.apply_copies(pairs)

    def _device_pos(self) -> jax.Array:
        """The slot positions as a device array.  JAX may read a host
        buffer after the call it was handed to has returned, so it gets a
        copy that later writes to ``self.pos`` cannot reach."""
        return jnp.asarray(self.pos.copy())

    # -- engine failure paths ------------------------------------------------

    def evict(self, uid: int) -> bool:
        """Release everything held for ``uid`` (engine failure paths:
        retry, bisect, quarantine, timeout).  Defensive against partial
        admission — a prefill that died mid-way may have registered the
        prompt or the sequence without ever occupying a slot."""
        found = False
        for i, r in enumerate(self.slots):
            if r is not None and r.uid == uid:
                self._release(i)
                found = True
                break
        self._tokens.pop(uid, None)
        self._prompts.pop(uid, None)
        if self.scfg.paged and not self.is_ssm and self.manager.has(uid):
            self.manager.free(uid)
        return found

    # -- serving-state checkpoint --------------------------------------------

    def snapshot(self) -> tuple[dict, dict]:
        """All mutable serving state as ``(arrays, meta)``.

        ``arrays`` is a pytree of device arrays (paged KV/state pages,
        slot positions, pending tokens, the sampling PRNG key) for the
        checkpoint leaf store — ternary state pages trit-pack 5/byte
        there for free.  ``meta`` is JSON-safe host bookkeeping (slot
        residency, emitted tokens, prompts, pool/prefix/block-table
        state).  ``restore()`` is the exact inverse; a fresh executor
        built from the same ``(params, cfg, scfg)`` continues decoding
        bit-identically.
        """
        tree: dict = {"pos": self._device_pos(), "cur_tok": self.cur_tok,
                      "rng_key": self._key}
        if self.scfg.paged:
            if self.is_ssm:
                tree["pages"] = self.state_store.pages
                tree["slot_bids"] = self._slot_bids
            else:
                tree["pages"] = self.kv_store.pages
        else:
            tree["caches"] = self.caches
        meta: dict = {
            "slots": [r.uid if r is not None else None
                      for r in self.slots],
            "tokens": {str(u): [int(t) for t in v]
                       for u, v in self._tokens.items()},
            "prompts": {str(u): np.asarray(v).tolist()
                        for u, v in self._prompts.items()},
            "prefill_tokens": int(self.prefill_tokens),
            "prefill_tokens_computed": int(self.prefill_tokens_computed),
            "pool": self.pool.state_dict(),
            "cache": self.cache.state_dict(),
        }
        if self.scfg.paged and not self.is_ssm:
            meta["manager"] = self.manager.state_dict()
        return tree, meta

    def restore(self, tree: dict, meta: dict) -> None:
        """Load a :meth:`snapshot` into this executor (same config)."""
        self.pos = np.array(tree["pos"], np.int32)
        self.cur_tok = jnp.asarray(np.asarray(tree["cur_tok"]), jnp.int32)
        self._key = jnp.asarray(np.asarray(tree["rng_key"]), jnp.uint32)
        if self.scfg.paged:
            if self.is_ssm:
                self.state_store.pages = [jnp.asarray(p)
                                          for p in tree["pages"]]
                self._slot_bids = jnp.asarray(
                    np.asarray(tree["slot_bids"]), jnp.int32)
            else:
                self.kv_store.pages = {k: jnp.asarray(v)
                                       for k, v in tree["pages"].items()}
        else:
            self.caches = jax.tree.map(jnp.asarray, tree["caches"])
        self.slots = [None if u is None else _Resident(int(u))
                      for u in meta["slots"]]
        self._tokens = {int(u): [int(t) for t in v]
                        for u, v in meta["tokens"].items()}
        self._prompts = {int(u): np.asarray(v, np.int64)
                         for u, v in meta["prompts"].items()}
        self.prefill_tokens = int(meta["prefill_tokens"])
        self.prefill_tokens_computed = int(meta["prefill_tokens_computed"])
        self.pool.load_state(meta["pool"])
        self.cache.load_state(meta["cache"])
        if self.scfg.paged and not self.is_ssm:
            self.manager.load_state(meta["manager"])

    # -- fork ----------------------------------------------------------------

    def fork(self, uid: int, new_uid: int) -> int:
        """Copy-on-write fork of a resident sequence into a free slot
        (standalone/executor-driven use; not yet engine-wired).

        The child shares every block with the parent until either
        writes; divergence costs one block copy at the write point.
        """
        if self.is_ssm or not self.scfg.paged:
            raise NotImplementedError("fork requires paged KV mode")
        src = next(i for i, r in enumerate(self.slots)
                   if r is not None and r.uid == uid)
        dst = self.slots.index(None)
        self.manager.fork(uid, new_uid)
        self.slots[dst] = _Resident(new_uid)
        self._tokens[new_uid] = list(self._tokens[uid])
        self._prompts[new_uid] = self._prompts[uid]
        self.pos[dst] = self.pos[src]
        self.cur_tok = self.cur_tok.at[dst].set(self.cur_tok[src])
        return dst

    # -- internals ----------------------------------------------------------

    def _admit(self, req) -> None:
        self.prefill(req.uid, req.value)

    def _release(self, slot: int) -> None:
        req = self.slots[slot]
        self.slots[slot] = None
        self.pos[slot] = 0      # empty slots write to NULL
        self.cur_tok = self.cur_tok.at[slot, 0].set(0)
        self._prompts.pop(req.uid, None)
        if self.scfg.paged and not self.is_ssm and \
                self.manager.has(req.uid):
            self.manager.free(req.uid)

    def _sample(self, lg) -> jax.Array:
        """lg (B, V_padded) -> sampled token ids (B,) int32."""
        lg = lg[:, : self.cfg.vocab]
        if self.scfg.temperature <= 0:
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)
        self._key, k = jax.random.split(self._key)
        return jax.random.categorical(
            k, lg / self.scfg.temperature, axis=-1).astype(jnp.int32)

    @property
    def n_jit_variants(self) -> int:
        return len(self._prefill_fns) + 1       # + the decode step


class _Resident:
    """Slot marker for sequences admitted via prefill() directly
    (engine requests carry .uid already; this mirrors that shape)."""

    __slots__ = ("uid",)

    def __init__(self, uid: int):
        self.uid = uid
