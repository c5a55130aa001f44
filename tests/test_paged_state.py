"""Paged ternary state: block pool / prefix cache / paged LLM serving.

Three layers of coverage:

* allocator mechanics — refcounts, LRU parking + eviction, COW,
  the reserved null block, prefix-cache chain matching;
* physical stores — 5-trits/byte pack/unpack exactness, KV
  gather/scatter through block tables, null-block padding routing;
* the paged `LLMExecutor` — **bit-exactness against the contiguous
  baseline** across dense / moe / mamba2, prefix hits surviving forks
  and evictions, the validate() length budget, and the engine-stats
  plumbing.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.models import transformer as TF
from repro.models.config import reduce_for_smoke
from repro.serving import CutieEngine, LLMExecutor, ServerConfig
from repro.serving.blocks import (NULL_BLOCK, BlockPool, KVPagedStore,
                                  OutOfBlocks, PagedSequenceManager,
                                  PrefixCache, StatePagedStore,
                                  chain_hashes, pack_last_axis,
                                  unpack_last_axis)

# ---------------------------------------------------------------------------
# BlockPool: allocate / retain / release / evict / COW
# ---------------------------------------------------------------------------


def test_pool_lifecycle_and_null_block():
    pool = BlockPool(5)
    assert pool.capacity == 4 and pool.n_free == 4
    a, b = pool.allocate(), pool.allocate()
    assert NULL_BLOCK not in (a, b)
    assert pool.n_active == 2
    with pytest.raises(ValueError):
        pool.retain(NULL_BLOCK)
    pool.release(a)
    assert pool.n_free == 3                  # anonymous block -> free list
    with pytest.raises(ValueError):
        pool.release(a)                      # double release


def test_pool_parks_hashed_blocks_and_evicts_lru():
    dropped = []
    pool = BlockPool(4, on_evict=lambda bid, h: dropped.append((bid, h)))
    x, y, z = pool.allocate(), pool.allocate(), pool.allocate()
    pool.set_hash(x, "hx")
    pool.set_hash(y, "hy")
    pool.release(x)                          # parks (LRU-oldest)
    pool.release(y)                          # parks
    pool.release(z)                          # anonymous -> free
    assert pool.n_cached == 2 and pool.n_free == 1
    got = [pool.allocate(), pool.allocate()]  # free first, then evict x
    assert pool.evictions == 1 and dropped == [(x, "hx")]
    assert x in got
    # everything referenced now -> exhausted
    pool.allocate()                          # evicts y
    with pytest.raises(OutOfBlocks):
        pool.allocate()


def test_pool_retain_reactivates_parked_block():
    pool = BlockPool(3)
    a = pool.allocate()
    pool.set_hash(a, "h")
    pool.release(a)
    assert pool.n_cached == 1
    pool.retain(a)                           # prefix hit on a parked block
    assert pool.n_cached == 0 and pool.refcount(a) == 1


def test_pool_copy_on_write():
    pool = BlockPool(5)
    a = pool.allocate()
    assert pool.writable(a) == (a, None)     # exclusive: in-place ok
    pool.retain(a)                           # now shared (ref 2)
    new, pair = pool.writable(a)
    assert new != a and pair == (a, new)
    assert pool.refcount(a) == 1 and pool.refcount(new) == 1
    # hash-registered blocks are shared even at refcount 1
    b = pool.allocate()
    pool.set_hash(b, "hb")
    nb, pairb = pool.writable(b)
    assert nb != b and pairb == (b, nb)


def test_pool_stale_retain_raises():
    """Regression: retaining a freed (or evicted-and-recycled) block id
    silently corrupted the free list — a stale id resurrected into two
    owners.  It must raise instead."""
    pool = BlockPool(4)
    a = pool.allocate()
    pool.release(a)                          # anonymous -> free list
    with pytest.raises(ValueError, match="stale"):
        pool.retain(a)
    # a parked block is NOT stale: prefix hits retain it legitimately
    b = pool.allocate()
    pool.set_hash(b, "hb")
    pool.release(b)                          # parks (LRU)
    pool.retain(b)
    assert pool.refcount(b) == 1


# ---------------------------------------------------------------------------
# PrefixCache: chain hashing + matching
# ---------------------------------------------------------------------------


def test_chain_hash_is_positional_through_chaining():
    toks = np.arange(8)
    h1 = chain_hashes(toks, 4)
    # same second block, different first block -> different chain key
    other = np.concatenate([np.arange(4) + 50, np.arange(4, 8)])
    h2 = chain_hashes(other, 4)
    assert h1[1] != h2[1]
    assert h1 == chain_hashes(toks, 4)       # deterministic


def test_prefix_cache_match_clamp_and_hit_rate():
    cache = PrefixCache()
    toks = np.arange(12)
    hs = chain_hashes(toks, 4)
    for i, h in enumerate(hs):
        cache.insert(h, i + 1)
    hs_m, bids = cache.match(toks, 4, max_blocks=2)   # clamped
    assert bids == [1, 2] and hs_m == hs[:2]
    assert cache.hit_rate == 8 / 12
    # drop only removes the mapping it still owns
    cache.drop(99, hs[0])                    # stale bid: no-op
    assert cache.get(hs[0]) == 1
    cache.drop(1, hs[0])
    assert cache.get(hs[0]) is None


# ---------------------------------------------------------------------------
# PagedSequenceManager: tables, sharing, COW, fork
# ---------------------------------------------------------------------------


def _mgr(num_blocks=12, bs=4):
    pool = BlockPool(num_blocks)
    cache = PrefixCache()
    pool.on_evict = cache.drop
    return PagedSequenceManager(pool, cache, bs)


def test_manager_prefix_reuse_shares_physical_blocks():
    m = _mgr()
    toks = np.arange(10)
    s1 = m.create(1, toks, total_len=12)
    assert s1.n_cached == 0
    m.commit(1)
    s2 = m.create(2, toks, total_len=12)
    assert s2.n_cached == 8                  # 2 full blocks reused
    assert s2.table[:2] == s1.table[:2]      # same physical blocks
    assert s2.table[2] != s1.table[2]        # private tail
    # last prompt token always recomputed: exact-multiple prompt
    s3 = m.create(3, np.arange(8), total_len=12)
    assert s3.n_cached == 4                  # clamped below 8


def test_manager_commit_is_insert_if_absent():
    m = _mgr()
    toks = np.arange(10)
    m.create(1, toks, 12)
    m.commit(1)
    m.create(2, toks, 12)
    m.commit(2)                              # duplicate chain: no steal
    hs = chain_hashes(toks, 4)
    assert m.cache.get(hs[0]) == m.get(1).table[0]
    assert m.get(2).table[0] == m.get(1).table[0]


def test_manager_fork_cow_and_free():
    m = _mgr()
    toks = np.arange(10)
    m.create(1, toks, 12)
    m.commit(1)
    m.fork(1, 2)
    assert m.get(2).table == m.get(1).table
    pair = m.ensure_writable(2, 9)           # child writes pos 9 (block 2)
    assert pair is not None
    assert m.get(2).table[2] != m.get(1).table[2]
    # parent's block 2 is exclusive again -> in-place
    assert m.ensure_writable(1, 9) is None
    m.free(2)
    m.free(1)
    # committed blocks park, private blocks free
    assert m.pool.n_active == 0 and m.pool.n_cached == 2


def test_manager_eviction_invalidates_prefix_then_recovers():
    m = _mgr(num_blocks=7, bs=4)             # capacity 6
    toks = np.arange(10)
    m.create(1, toks, 12)
    m.commit(1)
    m.free(1)                                # 2 parked + 4 free
    # pressure: a novel sequence needing 5 blocks evicts the parked LRU
    m.create(2, np.arange(18) + 90, 20)
    assert m.pool.evictions >= 1
    m.free(2)
    # original prompt now misses (chain broken at the evicted block)
    s3 = m.create(3, toks, 12)
    assert s3.n_cached < 8
    m.commit(3)
    m.free(3)
    s4 = m.create(4, toks, 12)               # recommitted -> hits again
    assert s4.n_cached == 8


def test_manager_probe_false_skips_cache():
    m = _mgr()
    toks = np.arange(10)
    m.create(1, toks, 12)
    m.commit(1)
    s = m.create(2, toks, 12, probe=False)
    assert s.n_cached == 0
    assert m.cache.lookup_tokens == 10       # only seq 1's probe counted


def test_manager_rid_collision_raises():
    """Regression: create()/fork() onto a live rid silently overwrote
    its record, orphaning the old table's refcounts forever (and a
    later free() double-released whichever record survived)."""
    m = _mgr()
    toks = np.arange(10)
    m.create(1, toks, 12)
    with pytest.raises(ValueError, match="already exists"):
        m.create(1, toks, 12)
    with pytest.raises(ValueError, match="already exists"):
        m.fork(1, 1)
    m.create(2, toks, 12)
    with pytest.raises(ValueError, match="live sequence"):
        m.adopt(2, 1)
    assert m.pool.n_active == 6              # nothing leaked by the raises
    m.free(1)
    m.free(2)
    assert m.pool.n_active == 0


def test_fork_commit_adopt_under_eviction_pressure():
    """The speculative write path's fork-commit protocol interleaved
    with eviction: fork a shadow of a committed sequence, COW its write
    span while the pool is tight enough that parked prefix blocks get
    recycled mid-flight, then free-the-original + adopt.  No block may
    be double-released or resurrected, and rollback (freeing the shadow
    instead) must leave the original untouched."""
    m = _mgr(num_blocks=8, bs=4)             # capacity 7
    toks = np.arange(10)
    m.create(1, toks, 12)                    # 3 blocks
    m.commit(1)                              # 2 hash-registered
    # park an unrelated committed prefix so eviction has a victim
    m.create(9, np.arange(8) + 70, 8)
    m.commit(9)
    m.free(9)                                # 2 parked, 2 free
    assert m.pool.n_cached == 2

    # shadow fork + span COW: needs 3 fresh blocks (every forked block
    # is shared) -> the free list runs dry and a parked block is
    # evicted and recycled as a COW destination mid-protocol
    m.fork(1, -1)
    pairs = m.ensure_span_writable(-1, 0, 10)
    assert len(pairs) == 3 and m.pool.evictions >= 1
    for src, dst in pairs:
        assert src != dst
    # commit: free the original, adopt the shadow under its id
    m.free(1)
    m.adopt(-1, 1)
    assert m.has(1) and not m.has(-1)
    # every table entry is exclusively owned and alive
    for bid in m.get(1).table:
        assert m.pool.refcount(bid) == 1
    m.free(1)
    assert m.pool.n_active == 0

    # rollback leg: fork a shadow, COW, then free the *shadow* — the
    # original must still decode (all blocks alive, refcount 1)
    m2 = _mgr(num_blocks=9, bs=4)
    m2.create(1, toks, 12)
    m2.commit(1)
    m2.fork(1, -1)
    m2.ensure_span_writable(-1, 0, 10)
    m2.free(-1)
    for bid in m2.get(1).table:
        assert m2.pool.refcount(bid) == 1
    m2.free(1)
    assert m2.pool.n_active == 0


# ---------------------------------------------------------------------------
# stores: trit packing + gather/scatter
# ---------------------------------------------------------------------------


def test_trit_pack_roundtrip_exact_and_5x():
    rng = np.random.default_rng(0)
    t = jnp.asarray(rng.integers(-1, 2, size=(6, 37)), jnp.int8)
    packed = pack_last_axis(t)
    assert packed.shape == (6, 8)            # ceil(37/5): 5 trits/byte
    assert (unpack_last_axis(packed, 37) == t).all()


def test_kv_store_gather_scatter_roundtrip():
    st = KVPagedStore(2, 6, 4, 2, 8, dtype="bfloat16")
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    rng = np.random.default_rng(1)
    rows = {n: jnp.asarray(rng.normal(size=(2, 2, 2, 8)), jnp.bfloat16)
            for n in ("k", "v")}
    st.pages = st.write_rows(st.pages, tables, jnp.asarray([3, 6]), rows)
    g = st.gather(st.pages, tables)
    assert g["k"].shape == (2, 2, 8, 2, 8)
    assert (g["k"][:, 0, 3] == rows["k"][:, 0]).all()
    assert (g["v"][:, 1, 6] == rows["v"][:, 1]).all()


def test_kv_store_write_span_routes_padding_to_null_block():
    st = KVPagedStore(1, 4, 4, 1, 4)
    table = jnp.asarray([1, 2], jnp.int32)
    kv = {n: jnp.ones((1, 8, 1, 4), jnp.bfloat16) for n in ("k", "v")}
    # start=2, only 3 real rows; 5 padded rows must not land in blocks
    st.pages = st.write_span(st.pages, table, jnp.int32(2), jnp.int32(3),
                             kv)
    g = st.gather(st.pages, table[None])
    real = np.asarray(g["k"][0, 0, :, 0, 0])
    assert (real[2:5] == 1.0).all()
    assert (real[:2] == 0).all() and (real[5:] == 0).all()


def test_state_store_trit_snapshots_are_exact():
    rng = np.random.default_rng(2)
    template = {"a": jnp.zeros((2, 9), jnp.int8),
                "b": jnp.zeros((5,), jnp.int8)}
    st = StatePagedStore(4, template, codec_name="trit")
    state = {"a": jnp.asarray(rng.integers(-1, 2, (2, 9)), jnp.int8),
             "b": jnp.asarray(rng.integers(-1, 2, (5,)), jnp.int8)}
    st.write_(2, state)
    back = st.read_([2])
    assert (back["a"][0] == state["a"]).all()
    assert (back["b"][0] == state["b"]).all()
    # packed block is ~5x smaller than int8
    assert st.pages[0].shape[-1] == -(-18 // 5)


# ---------------------------------------------------------------------------
# LLMExecutor: paged == contiguous, end to end
# ---------------------------------------------------------------------------

_SHARED = list(np.arange(20) % 50)
_PROMPTS = [np.array(_SHARED + [100 + i, i]) for i in range(4)]


def _model(name, layers):
    cfg = reduce_for_smoke(configs.get(name)).replace(n_layers=layers)
    return TF.init_params(cfg, jax.random.PRNGKey(0)), cfg


def _serve(params, cfg, scfg, prompts=_PROMPTS):
    eng = CutieEngine("fcfs")
    ex = LLMExecutor(params, cfg, scfg)
    eng.register("llm", ex)
    for pr in prompts:
        eng.submit(pr, model="llm")
    return eng.run(), ex, eng


@pytest.mark.parametrize("name,layers", [
    ("llama3_2_1b", 1), ("deepseek_moe_16b", 2), ("mamba2_780m", 1),
    ("moonlight_16b_a3b", 2)])
def test_paged_bit_identical_to_contiguous(name, layers):
    params, cfg = _model(name, layers)
    kw = dict(n_slots=2, max_new_tokens=4, max_len=64, block_size=8)
    out_c, _, _ = _serve(params, cfg, ServerConfig(paged=False, **kw))
    out_p, ex, eng = _serve(params, cfg, ServerConfig(paged=True, **kw))
    assert out_c == out_p                    # token-for-token identical
    st = ex.extra_stats()
    assert st["prefix_hit_rate"] > 0.5       # shared-prefix trace
    assert st["prefill_tokens_computed"] < st["prefill_tokens"]
    # stats ride into engine.stats()
    es = eng.stats()["paged_state"]["llm"]
    assert es["prefix_hit_rate"] == st["prefix_hit_rate"]
    assert es["evictions"] == 0 and "block_occupancy" in es


def test_paged_correct_under_eviction_pressure():
    """A pool too small to retain every prefix must evict parked blocks,
    recycle them, and still produce the contiguous answer."""
    params, cfg = _model("llama3_2_1b", 1)
    kw = dict(n_slots=2, max_new_tokens=4, max_len=64, block_size=8)
    # distinct prefixes: every finished prompt parks 2 committed blocks,
    # so a 9-block pool (4 per live seq) runs dry by the 4th admission
    prompts = [np.concatenate([[i], np.arange(21) % 40])
               for i in range(4)]
    tight = ServerConfig(paged=True, num_blocks=10, **kw)
    out_c, _, _ = _serve(params, cfg, ServerConfig(paged=False, **kw),
                         prompts)
    out_p, ex, _ = _serve(params, cfg, tight, prompts)
    assert out_c == out_p
    assert ex.extra_stats()["evictions"] > 0


class _Req:
    def __init__(self, uid, value):
        self.uid, self.value = uid, value


def test_fork_is_copy_on_write_and_does_not_perturb_parent():
    params, cfg = _model("llama3_2_1b", 1)
    scfg = ServerConfig(paged=True, n_slots=2, max_new_tokens=6,
                        max_len=64, block_size=8)
    prompt = np.asarray(_PROMPTS[0], np.int32)

    def drain(ex, reqs=()):
        outs = {}
        rep = ex.execute(list(reqs))
        for uid, toks in rep.completions:
            outs[uid] = toks
        for _ in range(40):
            if not ex.has_resident():
                break
            for uid, toks in ex.execute([]).completions:
                outs[uid] = toks
        return outs

    base = drain(LLMExecutor(params, cfg, scfg), [_Req(1, prompt)])

    ex = LLMExecutor(params, cfg, scfg)
    ex.execute([_Req(1, prompt)])            # prefill + first decode
    ex.fork(1, 2)
    # the child shares every physical block until someone writes
    assert ex.manager.get(2).table == ex.manager.get(1).table
    outs = drain(ex)
    assert outs[1] == base[1]                # parent bit-identical
    assert outs[2] == base[1]                # greedy child follows suit
    assert ex.pool.n_active == 0             # both released on completion


def test_validate_rejects_prompt_plus_budget_overflow():
    params, cfg = _model("llama3_2_1b", 1)
    scfg = ServerConfig(n_slots=1, max_len=32, max_new_tokens=8,
                        block_size=8)
    ex = LLMExecutor(params, cfg, scfg)
    ex.validate(np.arange(24))               # 24 + 8 == 32: fits
    with pytest.raises(ValueError, match="max_new_tokens"):
        ex.validate(np.arange(25))           # 25 + 8 > 32
    with pytest.raises(ValueError, match="non-empty"):
        ex.validate(np.zeros((0,), np.int32))


def test_free_capacity_is_block_limited():
    params, cfg = _model("llama3_2_1b", 1)
    scfg = ServerConfig(paged=True, n_slots=4, max_len=64, block_size=8,
                        max_new_tokens=4, num_blocks=1 + 2 * 8)
    ex = LLMExecutor(params, cfg, scfg)
    assert ex.free_capacity() == 2           # 16 blocks / 8 per seq


def test_config_rejects_misaligned_block_size():
    params, cfg = _model("llama3_2_1b", 1)
    with pytest.raises(ValueError, match="multiple"):
        LLMExecutor(params, cfg, ServerConfig(max_len=60, block_size=8))


# ---------------------------------------------------------------------------
# satellite: pipeline execution plan + fused-on-mesh warning
# ---------------------------------------------------------------------------


def _cnn_program(c=8, depth=2, seed=0):
    from repro.core import engine as core_engine

    keys = jax.random.split(jax.random.PRNGKey(seed), depth)
    instrs = []
    for k in keys:
        k1, k2 = jax.random.split(k)
        w = jax.random.normal(k1, (3, 3, c, c))
        bn = {"gamma": jax.random.normal(k2, (c,)) + 0.5,
              "beta": jnp.zeros((c,)), "mean": jnp.zeros((c,)),
              "var": jnp.ones((c,))}
        instrs.append(core_engine.compile_layer(w, bn))
    return core_engine.CutieProgram(
        instrs, core_engine.CutieInstance(n_i=c, n_o=c))


def test_execution_plan_modes():
    from repro.pipeline import CutiePipeline

    prog = _cnn_program()
    assert CutiePipeline(prog, backend="ref").execution_plan()["mode"] \
        == "scan"
    plan = CutiePipeline(prog, backend="fused").execution_plan()
    assert plan["mode"] == "program" and plan["backend"] == "fused"


def test_fused_backend_on_mesh_warns_and_reports_per_layer():
    from repro.pipeline import CutiePipeline

    prog = _cnn_program(seed=3)
    with pytest.warns(UserWarning, match="per-layer"):
        pipe = CutiePipeline(prog, backend="fused", mesh=1)
    plan = pipe.execution_plan()
    assert plan["mode"] == "sharded-per-layer"
    assert "dropped" in plan["reason"]
    # non-program backends shard without complaint
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        pipe2 = CutiePipeline(prog, backend="ref", mesh=1)
    assert pipe2.execution_plan()["mode"] == "sharded-per-layer"
