"""Slot positions kept on the host (`LLMExecutor.pos`, a numpy array).

The executor sets every position itself: the prompt length at
admission, one more per decode step, 0 on release, the source slot's on
a fork, and the accepted run under speculation.  So the decode step
reads none back from the device.  These tests hold the host array to the
device arithmetic it replaced, and the served tokens to paths that share
none of it.
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.models import transformer as TF
from repro.models.config import reduce_for_smoke
from repro.serving import (CutieEngine, LLMExecutor, ServerConfig,
                           SpecExecutor)

_SHARED = list(np.arange(20) % 50)
_PROMPTS = [np.array(_SHARED + [100 + i, i], np.int32) for i in range(4)]
_KW = dict(max_len=64, block_size=8)


@functools.cache
def _model(name="llama3_2_1b", layers=1, seed=0):
    cfg = reduce_for_smoke(configs.get(name)).replace(n_layers=layers)
    return TF.init_params(cfg, jax.random.PRNGKey(seed)), cfg


class _Req:
    def __init__(self, uid, value):
        self.uid, self.value = uid, value


def _drain(ex, outs):
    """Step ``ex`` until no slot is resident; ``outs`` gains every
    completion, uid -> tokens."""
    while ex.has_resident():
        outs.update(ex.execute([]).completions)
    return outs


def _serve(ex, prompts, **submit_kw):
    """Serve ``prompts`` through an engine; their tokens, in order."""
    eng = CutieEngine("fcfs")
    eng.register("llm", ex)
    handles = [eng.submit(p, model="llm", **submit_kw) for p in prompts]
    eng.run()
    return [h.request.result for h in handles]


def _assert_pending_at_pos(ex):
    """Each resident slot writes its pending token (the last one
    emitted) at the position the host holds for it."""
    for i, r in enumerate(ex.slots):
        if r is not None:
            want = len(ex._prompts[r.uid]) + len(ex._tokens[r.uid]) - 1
            assert ex.pos[i] == want, (i, ex.pos[i], want)


class _DevicePositions(LLMExecutor):
    """`LLMExecutor` that also keeps the positions on the device, moved
    by the `jnp` updates the executor made before they lived on the
    host."""

    def __init__(self, *args):
        super().__init__(*args)
        self.dev_pos = jnp.zeros((self.scfg.n_slots,), jnp.int32)

    def _slot_of(self, uid):
        return next(i for i, r in enumerate(self.slots)
                    if r is not None and r.uid == uid)

    def prefill(self, uid, tokens):
        res = super().prefill(uid, tokens)
        self.dev_pos = self.dev_pos.at[self._slot_of(uid)].set(len(tokens))
        return res

    def decode(self):
        nxt = super().decode()
        self.dev_pos = self.dev_pos + 1
        return nxt

    def _release(self, slot):
        self.dev_pos = self.dev_pos.at[slot].set(0)
        super()._release(slot)

    def fork(self, uid, new_uid):
        src = self._slot_of(uid)
        dst = super().fork(uid, new_uid)
        self.dev_pos = self.dev_pos.at[dst].set(self.dev_pos[src])
        return dst


def _assert_as_on_device(ex):
    assert ex.pos.dtype == np.int32
    assert ex.pos.shape == (ex.scfg.n_slots,)
    np.testing.assert_array_equal(ex.pos, np.asarray(ex.dev_pos))
    _assert_pending_at_pos(ex)


def test_host_positions_follow_admission_release_and_fork():
    params, cfg = _model()
    ex = _DevicePositions(params, cfg, ServerConfig(
        paged=True, n_slots=3, max_new_tokens=5, **_KW))
    waiting = [_Req(uid, p) for uid, p in enumerate(_PROMPTS)]
    outs, released = {}, 0
    for step in range(40):
        admit = [waiting.pop(0)] if waiting and None in ex.slots else []
        done = ex.execute(admit).completions
        outs.update(done)
        released += len(done)
        _assert_as_on_device(ex)
        if step == 1:
            ex.fork(0, 10)
            _assert_as_on_device(ex)
        if not waiting and not ex.has_resident():
            break
    assert sorted(outs) == [0, 1, 2, 3, 10] and released == 5
    assert outs[10] == outs[0]               # a greedy child follows suit


def test_paged_cow_after_prefix_sharing_matches_contiguous():
    """The second prompt reuses the first one's cached prefix blocks; a
    fork then shares its write block too, so the next decode step copies
    that block at the position the host holds.  Every answer equals
    contiguous decode of its prompt."""
    params, cfg = _model()
    kw = dict(max_new_tokens=6, **_KW)
    want = _serve(LLMExecutor(params, cfg, ServerConfig(
        paged=False, n_slots=2, **kw)), _PROMPTS[:2])
    ex = LLMExecutor(params, cfg, ServerConfig(paged=True, n_slots=3, **kw))
    cows = []
    ensure = ex.manager.ensure_writable

    def recorded(rid, pos):
        pair = ensure(rid, pos)
        if pair is not None:
            cows.append((rid, pos))
        return pair

    ex.manager.ensure_writable = recorded
    outs = dict(ex.execute([_Req(0, _PROMPTS[0])]).completions)
    outs.update(ex.execute([_Req(1, _PROMPTS[1])]).completions)
    assert ex.extra_stats()["prefix_hit_rate"] > 0
    ex.fork(1, 2)
    bs = ex.scfg.block_size
    while all(ex.manager.has(uid) for uid in (1, 2)):
        outs.update(ex.execute([]).completions)
        # the block each of them just wrote is its own
        written = [(t, ex.pos[i] - 1) for i, t in enumerate(ex.slots)
                   if t is not None and t.uid in (1, 2)]
        tables = [ex.manager.get(t.uid).table[p // bs] for t, p in written]
        assert len(written) < 2 or tables[0] != tables[1]
    _drain(ex, outs)
    assert cows and all(rid in (1, 2) for rid, _ in cows)
    assert [outs[0], outs[1], outs[2]] == [want[0], want[1], want[1]]


@pytest.mark.parametrize("name,paged", [
    ("llama3_2_1b", True), ("llama3_2_1b", False), ("mamba2_780m", True)])
def test_decode_step_compiles_once(name, paged):
    """The positions reach the decode program with the shape and dtype
    they always had, so admissions, releases and many steps leave one
    compiled variant."""
    params, cfg = _model(name)
    ex = LLMExecutor(params, cfg, ServerConfig(
        paged=paged, n_slots=2, max_new_tokens=4, **_KW))
    outs = _serve(ex, _PROMPTS)
    assert all(len(t) == 4 for t in outs)
    assert ex._decode_fn._cache_size() == 1


@pytest.mark.parametrize("name,paged", [
    ("llama3_2_1b", True), ("llama3_2_1b", False), ("mamba2_780m", True)])
def test_snapshot_restore_mid_decode_continues_bit_identically(name,
                                                                paged):
    """A snapshot taken mid-decode keeps its positions while the
    executor it came from runs on, and an executor restored from it
    emits what that one emitted."""
    params, cfg = _model(name)
    scfg = ServerConfig(paged=paged, n_slots=2, max_new_tokens=6, **_KW)
    a = LLMExecutor(params, cfg, scfg)
    a.execute([_Req(0, _PROMPTS[0]), _Req(1, _PROMPTS[2])])
    a.execute([])
    tree, meta = a.snapshot()
    meta = json.loads(json.dumps(meta))
    at_snapshot = a.pos.copy()
    want = _drain(a, {})
    b = LLMExecutor(params, cfg, scfg)
    b.restore(tree, meta)
    np.testing.assert_array_equal(b.pos, at_snapshot)
    assert b.pos.dtype == np.int32 and b.pos.flags.writeable
    assert _drain(b, {}) == want


@pytest.mark.parametrize("draft_seed", [0, 1])
def test_spec_greedy_beside_plain_slots_keeps_host_positions(draft_seed):
    """Speculating slots (the target as its own draft accepts every
    proposal, a random draft almost none) share steps with ``spec_k=0``
    slots that take the masked plain decode.  The positions stay those
    of the emitted tokens, and the answers those of plain decode."""
    params, cfg = _model("mamba2_780m")
    dparams, _ = _model("mamba2_780m", seed=draft_seed)
    kw = dict(n_slots=2, max_new_tokens=8, **_KW)
    want = _serve(LLMExecutor(params, cfg, ServerConfig(paged=True, **kw)),
                  _PROMPTS)
    ex = SpecExecutor(params, cfg, ServerConfig(paged=True, **kw),
                      dparams, cfg)
    eng = CutieEngine("fcfs")
    eng.register("llm", ex)
    handles = [eng.submit(p, model="llm", spec_k=0 if i % 2 else None)
               for i, p in enumerate(_PROMPTS)]
    while eng.busy():
        eng.step()
        _assert_pending_at_pos(ex)
    assert [h.request.result for h in handles] == want
    spec = ex.extra_stats()["spec"]
    assert spec["verify_steps"] > 0 and spec["plain_steps"] > 0
