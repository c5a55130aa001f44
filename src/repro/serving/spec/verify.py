"""`VerifyWorker` — score k proposals in one batched target forward.

The target model already has a prefix-aware prefill
(:func:`repro.models.decoding.prefill_with_prefix`) that runs a token
span against gathered cached KV; verification is that same path pointed
at the *decode frontier* instead of a prompt: gather the block-aligned
committed prefix, run ``replay + [pending] + proposals`` as one bucketed
suffix, and read the target's distribution for every proposal position
plus the bonus position out of the returned logits rows.

Writing the suffix KV back is where speculation could corrupt a
sequence: the span overlaps committed rows, and if the verify fails
midway (OOM, eviction pressure during COW) the sequence must stay
exactly as it was.  The worker therefore never writes into the live
sequence's blocks — it **forks a shadow** (`manager.fork` — pure
refcount sharing), COWs the span into the shadow, writes there, and
only on success frees the original and adopts the shadow under the
live id.  Rollback on any exception is `free(shadow)`: a refcount
release, never a payload restore.

SSM targets have no positional rows to page; instead the suffix is
scanned with :func:`~repro.models.decoding.ssm_prefill_states`, which
keeps the state after *every* step, and commit picks the state matching
the accepted run.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import decoding as DEC

_VERIFY_FLOOR = 8      # pow2 bucket floor for the SSM verify scan


def _bucket(n: int, floor: int) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


class VerifyWorker:
    """Batched proposal scoring against a `LLMExecutor`'s paged state."""

    def __init__(self, executor):
        self.ex = executor
        self._fns: dict = {}        # ssm verify-scan jit variants

    # -- attention targets ---------------------------------------------------

    def verify_kv(self, slot: int, uid: int, committed: np.ndarray,
                  cur: int, proposals: np.ndarray, pos: int) -> np.ndarray:
        """One target forward over ``[pending] + proposals``.

        ``committed`` are the tokens whose KV rows are already paged in
        (``len(committed) == pos``); ``cur`` is the pending token at
        position ``pos``.  Returns ``(k+1, V)`` target logits rows for
        positions ``pos+1 .. pos+k+1``.  The executor's paged KV ends up
        holding rows through ``pos+k`` under ``uid`` (garbage past the
        accept point is rewritten by the next verify and never attended:
        decode masks by position).
        """
        ex = self.ex
        bs = ex.scfg.block_size
        k = len(proposals)
        if len(committed) != pos:
            raise AssertionError(
                f"verify out of sync: {len(committed)} committed tokens "
                f"but slot position {pos}")
        c = (pos // bs) * bs
        suffix = np.concatenate([
            np.asarray(committed[c:], np.int32),
            np.asarray([cur], np.int32),
            np.asarray(proposals, np.int32)])
        n_real = len(suffix)                # (pos - c) + 1 + k
        shadow = -uid
        mgr = ex.manager
        mgr.fork(uid, shadow)
        try:
            pairs = mgr.ensure_span_writable(shadow, c, pos + k + 1)
            ex.kv_store.apply_copies(pairs)
            table_row = jnp.asarray(
                mgr.table_array(shadow, ex.blocks_per_seq))
            prefix_kv = ex.kv_store.gather(
                ex.kv_store.pages, table_row[None, :c // bs]) if c else \
                DEC.empty_prefix(ex.cfg)
            sb = _bucket(n_real, bs)
            padded = np.zeros((1, sb), np.int32)
            padded[0, :n_real] = suffix
            fn = ex._suffix_fn(c, sb)       # shares prefill's jit cache
            logits, kv, _ = fn(ex.params, jnp.asarray(padded), prefix_kv)
            ex.kv_store.pages = ex.kv_store.write_span(
                ex.kv_store.pages, table_row, jnp.int32(c),
                jnp.int32(n_real), {n: a[:, 0] for n, a in kv.items()})
        except Exception:
            mgr.free(shadow)
            raise
        mgr.free(uid)
        mgr.adopt(shadow, uid)
        r = pos - c                         # row index of the pending token
        return np.asarray(logits[0, r:r + k + 1, :ex.cfg.vocab],
                          np.float32)

    # -- SSM targets ---------------------------------------------------------

    def verify_ssm(self, slot: int, uid: int, cur: int,
                   proposals: np.ndarray, pos: int
                   ) -> tuple[np.ndarray, object]:
        """Scan ``[pending] + proposals`` keeping every per-step state.

        Returns ``((k+1, V) target rows, states)``; pass ``states`` and
        the accept count to :meth:`commit_ssm` — the slot state is not
        touched until then, so rejection needs no rollback at all.
        """
        ex = self.ex
        k = len(proposals)
        n_real = 1 + k
        sb = _bucket(n_real, _VERIFY_FLOOR)
        toks = np.zeros((1, sb), np.int32)
        toks[0, 0] = cur
        toks[0, 1:n_real] = np.asarray(proposals, np.int32)
        state = ex.state_store.read_([int(ex._slot_bids[slot])])
        logits, states = self._ssm_fn(sb)(
            ex.params, jnp.asarray(toks), state, jnp.int32(pos))
        return (np.asarray(logits[0, :n_real, :ex.cfg.vocab], np.float32),
                states)

    def commit_ssm(self, slot: int, states, j: int) -> None:
        """Adopt the state after the pending token + ``j`` accepted
        proposals (scan step index ``j``)."""
        ex = self.ex
        state = jax.tree.map(lambda a: a[j][:, 0], states["ssm"])
        ex.state_store.write_(int(ex._slot_bids[slot]), state)

    def _ssm_fn(self, sb: int):
        key = ("ssm", sb)
        if key not in self._fns:
            cfg = self.ex.cfg

            def fn(p, toks, state, pos0):
                caches = {"ssm": jax.tree.map(lambda a: a[0][:, None],
                                              state)}
                return DEC.ssm_prefill_states(p, toks, caches, cfg, pos0)

            self._fns[key] = jax.jit(fn)
        return self._fns[key]

    @property
    def n_jit_variants(self) -> int:
        return len(self._fns)
