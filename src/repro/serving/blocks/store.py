"""Physical paged state: block-granular KV pages and state-slot pages.

Two stores share the :class:`~repro.serving.blocks.pool.BlockPool`'s id
space:

* :class:`KVPagedStore` — attention families.  KV rows live in
  ``(L, num_blocks, block_size, Hk, Dh)`` pages, K and V (or, for MLA,
  one ``(L, num_blocks, block_size, latent_dim)`` page of latent rows);
  a per-sequence block table maps logical positions to physical blocks,
  and the decode step *gathers* through the table instead of indexing a
  contiguous cache.
  With ``codec="trit"`` the pages hold **ternarized** rows packed 5
  trits/byte (`repro.core.codec` layout) plus one scale per (position,
  head) — 1.6 bits per element, so a fixed HBM budget holds ~5x the
  context an int8 cache would (paper §III-A).
* :class:`StatePagedStore` — SSM/mamba2 families.  A "block" holds one
  recurrent state snapshot (a whole pytree, flattened per leaf); the
  same pool allocates them, and with ``codec="trit"`` ternary state
  leaves pack 5/byte *losslessly* (trit values round-trip exactly).

All traced methods are pure ``(pages, ...) -> pages`` functions so the
executor can jit gather -> decode -> scatter as one program; the stores
also keep a live ``self.pages`` for the eager call sites.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec
from repro.serving.blocks.pool import NULL_BLOCK

Array = jax.Array

_POW3 = np.asarray(codec.POW3)


def pack_last_axis(t: Array) -> Array:
    """Trits {-1,0,1} ``(..., n)`` -> uint8 ``(..., ceil(n/5))``
    (little-endian in the trit index, `repro.core.codec` layout)."""
    n = t.shape[-1]
    pad = (-n) % codec.TRITS_PER_BYTE
    d = jnp.pad(t.astype(jnp.int32),
                [(0, 0)] * (t.ndim - 1) + [(0, pad)]) + 1
    g = d.reshape(*d.shape[:-1], -1, codec.TRITS_PER_BYTE)
    return jnp.sum(g * jnp.asarray(_POW3), axis=-1).astype(jnp.uint8)


def unpack_last_axis(b: Array, n: int) -> Array:
    """Inverse of :func:`pack_last_axis`: ``(..., ceil(n/5))`` bytes ->
    ``(..., n)`` int8 trits."""
    v = b.astype(jnp.int32)
    digits = []
    for _ in range(codec.TRITS_PER_BYTE):
        digits.append(v % 3)
        v = v // 3
    t = jnp.stack(digits, axis=-1).reshape(*b.shape[:-1], -1) - 1
    return t[..., :n].astype(jnp.int8)


def ternarize_rows(v: Array) -> tuple[Array, Array]:
    """Per-row symmetric ternarization over the last axis.

    Returns ``(trits int8, scale f32)`` with ``scale = max|v|`` and a
    0.5-scale dead zone — the TWN-style quantizer the rest of the repo
    uses for activations, applied to KV rows at cache-write time.
    """
    x = v.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=-1)
    safe = jnp.maximum(scale, 1e-12)[..., None]
    t = jnp.where(jnp.abs(x) > 0.5 * safe, jnp.sign(x), 0.0)
    return t.astype(jnp.int8), scale


class KVPagedStore:
    """Paged KV pages + pure gather/scatter over block tables.

    ``latent_dim > 0`` stores MLA pages instead of K and V: one row of
    ``latent_dim`` values per position and layer, under the name
    ``"lat"`` (raw codec only)."""

    def __init__(self, n_layers: int, num_blocks: int, block_size: int,
                 n_kv: int, d_head: int, dtype="bfloat16",
                 codec_name: str = "raw", latent_dim: int = 0):
        if codec_name not in ("raw", "trit"):
            raise ValueError(f"codec must be 'raw' or 'trit', "
                             f"got {codec_name!r}")
        if latent_dim and codec_name != "raw":
            raise ValueError(
                f"kv_codec={codec_name!r} is not supported for MLA latent "
                f"pages; use 'raw'")
        self.n_layers, self.num_blocks = n_layers, num_blocks
        self.block_size, self.n_kv, self.d_head = block_size, n_kv, d_head
        self.latent_dim = latent_dim
        self.dtype = jnp.dtype(dtype)
        self.codec = codec_name
        self.names = ("lat",) if latent_dim else ("k", "v")
        if latent_dim:
            lat = (n_layers, num_blocks, block_size, latent_dim)
            self.pages = {"lat": jnp.zeros(lat, self.dtype)}
        elif codec_name == "raw":
            kv = (n_layers, num_blocks, block_size, n_kv, d_head)
            self.pages = {"k": jnp.zeros(kv, self.dtype),
                          "v": jnp.zeros(kv, self.dtype)}
        else:
            pw = codec.packed_size(d_head)
            pk = (n_layers, num_blocks, block_size, n_kv, pw)
            sc = (n_layers, num_blocks, block_size, n_kv)
            self.pages = {"k": jnp.zeros(pk, jnp.uint8),
                          "v": jnp.zeros(pk, jnp.uint8),
                          "k_scale": jnp.zeros(sc, jnp.float32),
                          "v_scale": jnp.zeros(sc, jnp.float32)}

    # -- sizing -------------------------------------------------------------

    def bytes_per_block(self) -> int:
        """Physical bytes of one block across all layers (both of K/V,
        or the latent rows)."""
        if self.latent_dim:
            return (self.n_layers * self.block_size * self.latent_dim
                    * self.dtype.itemsize)
        per = self.block_size * self.n_kv
        if self.codec == "raw":
            elem = per * self.d_head * self.dtype.itemsize
        else:
            elem = per * (codec.packed_size(self.d_head) + 4)  # + f32 scale
        return 2 * self.n_layers * elem

    # -- codec --------------------------------------------------------------

    def _encode(self, rows: Array):
        """Compute-dtype rows -> stored representation dict pieces."""
        if self.codec == "raw":
            return {"": rows.astype(self.dtype)}
        t, scale = ternarize_rows(rows)
        return {"": pack_last_axis(t), "_scale": scale}

    def _decode(self, packed: Array, scale: Optional[Array]):
        if self.codec == "raw":
            return packed
        t = unpack_last_axis(packed, self.d_head)
        return (t.astype(jnp.float32)
                * scale[..., None]).astype(jnp.bfloat16)

    # -- pure (traceable) ops ----------------------------------------------

    def gather(self, pages: dict, tables: Array) -> dict:
        """``tables (B, MB) int32`` -> contiguous KV view
        ``{"k"/"v": (L, B, MB*block_size, Hk, Dh)}`` (MLA: ``{"lat":
        (L, B, MB*block_size, latent_dim)}``)."""
        out = {}
        for name in self.names:
            g = pages[name][:, tables]       # (L, B, MB, BS, Hk, [Dh|PW])
            sc = (pages[f"{name}_scale"][:, tables]
                  if self.codec == "trit" else None)
            l, b, mb, bs = g.shape[:4]
            g = self._decode(g, sc)
            out[name] = g.reshape(l, b, mb * bs, *g.shape[4:])
        return out

    def write_rows(self, pages: dict, tables: Array, pos: Array,
                   rows: dict) -> dict:
        """Scatter one decode step's new rows ``{"k"/"v": (L, B, Hk, Dh)}``
        (or ``{"lat": (L, B, latent_dim)}``) at per-sequence positions
        ``pos (B,)`` through the tables."""
        b = pos.shape[0]
        blocks = tables[jnp.arange(b), pos // self.block_size]
        off = pos % self.block_size
        new = dict(pages)
        for name in self.names:
            enc = self._encode(rows[name])
            new[name] = pages[name].at[:, blocks, off].set(enc[""])
            if self.codec == "trit":
                new[f"{name}_scale"] = pages[f"{name}_scale"].at[
                    :, blocks, off].set(enc["_scale"])
        return new

    def write_span(self, pages: dict, table: Array, start: Array,
                   n_real: Array, kv: dict) -> dict:
        """Scatter a prefill's suffix rows ``{"k"/"v": (L, S, Hk, Dh)}``
        at positions ``start .. start+n_real-1`` of one sequence.

        ``S`` is static (the jit bucket); rows past ``n_real`` (bucket
        padding) are routed to the null block, which never holds live
        data.
        """
        s = kv[self.names[0]].shape[1]
        j = jnp.arange(s)
        posn = start + j
        valid = j < n_real
        idx = jnp.clip(posn // self.block_size, 0, table.shape[0] - 1)
        blocks = jnp.where(valid, table[idx], NULL_BLOCK)
        off = jnp.where(valid, posn % self.block_size, 0)
        new = dict(pages)
        for name in self.names:
            enc = self._encode(kv[name])
            new[name] = pages[name].at[:, blocks, off].set(enc[""])
            if self.codec == "trit":
                new[f"{name}_scale"] = pages[f"{name}_scale"].at[
                    :, blocks, off].set(enc["_scale"])
        return new

    def copy_blocks(self, pages: dict, src: Array, dst: Array) -> dict:
        """COW payload copies: ``pages[:, dst] = pages[:, src]``."""
        return {name: arr.at[:, dst].set(arr[:, src])
                for name, arr in pages.items()}

    # -- eager wrappers over self.pages -------------------------------------

    def apply_copies(self, pairs: list[tuple[int, int]]) -> None:
        if not pairs:
            return
        src = jnp.asarray([p[0] for p in pairs], jnp.int32)
        dst = jnp.asarray([p[1] for p in pairs], jnp.int32)
        self.pages = self.copy_blocks(self.pages, src, dst)


class StatePagedStore:
    """State-slot pages: one block = one recurrent-state snapshot.

    ``template`` is a pytree of arrays (or ShapeDtypeStructs) describing
    one sequence's state.  With ``codec="trit"`` every leaf must hold
    trits in {-1, 0, +1}; leaves are flattened and packed 5/byte via
    `repro.core.codec` — an *exact* roundtrip, which is what makes the
    5x capacity claim free for ternary state.
    """

    def __init__(self, num_blocks: int, template, codec_name: str = "raw"):
        if codec_name not in ("raw", "trit"):
            raise ValueError(f"codec must be 'raw' or 'trit', "
                             f"got {codec_name!r}")
        self.num_blocks = num_blocks
        self.codec = codec_name
        self.treedef = jax.tree.structure(template)
        leaves = jax.tree.leaves(template)
        self.shapes = [tuple(leaf.shape) for leaf in leaves]
        self.dtypes = [jnp.dtype(leaf.dtype) for leaf in leaves]
        if codec_name == "raw":
            self.pages = [jnp.zeros((num_blocks,) + s, d)
                          for s, d in zip(self.shapes, self.dtypes)]
        else:
            self.pages = [
                jnp.zeros((num_blocks,
                           codec.packed_size(math.prod(s) or 1)),
                          jnp.uint8)
                for s in self.shapes]

    def bytes_per_block(self) -> int:
        return sum(int(p[0].size) * p[0].dtype.itemsize
                   for p in (pg for pg in self.pages))

    # -- pure ops -----------------------------------------------------------

    def read(self, pages: list, bids: Array):
        """``bids (B,)`` -> state pytree with a leading batch axis."""
        leaves = []
        for pg, shape, dt in zip(pages, self.shapes, self.dtypes):
            a = pg[bids]
            if self.codec == "trit":
                n = math.prod(shape) or 1
                a = unpack_last_axis(a, n).reshape(
                    (a.shape[0],) + shape).astype(dt)
            leaves.append(a)
        return jax.tree.unflatten(self.treedef, leaves)

    def write(self, pages: list, bid, state) -> list:
        """Store one sequence's state pytree into block ``bid``."""
        out = []
        for pg, leaf, _shape in zip(pages, jax.tree.leaves(state),
                                    self.shapes):
            if self.codec == "trit":
                leaf = pack_last_axis(leaf.reshape(-1))
            out.append(pg.at[bid].set(leaf))
        return out

    def write_batch(self, pages: list, bids: Array, states) -> list:
        """Scatter a batch of states (leaves with a leading batch axis
        matching ``bids (B,)``) into their blocks in one op."""
        out = []
        for pg, leaf, _shape in zip(pages, jax.tree.leaves(states),
                                    self.shapes):
            if self.codec == "trit":
                leaf = pack_last_axis(leaf.reshape(leaf.shape[0], -1))
            out.append(pg.at[bids].set(leaf.astype(pg.dtype)))
        return out

    def copy_blocks(self, pages: list, src: Array, dst: Array) -> list:
        return [pg.at[dst].set(pg[src]) for pg in pages]

    # -- eager wrappers ------------------------------------------------------

    def write_(self, bid: int, state) -> None:
        self.pages = self.write(self.pages, jnp.asarray(bid), state)

    def read_(self, bids):
        return self.read(self.pages, jnp.asarray(bids, jnp.int32))

    def apply_copies(self, pairs: list[tuple[int, int]]) -> None:
        if not pairs:
            return
        src = jnp.asarray([p[0] for p in pairs], jnp.int32)
        dst = jnp.asarray([p[1] for p in pairs], jnp.int32)
        self.pages = self.copy_blocks(self.pages, src, dst)
