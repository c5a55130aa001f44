"""5-trits-in-8-bits storage codec (paper §III-A, after Muller et al. [67]).

A ternary symbol carries log2(3) ~ 1.585 bits.  The naive 2-bit encoding
wastes one codeword in four; CUTIE instead packs 5 trits into one byte
(3^5 = 243 <= 256), i.e. 1.6 bits per trit.  CUTIE uses this on the
feature-map and weight memories; this framework additionally uses it

* for checkpoint compression of ternary tensors (`repro.checkpoint`),
* as the on-wire format for ternary collectives / gradient compression
  (`repro.optim.compression`) — a 10x reduction vs bf16 on the ICI path.

This file is the pure-jnp reference codec; `repro.kernels.trit_codec` is the
Pallas TPU kernel with the same semantics.

Encoding: digits d_i = t_i + 1 in {0,1,2};  byte = sum_i d_i * 3^i  (i<5).
Decoding: repeated div/mod 3.  Values are little-endian in the trit index.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

POW3 = np.array([1, 3, 9, 27, 81], dtype=np.int32)  # 3^i, i in [0,5)
TRITS_PER_BYTE = 5


def packed_size(n: int) -> int:
    """Number of bytes needed to pack n trits."""
    return (n + TRITS_PER_BYTE - 1) // TRITS_PER_BYTE


def pack_trits(t: Array) -> Array:
    """Pack a flat int array of trits {-1,0,1} into uint8, 5 per byte.

    The input is padded with zeros up to a multiple of 5; callers must
    remember the original length to unpack.
    """
    t = t.reshape(-1).astype(jnp.int32)
    n = t.shape[0]
    pad = (-n) % TRITS_PER_BYTE
    t = jnp.pad(t, (0, pad))
    groups = (t + 1).reshape(-1, TRITS_PER_BYTE)
    vals = jnp.sum(groups * jnp.asarray(POW3)[None, :], axis=1)
    return vals.astype(jnp.uint8)


def unpack_trits(b: Array, n: int) -> Array:
    """Inverse of `pack_trits`: uint8 bytes -> n trits in {-1,0,1} (int8)."""
    v = b.astype(jnp.int32)
    digits = []
    for _ in range(TRITS_PER_BYTE):
        digits.append(v % 3)
        v = v // 3
    trits = jnp.stack(digits, axis=-1).reshape(-1) - 1
    return trits[:n].astype(jnp.int8)


def pack_rows(t: Array) -> Array:
    """(R, n) trits -> (R, ceil(n/5)) bytes, one codec stream per row.

    Each row is zero-padded to a multiple of 5 on its own, so every row
    decodes independently — the layout the Pallas kernels decode next to
    their compute (`repro.kernels.trit_codec.unpack_rows`).
    """
    r, n = t.shape
    t = jnp.pad(t, ((0, 0), (0, (-n) % TRITS_PER_BYTE)))
    return pack_trits(t.reshape(-1)).reshape(r, -1)


def pack_filter_rows(w: Array) -> Array:
    """(K, K, Cin, Cout) trits -> (Cout, ceil(K*K*Cin/5)) packed rows.

    Row r holds output channel r's K*K*Cin weights flattened (kh, kw, ci)-
    major and zero-padded per row to a multiple of 5, so every row decodes
    independently — the layout the packed conv kernel
    (`repro.kernels.ternary_conv2d.ternary_conv2d_packed_pallas`) tiles
    over output channels and decodes next to its taps.
    """
    k, _, cin, cout = w.shape
    return pack_rows(jnp.transpose(w, (3, 0, 1, 2)).reshape(cout, -1))


def pack_tensor(x: Array) -> tuple[Array, tuple[int, ...]]:
    """Pack an arbitrary-shape ternary tensor; returns (bytes, shape)."""
    return pack_trits(x), tuple(x.shape)


def unpack_tensor(b: Array, shape: tuple[int, ...],
                  dtype=jnp.int8) -> Array:
    n = int(np.prod(shape)) if shape else 1
    return unpack_trits(b, n).reshape(shape).astype(dtype)


def compression_ratio(dtype_bits: int = 16) -> float:
    """Bits saved vs a dense dtype (default bf16): 16 / 1.6 = 10x."""
    return dtype_bits / (8.0 / TRITS_PER_BYTE)
