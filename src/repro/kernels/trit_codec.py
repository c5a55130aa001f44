"""Pallas kernels for the 5-trits-per-byte codec (paper §III-A).

Layout contract (shared with `repro.kernels.ref` and `repro.core.codec`):
trit index k maps to (byte g = k // 5, digit i = k % 5), little-endian in i.
Both kernels work on (R, 5*G) <-> (R, G) 2-D views; callers reshape.

In-kernel, the codec is two small matmuls against 0/1 (or 3^i) selection
matrices built from iotas: trit k sits in byte k // 5, which is a lane
gather the TPU compiler cannot express as a reshape of the lane axis, but
the MXU does exactly (bf16 operands hold 0..242 exactly, and each output
is a sum of at most five integer products).  The base-3 digit of each
lane is then peeled off in float32 with exact floor divisions.
:func:`unpack_rows` / :func:`pack_rows` are the one in-kernel codec,
shared by this module's kernels, the packed-weight conv (weight decode
per tap) and the fused trunk (packed activations at trunk boundaries).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TRITS_PER_BYTE = 5


def _floordiv(x, d):
    """floor(x / d) for float32 x holding a non-negative integer < 2^20
    and a positive integer d (scalar or array): x + 1/2 keeps the
    quotient at least 1/(2d) away from an integer, far more than the
    rounding error of the reciprocal multiply."""
    return jnp.floor((x + 0.5) * (1.0 / d))


def _pow3(digit):
    """3 ** digit for an int32 array of digit positions in 0..4."""
    p = jnp.ones_like(digit)
    for i in range(1, TRITS_PER_BYTE):
        p = jnp.where(digit >= i, 3 * p, p)
    return p


def unpack_rows(b, start: int, n: int):
    """(R, G) packed bytes -> (R, n) int32 trits in {-1, 0, 1}.

    Row r's trits at flat positions ``start .. start + n - 1`` of that
    row's codec stream (byte k // 5, digit k % 5).
    """
    g = b.shape[-1]
    pos = jax.lax.broadcasted_iota(jnp.int32, (g, n), 1) + start
    byte = jax.lax.broadcasted_iota(jnp.int32, (g, n), 0)
    pick = (_floordiv(pos.astype(jnp.float32), TRITS_PER_BYTE)
            == byte.astype(jnp.float32)).astype(jnp.bfloat16)
    v = jax.lax.dot_general(
        b.astype(jnp.int32).astype(jnp.bfloat16), pick,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # (R, n) byte of each trit
    lane = (jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
            + start).astype(jnp.float32)
    digit = lane - TRITS_PER_BYTE * _floordiv(lane, TRITS_PER_BYTE)
    q = _floordiv(v, _pow3(digit.astype(jnp.int32)).astype(jnp.float32))
    return (q - 3 * _floordiv(q, 3)).astype(jnp.int32) - 1


def pack_rows(t):
    """(R, n) trits -> (R, ceil(n / 5)) packed uint8 bytes.

    Each row is one codec stream, zero-padded to a multiple of 5 exactly
    like `repro.core.codec.pack_trits` (a padded trit 0 is digit 1).
    """
    n = t.shape[-1]
    g = -(-n // TRITS_PER_BYTE)
    pos = jax.lax.broadcasted_iota(jnp.int32, (n, g), 0).astype(jnp.float32)
    byte = jax.lax.broadcasted_iota(jnp.int32, (n, g), 1).astype(jnp.float32)
    q = _floordiv(pos, TRITS_PER_BYTE)
    digit = (pos - TRITS_PER_BYTE * q).astype(jnp.int32)
    weight = jnp.where(q == byte, _pow3(digit), 0).astype(jnp.bfloat16)
    v = jax.lax.dot_general(
        (t.astype(jnp.int32) + 1).astype(jnp.bfloat16), weight,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    tail = n % TRITS_PER_BYTE
    if tail:              # padded digits of the last byte are all 1
        last = jax.lax.broadcasted_iota(jnp.int32, (1, g), 1) == g - 1
        v = v + jnp.where(last, (3 ** TRITS_PER_BYTE - 3 ** tail) // 2,
                          0).astype(jnp.float32)
    return v.astype(jnp.int32).astype(jnp.uint8)


def _pack_kernel(t_ref, o_ref):
    o_ref[...] = pack_rows(t_ref[...])


def _unpack_kernel(b_ref, o_ref):
    o_ref[...] = unpack_rows(b_ref[...], 0, o_ref.shape[-1]).astype(
        jnp.int8)


def pack_trits_pallas(t, *, br: int = 256, bg: int = 128,
                      interpret: bool = False):
    """(R, 5*G) int8 trits -> (R, G) uint8."""
    r, k = t.shape
    assert k % TRITS_PER_BYTE == 0
    g = k // TRITS_PER_BYTE
    br, bg = min(br, r), min(bg, g)
    assert r % br == 0 and g % bg == 0
    return pl.pallas_call(
        _pack_kernel,
        grid=(r // br, g // bg),
        in_specs=[pl.BlockSpec((br, bg * TRITS_PER_BYTE),
                               lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, bg), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, g), jnp.uint8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(t)


def unpack_trits_pallas(b, *, br: int = 256, bg: int = 128,
                        interpret: bool = False):
    """(R, G) uint8 -> (R, 5*G) int8 trits."""
    r, g = b.shape
    br, bg = min(br, r), min(bg, g)
    assert r % br == 0 and g % bg == 0
    return pl.pallas_call(
        _unpack_kernel,
        grid=(r // br, g // bg),
        in_specs=[pl.BlockSpec((br, bg), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, bg * TRITS_PER_BYTE),
                               lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, g * TRITS_PER_BYTE), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(b)


def _thermo_kernel(x_ref, o_ref, *, m: int, ternary: bool):
    x = x_ref[...].astype(jnp.int32)                # (br, 1)
    idx = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], m), 1)
    if ternary:
        s = jnp.sign(x - m)
        f = jnp.where(idx < jnp.abs(x - m), 1, -1)
        o_ref[...] = (s * ((f + 1) // 2)).astype(jnp.int8)
    else:
        o_ref[...] = jnp.where(idx < x, 1, -1).astype(jnp.int8)


def thermometer_pallas(x, m: int, *, ternary: bool = True, br: int = 512,
                       interpret: bool = False):
    """int32 levels (R,) -> (R, m) thermometer trits/bits (paper §III-D)."""
    r = x.shape[0]
    br = min(br, r)
    assert r % br == 0
    return pl.pallas_call(
        functools.partial(_thermo_kernel, m=m, ternary=ternary),
        grid=(r // br,),
        in_specs=[pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, m), jnp.int8),
        interpret=interpret,
    )(x.reshape(r, 1).astype(jnp.int32))
