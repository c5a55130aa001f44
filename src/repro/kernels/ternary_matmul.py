"""Packed-trit weight-stationary matmul — CUTIE's OCU array on the MXU.

The ASIC computes, for each output pixel, all N_O output channels in one
combinational shot with weights held in per-OCU private buffers.  The TPU
translation of that design point:

  * **weights live packed** (5 trits/byte, `repro.core.codec` layout) in HBM
    and are decoded to int8 {-1,0,+1} *inside* the kernel, right next to the
    MXU — HBM traffic for weights is 16x smaller than bf16 and 10x smaller
    than a 2-bit encoding would not reach (1.6 b/trit, paper §III-A);
  * **weight-stationarity**: the K-reduction is innermost in the grid, so a
    (bk, bn) weight tile is resident in VMEM while the m-stream passes; for
    CUTIE-CNN-sized layers (3*3*128*128 trits = 29 KiB packed) the *entire*
    weight tensor fits VMEM and the grid degenerates to the m-axis only —
    the literal "completely unrolled" regime;
  * **fused epilogue**: the folded two-threshold ternarization (paper
    §III-C) or the TWN scale is applied in-register before writeback, so
    intermediate integer accumulators never touch HBM — the paper's "no
    partial sums are ever stored" property.

Grid: (M/bm, N/bn, K/bk) with K innermost; accumulation in a VMEM scratch
(int32 for trit activations, f32 for bf16 activations).  MXU alignment: the
decoded K-block is 5*bk5 rows; bk5 defaults to 128 -> 640-row reduction
slabs, bm = bn = 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import epilogue as epi

TRITS_PER_BYTE = 5


def _decode_block(vb):
    """(bk5, bn) uint8 -> (5*bk5, bn) int8 trits (row-interleaved base-3)."""
    v = vb.astype(jnp.int32)
    digits = []
    for _ in range(TRITS_PER_BYTE):
        digits.append(v % 3)
        v = v // 3
    d = jnp.stack(digits, axis=1)                 # (bk5, 5, bn)
    return (d.reshape(d.shape[0] * TRITS_PER_BYTE, d.shape[2]) - 1)


def _mm_kernel(x_ref, w_ref, *rest, epilogue: str, acc_dtype, out_dtype):
    """rest = epilogue operand refs + (o_ref, acc_ref scratch)."""
    acc_ref = rest[-1]
    o_ref = rest[-2]
    ep_refs = rest[:-2]
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w_trits = _decode_block(w_ref[...])
    if acc_dtype == jnp.int32:
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], w_trits.astype(jnp.int8),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    else:
        acc_ref[...] += jnp.dot(
            x_ref[...], w_trits.astype(x_ref.dtype),
            preferred_element_type=jnp.float32)

    @pl.when(k_idx == pl.num_programs(2) - 1)
    def _epilogue():
        acc = acc_ref[...]
        if epilogue == "threshold":
            t_lo, t_hi, flip = (r[...] for r in ep_refs)   # (1, bn) each
            o_ref[...] = epi.two_threshold(acc, t_lo, t_hi, flip).astype(
                jnp.int8)
        elif epilogue == "scale":
            (scale,) = ep_refs
            o_ref[...] = (acc.astype(jnp.float32) * scale[...]).astype(out_dtype)
        else:
            o_ref[...] = acc.astype(out_dtype)


def ternary_matmul_pallas(x, w_packed, *, scale=None, t_lo=None, t_hi=None,
                          flip=None, bm: int = 128, bn: int = 128,
                          bk5: int = 128, interpret: bool = False):
    """x (M, K) [int8 trits | bf16/f32] @ decode(w_packed) (K, N).

    ``w_packed`` is (K/5, N) uint8.  Epilogues as in `ref.ternary_matmul`.
    Shapes must tile: M % bm == 0, N % bn == 0, (K/5) % bk5 == 0.
    """
    m, k = x.shape
    k5, n = w_packed.shape
    assert k == k5 * TRITS_PER_BYTE, (x.shape, w_packed.shape)
    bm, bn, bk5 = min(bm, m), min(bn, n), min(bk5, k5)
    assert m % bm == 0 and n % bn == 0 and k5 % bk5 == 0, (m, n, k5, bm, bn, bk5)
    bk = bk5 * TRITS_PER_BYTE

    is_int = jnp.issubdtype(x.dtype, jnp.integer)
    acc_dtype = jnp.int32 if is_int else jnp.float32

    if t_lo is not None:
        epilogue, out_dtype = "threshold", jnp.int8
        ep = [jnp.asarray(t_lo, jnp.float32).reshape(1, n),
              jnp.asarray(t_hi, jnp.float32).reshape(1, n),
              jnp.asarray(flip).astype(jnp.int8).reshape(1, n)]
    elif scale is not None:
        epilogue = "scale"
        out_dtype = x.dtype if not is_int else jnp.float32
        ep = [jnp.asarray(scale, jnp.float32).reshape(1, n)]
    else:
        epilogue, out_dtype, ep = "none", acc_dtype, []

    ep_specs = [pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)) for _ in ep]

    kernel = functools.partial(
        _mm_kernel, epilogue=epilogue, acc_dtype=acc_dtype,
        out_dtype=out_dtype)

    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k5 // bk5),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk5, bn), lambda i, j, kk: (kk, j)),
            *ep_specs,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w_packed, *ep)


def _mm_dense_kernel(x_ref, w_ref, o_ref, acc_ref):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k_idx == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_ref[...]


def ternary_matmul_dense_pallas(x, w, *, bm: int = 128, bn: int = 128,
                                bk: int = 512, interpret: bool = False):
    """Unpacked trit matmul (int8 x int8 -> int32), MXU int8 path."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    return pl.pallas_call(
        _mm_dense_kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x.astype(jnp.int8), w.astype(jnp.int8))
