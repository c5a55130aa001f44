"""Shared in-kernel OCU writeback: pool -> two-threshold -> const fixup.

The single implementation of CUTIE's layer epilogue used by every Pallas
execution path — the per-layer conv kernel (`ternary_conv2d`), its
packed-weight variant and the fused-trunk megakernel (`fused_trunk`) all
call :func:`layer_epilogue` on the int32 accumulator while it is still in
registers/VMEM, so pre-threshold integers never spill to HBM:

* merged pooling on the pre-threshold accumulator (paper Fig. 5: avg =
  window sum against pre-scaled thresholds, max = max of sign(g)*z),
* the folded two-threshold compare (paper §III-C),
* the degenerate-channel fixup (g == 0 channels take their stored
  per-channel constant).

Bit-identical to the jnp reference pair ``engine._pool_pre_threshold`` +
``folding.apply_thresholds``, but written so the TPU compiler (Mosaic)
accepts it: pooling is a reshape-and-reduce over the window (Mosaic only
lowers unit-stride slices), and the compares fold the per-channel flip
into a +-1 sign instead of selecting between boolean arrays (Mosaic
cannot lower a select of i1 vectors).
Per-channel vectors broadcast against ``(..., C)`` accumulators, so both
the per-layer kernels (one image per grid step) and the trunk kernel
(whole batch) share it unchanged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def channel_sign(flip, dtype):
    """sign(g) per channel: -1 where the compare direction flips, else 1."""
    return 1 - 2 * (flip != 0).astype(dtype)


def fit_axis(z, axis: int, n: int):
    """Crop, or zero-pad at the end, ``z`` along ``axis`` to length ``n``."""
    m = z.shape[axis]
    if m >= n:
        return jax.lax.slice_in_dim(z, 0, n, axis=axis)
    pad = list(z.shape)
    pad[axis] = n - m
    return jnp.concatenate([z, jnp.zeros(pad, z.dtype)], axis=axis)


def _window_reduce(z, win, out_hw, reduce):
    """Reduce each non-overlapping ``win`` = (wh, ww) window of z
    (N, H, W, C) to one value: (N, OH, OW, C) with (OH, OW) = out_hw.

    Rows and columns past ``win * out_hw`` are dropped (cropped windows)
    and missing ones zero-filled, so only whole windows reach
    ``reduce(a, axis)``.  Splitting a dimension by reshape is the form
    Mosaic lowers; a strided slice is not.
    """
    n, _, _, c = z.shape
    (oh, ow), (wh, ww) = out_hw, win
    z = fit_axis(fit_axis(z, 1, oh * wh), 2, ow * ww)
    z = reduce(z.reshape(n * oh, wh, ow * ww, c), 1)
    z = reduce(z.reshape(n * oh, ow, ww, c), 2)
    return z.reshape(n, oh, ow, c)


def _first(a, axis: int):
    return jax.lax.index_in_dim(a, 0, axis, keepdims=False)


def subsample(z, stride, out_hw):
    """Stride a stride-1 conv result: z (N, H, W, C) -> every
    ``stride``-th row and column, (N, OH, OW, C) with (OH, OW) = out_hw.

    A strided conv equals the stride-1 conv sampled at multiples of the
    stride, so kernels compute the stride-1 accumulator and keep the
    first element of each stride x stride window."""
    if tuple(stride) == (1, 1):
        return z
    return _window_reduce(z, stride, out_hw, _first)


def pool_int(z, flip, pool):
    """Merged pooling on integer pre-activations z (N, OH, OW, C).

    Windows that do not fit are cropped (exactly like the reference
    ``engine._pool_pre_threshold``).  ``flip`` is the per-channel compare
    direction (int8/bool, (C,)); max pooling pools sign(g)*z so it
    commutes with the flipped compare.
    """
    kind, win = pool
    n, oh, ow, c = z.shape
    ph, pw = oh // win, ow // win
    if ph == 0 or pw == 0:
        raise ValueError(
            f"pool window {win} exceeds the {oh}x{ow} conv output; "
            "run CutieProgram.validate(in_shape=...) to catch this at "
            "compile time")
    if kind == "avg":                         # thresholds pre-scaled
        return _window_reduce(z, (win, win), (ph, pw), jnp.sum)
    sgn = channel_sign(flip, z.dtype)
    return _window_reduce(z * sgn, (win, win), (ph, pw), jnp.max) * sgn


def two_threshold(z, t_lo, t_hi, flip):
    """Folded two-threshold ternarize of an integer accumulator -> int32.

    Comparing sign(g)*z against sign(g)*t is the flipped compare: the
    negation is exact in float32."""
    sgn = channel_sign(flip, jnp.float32)
    sz = sgn * z.astype(jnp.float32)
    return ((sz > sgn * t_hi).astype(jnp.int32)
            - (sz < sgn * t_lo).astype(jnp.int32))


def const_fixup(y, const, is_const):
    """Degenerate (g == 0) channels take their stored constant trit."""
    keep = (is_const != 0).astype(y.dtype)
    return y + keep * (const.astype(y.dtype) - y)


def zero_count(x) -> jnp.ndarray:
    """Scalar int32 count of zero trits in x (kernel-safe, exact; the TPU
    compares 32-bit lanes only, so int8 trits widen first)."""
    return jnp.sum((x.astype(jnp.int32) == 0).astype(jnp.int32),
                   dtype=jnp.int32)


def _coverage(idx, n_anchor: int, k: int):
    """How many of the ``n_anchor`` stride-1 length-``k`` boxes cover
    each index in ``idx`` — the trapezoid 1,2,..,k,..,2,1 clipped by the
    anchor count.  ``idx`` is a traced iota (a numpy constant would be
    captured by the Pallas kernel, which rejects non-ref consts)."""
    return jnp.minimum(jnp.minimum(idx, n_anchor - 1),
                       jnp.minimum(k - 1, n_anchor + k - 2 - idx)) + 1


def window_toggle_count(xp, k: int, oh: int, ow: int, cin: int
                        ) -> jnp.ndarray:
    """Int32 toggle count over consecutive raster windows, in-kernel.

    ``xp`` is one image's (PH, PW, C) padded input already resident in
    VMEM; the (oh, ow) stride-1 window grid walks it in row-major raster
    order — the unrolled OCU schedule.  The count is the number of
    (tap, channel) positions that differ between consecutive windows,
    summed over the whole raster: the integer numerator of
    `repro.energy.switching.window_toggle`'s ``mult_toggle`` (and, per
    window, of ``window_hamming``).  Only the first ``cin`` channels are
    counted, so zero-padded spare trunk channels never inflate it.  The
    toggle count is invariant to the (tap, channel) feature ordering —
    only the raster order of windows matters — so this matches the
    traced-side patch extraction exactly, integer for integer.

    Computed without materializing the (OH*OW, K*K*C) patch matrix: a
    horizontal window step (r,c)->(r,c+1) toggles exactly the k*k box of
    the pixel-difference map D[i,j] = #{ch: x[i,j+1,ch] != x[i,j,ch]}
    anchored at (r,c), so the sum over all oh*(ow-1) such steps is one
    weighted reduction of D against the static box-coverage counts; the
    oh-1 row-wrap steps (r,ow-1)->(r+1,0) — not shifts, the raster
    jumps — are summed directly.  O(PH*PW*C) instead of O(OH*OW*K*K*C).
    """
    ph, pw = oh + k - 1, ow + k - 1
    x = jax.lax.slice(xp, (0, 0, 0), (ph, pw, cin)).astype(jnp.int32)
    total = jnp.int32(0)
    if ow > 1:
        d = jnp.sum((jax.lax.slice(x, (0, 1, 0), (ph, pw, cin))
                     != jax.lax.slice(x, (0, 0, 0), (ph, pw - 1, cin))
                     ).astype(jnp.int32), axis=-1)        # (PH, PW-1)
        ri = jax.lax.broadcasted_iota(jnp.int32, (ph, pw - 1), 0)
        ci = jax.lax.broadcasted_iota(jnp.int32, (ph, pw - 1), 1)
        cover = _coverage(ri, oh, k) * _coverage(ci, ow - 1, k)
        total = total + jnp.sum(d * cover, dtype=jnp.int32)
    if oh > 1:
        for kh in range(k):       # k row-tap slices, each (OH-1, K, C)
            nxt = jax.lax.slice(x, (kh + 1, 0, 0), (kh + oh, k, cin))
            prv = jax.lax.slice(x, (kh, ow - 1, 0), (kh + oh - 1, pw, cin))
            total = total + jnp.sum((nxt != prv).astype(jnp.int32),
                                    dtype=jnp.int32)
    return total


def layer_epilogue(z, t_lo, t_hi, flip, const=None, is_const=None,
                   pool=None):
    """Full OCU writeback: optional merged pool, compare, const channels.

    ``z`` is the int32 accumulator shaped (N, OH, OW, C); the threshold
    vectors are per-channel and broadcast on the trailing axis.  With
    ``const is None`` the degenerate-channel fixup is skipped (legacy
    callers that patch constants outside the kernel).
    """
    if pool is not None:
        z = pool_int(z, flip, pool)
    y = two_threshold(z, t_lo, t_hi, flip)
    if const is not None:
        y = const_fixup(y, const, is_const)
    return y.astype(jnp.int8)
