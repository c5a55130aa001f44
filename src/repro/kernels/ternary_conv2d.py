"""Ternary KxK conv with fused OCU epilogue — the OCU array.

This is the literal CUTIE regime: for the paper's design point
(K=3, N_I=N_O=128, 32x32 feature maps) the *entire* weight tensor
(3*3*128*128 trits) plus one whole padded input image fit comfortably in
VMEM, so the kernel holds the weights stationary for the full layer and the
grid walks (image, output-channel tile) only — there is no K-reduction grid
axis and no partial-sum traffic to HBM, matching "each output channel value
is computed in a single cycle ... no storing of partial results" (§III-C).

The K*K spatial taps are a Python loop *inside* the kernel (fully unrolled
at trace time — the filter-dimension unrolling of Listing 1), each tap being
an (OH*OW, C_in) x (C_in, bco) int8 MXU dot.  The taps always run at
stride 1; a strided layer keeps every stride-th accumulator row and
column (`epilogue.subsample`), because the TPU compiler lowers only
unit-stride slices.

Layout: x NHWC (pre-padded outside), w HWIO, out NHWC.  The fused epilogue
(`repro.kernels.epilogue`, shared with the fused-trunk megakernel) applies
merged pre-threshold pooling, the folded two-threshold compare and the
degenerate-channel fixup in-register, so neither the int32 accumulator nor
the pooled integers ever leave registers/VMEM.

Two weight layouts are supported:

* :func:`ternary_conv2d_pallas` — dense int8 trits (K, K, Cin, Cout),
* :func:`ternary_conv2d_packed_pallas` — weights stored packed at
  5 trits/byte (paper §III-A), one byte row per output channel, decoded
  *inside* the kernel right next to the taps that consume them (the
  deployment path: HBM holds 1.6 bits/trit, VMEM briefly holds the tile's
  decoded slice).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.codec import TRITS_PER_BYTE
from repro.kernels import epilogue as epi
from repro.kernels import trit_codec as C

# lhs (M, Cin) x rhs (Cin, bco), and x rhs (bco, Cin) for packed rows
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))


def _conv_taps(xv, w_at, k: int, oh: int, ow: int, dims=_NN) -> jax.Array:
    """Unrolled K*K stride-1 taps over a padded image -> (OH*OW, bco)
    int32 acc.

    ``xv`` is the (PH, PW, Cin) padded image; ``w_at(kh, kw)`` yields the
    tap weights, contracted with the window by ``dims``.
    """
    cin = xv.shape[-1]
    acc = None
    for kh in range(k):                             # completely unrolled taps
        for kw in range(k):
            win = xv[kh:kh + oh, kw:kw + ow, :]     # (OH, OW, Cin)
            d = jax.lax.dot_general(
                win.reshape(oh * ow, cin), w_at(kh, kw), dims,
                preferred_element_type=jnp.int32)
            acc = d if acc is None else acc + d
    return acc


def _finish(acc, o_ref, ep_refs, *, full_hw, stride, oh: int, ow: int,
            pool, fuse_threshold: bool):
    """Shared writeback: raw int32, or the fused epilogue to trits.

    ``acc`` is the stride-1 accumulator over ``full_hw``; (oh, ow) are the
    layer's strided conv dims.  Returns the written block so callers can
    derive in-VMEM statistics from it without re-reading the output ref.
    """
    z = epi.subsample(acc.reshape(1, *full_hw, acc.shape[-1]), stride,
                      (oh, ow))
    if not fuse_threshold:
        out = z[0]
        o_ref[0] = out
        return out
    vecs = [r[...] for r in ep_refs]                # (1, bco) each
    t_lo, t_hi, flip = vecs[:3]
    const, is_const = vecs[3:] if len(vecs) == 5 else (None, None)
    out = epi.layer_epilogue(z, t_lo, t_hi, flip, const, is_const, pool)
    o_ref[...] = out
    return out


def _cell_stats(xv, out, s_ref, *, k: int, padding: bool, hw):
    """Per-grid-cell int32 counters: (in-zero, out-zero, window-toggle).

    The grid's two axes are "parallel" — cells cannot accumulate into a
    shared slot — so each (image, cout-tile) cell writes its own (3,)
    row and the host combines them (`combine_cell_stats`): in-zero and
    toggle are whole-image quantities (identical across cout tiles),
    out-zero covers the cell's channel tile.  ``xv`` is the cell's
    (PH, PW, Cin) input as the kernel sees it (pre-padded when the layer
    pads), ``hw`` the *unpadded* (H, W), so in-zero counts the logical
    interior only and the stride-1 toggle raster matches the traced
    `energy.switching.window_toggle_count` exactly.
    """
    h0, w0 = hw
    if padding:
        p = k // 2
        interior = xv[p:p + h0, p:p + w0, :]
        wh, ww = h0, w0
    else:
        interior = xv
        wh, ww = h0 - k + 1, w0 - k + 1
    s_ref[0, 0] = jnp.stack([
        epi.zero_count(interior),
        epi.zero_count(out),
        epi.window_toggle_count(xv, k, wh, ww, xv.shape[-1]),
    ])


def _full_hw(xv, k: int):
    """Stride-1 conv output dims of a padded (PH, PW, Cin) image."""
    return xv.shape[0] - k + 1, xv.shape[1] - k + 1


def _conv_kernel(x_ref, w_ref, *rest, k: int, stride, oh: int, ow: int,
                 fuse_threshold: bool, pool, emit_stats: bool, padding,
                 stats_hw):
    if emit_stats:
        o_ref, s_ref = rest[-2], rest[-1]
        ep_refs = rest[:-2]
    else:
        o_ref, s_ref = rest[-1], None
        ep_refs = rest[:-1]  # no scratch: accumulator lives in registers
    xv = x_ref[0]
    full_hw = _full_hw(xv, k)
    acc = _conv_taps(xv, lambda kh, kw: w_ref[kh, kw], k, *full_hw)
    out = _finish(acc, o_ref, ep_refs, full_hw=full_hw, stride=stride,
                  oh=oh, ow=ow, pool=pool, fuse_threshold=fuse_threshold)
    if s_ref is not None:
        _cell_stats(x_ref[0], out, s_ref, k=k, padding=padding,
                    hw=stats_hw)


def _packed_conv_kernel(x_ref, wp_ref, *rest, k: int, cin: int, stride,
                        oh: int, ow: int, pool, emit_stats: bool, padding,
                        stats_hw):
    """Conv with the 5-trits/byte decode fused in front of the taps."""
    if emit_stats:
        o_ref, s_ref = rest[-2], rest[-1]
        ep_refs = rest[:-2]
    else:
        o_ref, s_ref = rest[-1], None
        ep_refs = rest[:-1]
    wp = wp_ref[...]                                # (bco, G) bytes

    def w_at(kh, kw):                               # (bco, Cin) tap rows
        return C.unpack_rows(wp, (kh * k + kw) * cin, cin).astype(jnp.int8)

    xv = x_ref[0]
    full_hw = _full_hw(xv, k)
    acc = _conv_taps(xv, w_at, k, *full_hw, dims=_NT)
    out = _finish(acc, o_ref, ep_refs, full_hw=full_hw, stride=stride,
                  oh=oh, ow=ow, pool=pool, fuse_threshold=bool(ep_refs))
    if s_ref is not None:
        _cell_stats(x_ref[0], out, s_ref, k=k, padding=padding,
                    hw=stats_hw)


def combine_cell_stats(cells) -> "jnp.ndarray":
    """(N, Cout-tiles, 3) per-cell counters -> the layer's (3,) totals.

    in-zero is per-image (summed over the batch, read from tile 0);
    out-zero sums every cell (each covers one channel tile); toggle is
    batch element 0's whole-image raster (tile 0 of image 0).
    """
    return jnp.stack([jnp.sum(cells[:, 0, 0]),
                      jnp.sum(cells[:, :, 1]),
                      cells[0, 0, 2]])


def _geometry(x, k: int, stride, padding: bool):
    """Pad the input and compute conv output dims (shared by both layouts)."""
    _, h, wd, _ = x.shape
    sh, sw = stride
    if padding:
        p = k // 2
        x = jnp.pad(x, ((0, 0), (p, p), (p, p), (0, 0)))
        oh, ow = -(-h // sh), -(-wd // sw)
    else:
        oh = (h - k) // sh + 1
        ow = (wd - k) // sw + 1
    return x, oh, ow


def _epilogue_operands(cout: int, t_lo, t_hi, flip, const, is_const, pool,
                       oh: int, ow: int):
    """Stack the per-channel epilogue vectors + the blocked output shape.

    Returns (operands, out_dims, out_dtype): 3 vectors (legacy compare-only
    epilogue) or 5 (with the degenerate-channel fixup); pooling shrinks the
    output dims and requires the fused epilogue.
    """
    fuse = t_lo is not None
    if pool is not None and not fuse:
        raise ValueError("merged pooling requires the fused threshold "
                         "epilogue (t_lo/t_hi/flip)")
    if not fuse:
        return [], (oh, ow), jnp.int32
    ep = [jnp.asarray(t_lo, jnp.float32).reshape(1, cout),
          jnp.asarray(t_hi, jnp.float32).reshape(1, cout),
          jnp.asarray(flip).astype(jnp.int8).reshape(1, cout)]
    if const is not None:
        ep += [jnp.asarray(const).astype(jnp.int8).reshape(1, cout),
               jnp.asarray(is_const).astype(jnp.int8).reshape(1, cout)]
    if pool is not None:
        win = pool[1]
        oh, ow = oh // win, ow // win
    return ep, (oh, ow), jnp.int8


def _stats_outputs(emit_stats: bool, fuse: bool, n: int, tiles: int,
                   out_spec, out_shape):
    """Append the (N, tiles, 3) int32 per-cell counter output when asked."""
    if not emit_stats:
        return out_spec, out_shape
    if not fuse:
        raise ValueError("emit_stats requires the fused threshold "
                         "epilogue (t_lo/t_hi/flip): raw int32 outputs "
                         "have no trit statistics")
    return ([out_spec, pl.BlockSpec((1, 1, 3), lambda i, j: (i, j, 0))],
            [out_shape, jax.ShapeDtypeStruct((n, tiles, 3), jnp.int32)])


def ternary_conv2d_pallas(x, w, *, stride=(1, 1), padding=True,
                          t_lo=None, t_hi=None, flip=None,
                          const=None, is_const=None, pool=None,
                          bco: int = 128, emit_stats: bool = False,
                          interpret: bool = False):
    """NHWC trit conv.  x (N,H,W,Cin) int8, w (K,K,Cin,Cout) int8.

    Fused thresholds (t_lo/t_hi/flip per Cout) produce int8 trits; adding
    const/is_const also resolves degenerate (g == 0) channels in-kernel,
    and ``pool=("max"|"avg", win)`` applies merged pooling on the int32
    accumulator before the compare (paper Fig. 5).  Without thresholds the
    raw int32 pre-activations are returned.

    ``emit_stats=True`` adds a per-grid-cell int32 counter output (see
    `_cell_stats`) and returns ``(y, stats)`` where ``stats`` is the
    layer's combined (3,) totals — (in-zero, out-zero, window-toggle) —
    integer-identical to the traced per-layer statistics.
    """
    n, h0, w0, cin = x.shape
    k, _, _, cout = w.shape
    x, oh, ow = _geometry(x, k, stride, padding)
    ph, pw = x.shape[1], x.shape[2]
    bco = min(bco, cout)
    assert cout % bco == 0

    ep, (po, pq), out_dtype = _epilogue_operands(
        cout, t_lo, t_hi, flip, const, is_const, pool, oh, ow)
    ep_specs = [pl.BlockSpec((1, bco), lambda i, j: (0, j)) for _ in ep]

    kernel = functools.partial(
        _conv_kernel, k=k, stride=stride, oh=oh, ow=ow,
        fuse_threshold=bool(ep), pool=pool, emit_stats=emit_stats,
        padding=padding, stats_hw=(h0, w0))
    out_specs, out_shape = _stats_outputs(
        emit_stats, bool(ep), n, cout // bco,
        pl.BlockSpec((1, po, pq, bco), lambda i, j: (i, 0, 0, j)),
        jax.ShapeDtypeStruct((n, po, pq, cout), out_dtype))

    got = pl.pallas_call(
        kernel,
        grid=(n, cout // bco),
        in_specs=[
            pl.BlockSpec((1, ph, pw, cin), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((k, k, cin, bco), lambda i, j: (0, 0, 0, j)),
            *ep_specs,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ternary_conv2d",
    )(x.astype(jnp.int8), w.astype(jnp.int8), *ep)
    if emit_stats:
        y, cells = got
        return y, combine_cell_stats(cells)
    return got


def ternary_conv2d_packed_pallas(x, w_packed, *, k: int, cin: int,
                                 stride=(1, 1), padding=True,
                                 t_lo=None, t_hi=None, flip=None,
                                 const=None, is_const=None, pool=None,
                                 bco: int = 128, emit_stats: bool = False,
                                 interpret: bool = False):
    """Conv from packed weights: decode happens next to the compute.

    ``w_packed`` is (Cout, G) uint8 — each row one output channel's
    K*K*Cin weights at 5 trits/byte (`repro.core.codec.pack_filter_rows`).
    The kernel decodes its Cout tile in VMEM and runs the same taps +
    fused epilogue as the dense kernel; the dense weight tensor never
    exists outside the kernel.  ``emit_stats`` as in
    :func:`ternary_conv2d_pallas`.
    """
    n, h0, w0 = x.shape[0], x.shape[1], x.shape[2]
    cout, g = w_packed.shape
    assert g * TRITS_PER_BYTE >= k * k * cin, (g, k, cin)
    x, oh, ow = _geometry(x, k, stride, padding)
    ph, pw = x.shape[1], x.shape[2]
    bco = min(bco, cout)
    assert cout % bco == 0

    ep, (po, pq), out_dtype = _epilogue_operands(
        cout, t_lo, t_hi, flip, const, is_const, pool, oh, ow)
    ep_specs = [pl.BlockSpec((1, bco), lambda i, j: (0, j)) for _ in ep]

    kernel = functools.partial(
        _packed_conv_kernel, k=k, cin=cin, stride=stride, oh=oh, ow=ow,
        pool=pool, emit_stats=emit_stats, padding=padding,
        stats_hw=(h0, w0))
    out_specs, out_shape = _stats_outputs(
        emit_stats, bool(ep), n, cout // bco,
        pl.BlockSpec((1, po, pq, bco), lambda i, j: (i, 0, 0, j)),
        jax.ShapeDtypeStruct((n, po, pq, cout), out_dtype))

    got = pl.pallas_call(
        kernel,
        grid=(n, cout // bco),
        in_specs=[
            pl.BlockSpec((1, ph, pw, cin), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((bco, g), lambda i, j: (j, 0)),
            *ep_specs,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ternary_conv2d_packed",
    )(x.astype(jnp.int8), w_packed, *ep)
    if emit_stats:
        y, cells = got
        return y, combine_cell_stats(cells)
    return got
