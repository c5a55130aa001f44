"""Fused-trunk megakernel: L uniform layers inside ONE pallas_call.

CUTIE's thesis is that non-computational energy dominates, so the
datapath is completely unrolled and "no storing of partial results"
happens (paper §III-C) — activations flow layer to layer without ever
leaving the chip.  The per-layer execution stack contradicts that: every
``pallas_call`` boundary round-trips the activation tensor through HBM at
8 bits per 1.58-bit trit.  This kernel is the software analogue of the
ASIC's layer FIFO driving the OCU array back-to-back:

* the whole trunk's ternary weights (L, K, K, C, C) are held stationary
  in VMEM (the paper's design point — 3*3*128*128 trits x 7 layers —
  fits comfortably),
* activations ping-pong between two padded VMEM scratch buffers; each
  layer reads its padded input from one, runs the completely unrolled
  OCU window dot (every output pixel's K*K*C window against all output
  channels at once — §III-C's "single cycle" per output), and writes the
  next trit map into the other, so **zero** inter-layer HBM traffic
  occurs inside the trunk,
* the folded two-threshold epilogue, merged pre-threshold pooling and
  the degenerate-channel fixup (`repro.kernels.epilogue`, shared with the
  per-layer kernels) are applied in-register before the writeback.

The layer loop is a Python loop unrolled at trace time, so per-layer
spatial dims (stride / pooling shrink them monotonically) are static and
the scratch buffers are sized once for the trunk's input.  Trunks are
carved out of a program by ``repro.compiler.trunks.plan_segments`` under
a VMEM budget; the ``fused`` pipeline backend stitches trunks together
with trit-packed (5/byte) activations at the remaining HBM boundaries.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.codec import packed_size
from repro.core.engine import conv_out_dims, layer_out_dims
from repro.kernels import epilogue as epi
from repro.kernels import trit_codec as C


def trunk_shapes(in_hw, k: int, metas) -> list[tuple[int, int]]:
    """Static per-layer activation dims [input, after layer 0, ...].

    ``metas`` is the trunk's static layer metadata: one (stride, pool)
    pair per layer; every trunk layer is padded (padding=True), so dims
    shrink monotonically and the first layer's padded extent bounds all.
    The recurrence itself is `engine.layer_out_dims` — the same one the
    trunk planner prices scratch buffers with.
    """
    h, w = in_hw
    shapes = [(h, w)]
    for stride, pool in metas:
        h, w = layer_out_dims(k, stride, True, pool, h, w)
        shapes.append((h, w))
    return shapes


def _trunk_kernel(x_ref, w_ref, tlo_ref, thi_ref, flip_ref, const_ref,
                  isc_ref, o_ref, *rest, k: int, metas, shapes,
                  unpack_shape, pack_out: bool, stats_cin):
    """The megakernel body: unrolled layers over ping-pong scratch.

    The scratch buffers carry ``cu`` channels (the trunk's zero-padded
    common input width); every layer writes its ``c`` output channels
    into a freshly zeroed buffer, so the cu - c spare channels stay
    exactly zero and meet only zero weight rows downstream.

    With ``unpack_shape`` the kernel input is 5-trits/byte packed bytes
    (the previous trunk's output, one codec row per pixel) decoded here
    in VMEM; with ``pack_out`` the final trit map is packed before the
    writeback — so the only tensor that crosses HBM between two fused
    trunks is the packed bytes (paper §III-A's 1.6 bits/trit on the
    feature-map path).

    With ``stats_cin`` (the head layer's *logical* Cin) a second output
    ref rides along and receives per-layer int32 switching counters —
    (in-zero, out-zero, window-toggle) — computed on the activations
    while they are still in VMEM, sliced to each layer's logical channel
    count so the zero-padded spare channels never inflate them.
    """
    if stats_cin is None:
        s_ref, (a_ref, b_ref) = None, rest
    else:
        s_ref, a_ref, b_ref = rest
    p = k // 2
    n, cu = a_ref.shape[0], a_ref.shape[-1]
    c = w_ref.shape[-1]
    h, w = shapes[0]
    stat_rows = []
    a_ref[...] = jnp.zeros(a_ref.shape, jnp.int8)   # zero halo once
    if unpack_shape is None:
        a_ref[:, p:p + h, p:p + w, :] = x_ref[...]
    else:
        cin = unpack_shape[-1]
        trits = C.unpack_rows(x_ref[...], 0, cin)   # (N*H*W, Cin)
        a_ref[:, p:p + h, p:p + w, :cin] = trits.reshape(
            unpack_shape).astype(jnp.int8)
    src, dst = a_ref, b_ref
    for l, (stride, pool) in enumerate(metas):
        h, w = shapes[l]
        oh, ow = conv_out_dims(k, stride, True, h, w)
        xp = src[:, :h + 2 * p, :w + 2 * p, :]      # padded view, in VMEM
        if s_ref is not None:
            # Logical channel width of this layer's input: the head's
            # true Cin (spare trunk channels are zero-padding, not
            # activations), C afterwards.
            cin_l = stats_cin if l == 0 else c
            in_zero = epi.zero_count(src[:, p:p + h, p:p + w, :cin_l])
            toggle = epi.window_toggle_count(
                xp[0, :, :, :cin_l], k, h, w, cin_l)
        # The completely unrolled OCU dot (paper §III-C: "each output
        # channel value is computed in a single cycle"): gather every
        # output pixel's K*K*C window and contract it against all output
        # channels in ONE dot.  Accumulation runs in float32 — trit*trit
        # partial sums are integers bounded by K*K*C (+ pool window sums,
        # <= ~2e4) << 2^24, so every value is exactly representable and
        # the result is bit-identical to int32 accumulation, while the
        # whole-batch (N*H*W, K*K*C) gemm runs at full gemm throughput.
        # Windows are gathered at stride 1 (the TPU compiler lowers only
        # unit-stride slices); a strided layer then keeps every
        # stride-th accumulator row and column.
        wins = [xp[:, kh:kh + h, kw:kw + w, :]      # (N, H, W, Cu)
                for kh in range(k) for kw in range(k)]
        patch = jnp.concatenate(wins, axis=-1).reshape(
            n * h * w, k * k * cu).astype(jnp.float32)
        acc = jax.lax.dot_general(
            patch, w_ref[l].reshape(k * k * cu, c).astype(jnp.float32),
            (((1,), (0,)), ((), ())))
        z = epi.subsample(acc.reshape(n, h, w, c), stride, (oh, ow))
        vecs = [r[l:l + 1] for r in (tlo_ref, thi_ref, flip_ref, const_ref,
                                     isc_ref)]      # (1, C) each
        out = epi.layer_epilogue(z, *vecs, pool)    # (N, OH', OW', C) trits
        if s_ref is not None:
            stat_rows.append(jnp.stack(
                [in_zero, epi.zero_count(out), toggle]))
        if l == len(metas) - 1:
            if pack_out:
                o_ref[...] = C.pack_rows(out.reshape(-1, c))
            else:
                o_ref[...] = out
        else:
            nh, nw = shapes[l + 1]
            dst[...] = jnp.zeros(dst.shape, jnp.int8)
            dst[:, p:p + nh, p:p + nw, :c] = out
            src, dst = dst, src
    if s_ref is not None:
        s_ref[...] = jnp.stack(stat_rows)           # (L, 3) int32


def fused_trunk_pallas(x, w_stack, t_lo, t_hi, flip, const, is_const, *,
                       metas, packed_in=None, pack_out: bool = False,
                       emit_stats: bool = False, stats_cin=None,
                       interpret: bool = False):
    """Run a trunk of L uniform padded layers in one pallas_call.

    x (N, H, W, Cu) int8 trits; w_stack (L, K, K, Cu, C) int8, where C
    is the trunk width and Cu >= C is the common input width (the head
    layer's Cin and every layer's Cin zero-padded up to it — exact,
    because zero weights meet zero activations).  Thresholds are stacked
    per layer: t_lo/t_hi (L, C) float32, flip/const/is_const (L, C)
    int8-coercible.  ``metas`` is a static tuple of (stride, pool) per
    layer; all layers share K and C and use full zero padding (the
    trunk-fusibility contract `plan_segments` enforces).

    Trit-packed trunk boundaries: with ``packed_in=(N, H, W, Cin)`` the
    input ``x`` is instead the (N*H*W, ceil(Cin/5)) uint8 bytes a
    ``pack_out=True`` trunk produced — each pixel's channels one
    `repro.core.codec.pack_rows` row, 5 trits/byte — decoded in-VMEM
    inside the kernel; with ``pack_out=True`` the result is the final
    trit map packed the same way.  Chaining trunks this way means only
    packed bytes ever cross HBM between them.

    In-kernel switching counters: with ``emit_stats=True`` a second
    (L, 3) int32 output rides along — per layer (input-zero count over
    the whole batch's logical channels, output-zero count, window-toggle
    count of batch element 0's stride-1 raster windows) — and the return
    value becomes ``(out, stats)``.  ``stats_cin`` is the head layer's
    logical Cin (defaults to the input's channel count / the packed_in
    Cin); layers past the head use the trunk width C.  The counts are
    exactly the integers the traced per-layer path computes, so tracer
    rows derived from them are bit-identical to a per-layer traced run.
    """
    nl, k = w_stack.shape[0], w_stack.shape[1]
    cu, c = w_stack.shape[3], w_stack.shape[4]
    assert cu >= c, w_stack.shape
    assert len(metas) == nl, (len(metas), nl)
    if packed_in is None:
        n, h, w, xc = x.shape
        assert xc == cu, (x.shape, cu)
        x = x.astype(jnp.int8)
        in_spec = pl.BlockSpec((n, h, w, cu), lambda i: (0, 0, 0, 0))
    else:
        n, h, w, cin = packed_in
        assert cin <= cu, (packed_in, cu)
        assert x.shape == (n * h * w, packed_size(cin)), (
            x.shape, packed_in)
        in_spec = pl.BlockSpec(x.shape, lambda i: (0, 0))
    p = k // 2
    shapes = trunk_shapes((h, w), k, metas)
    oh, ow = shapes[-1]

    th = [jnp.asarray(t_lo, jnp.float32).reshape(nl, c),
          jnp.asarray(t_hi, jnp.float32).reshape(nl, c),
          jnp.asarray(flip).astype(jnp.int8).reshape(nl, c),
          jnp.asarray(const).astype(jnp.int8).reshape(nl, c),
          jnp.asarray(is_const).astype(jnp.int8).reshape(nl, c)]

    if pack_out:
        rows = (n * oh * ow, packed_size(c))
        out_spec = pl.BlockSpec(rows, lambda i: (0, 0))
        out_shape = jax.ShapeDtypeStruct(rows, jnp.uint8)
    else:
        out_spec = pl.BlockSpec((n, oh, ow, c), lambda i: (0, 0, 0, 0))
        out_shape = jax.ShapeDtypeStruct((n, oh, ow, c), jnp.int8)

    if emit_stats:
        if stats_cin is None:
            stats_cin = packed_in[-1] if packed_in else x.shape[-1]
        out_spec = [out_spec, pl.BlockSpec((nl, 3), lambda i: (0, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((nl, 3), jnp.int32)]
    else:
        stats_cin = None

    kernel = functools.partial(
        _trunk_kernel, k=k, metas=tuple(metas), shapes=shapes,
        unpack_shape=tuple(packed_in) if packed_in else None,
        pack_out=pack_out, stats_cin=stats_cin)
    scratch = pltpu.VMEM((n, h + 2 * p, w + 2 * p, cu), jnp.int8)

    return pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[
            in_spec,
            pl.BlockSpec((nl, k, k, cu, c), lambda i: (0, 0, 0, 0, 0)),
            *[pl.BlockSpec((nl, c), lambda i: (0, 0)) for _ in th],
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[scratch, scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x, w_stack.astype(jnp.int8), *th)
