"""Fused-trunk execution: megakernel, shared epilogue, segmentation.

The acceptance property of the ``fused`` backend: a contiguous trunk of
uniform layers running inside ONE Pallas megakernel — weights stationary
in VMEM, activations ping-ponging between scratch buffers, pooling /
thresholds / degenerate channels resolved in-register — is bit-identical
to the ``ref`` oracle, and so are the per-layer kernels it falls back to
at trunk boundaries (including the packed-decode-in-kernel conv).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compiler
from repro.core import codec, engine
from repro.kernels import fused_trunk as FT
from repro.kernels import ternary_conv2d as K
from repro.pipeline import CutiePipeline, FusedBackend, StatsTracer


def _layer(key, cin, cout, *, pool=None, stride=(1, 1), padding=True,
           const_frac=0.0):
    k1, k2, k3 = jax.random.split(key, 3)
    w = jax.random.normal(k1, (3, 3, cin, cout))
    gamma = jax.random.normal(k2, (cout,)) + 0.5
    if const_frac:
        gamma = jnp.where(jax.random.bernoulli(k3, const_frac, (cout,)),
                          0.0, gamma)
    bn = {"gamma": gamma, "beta": jnp.zeros((cout,)),
          "mean": jnp.zeros((cout,)), "var": jnp.ones((cout,))}
    return engine.compile_layer(w, bn, pool=pool, stride=stride,
                                padding=padding)


def _trits(key, shape):
    return jax.random.randint(key, shape, -1, 2).astype(jnp.int8)


def _stack_thresholds(layers):
    return [jnp.stack([getattr(li.thresholds, f) for li in layers])
            for f in ("t_lo", "t_hi", "flip", "const", "is_const")]


def _oracle(layers, x):
    cur = x
    for li in layers:
        cur, _ = engine.run_layer(cur, li)
    return np.asarray(cur)


# ---------------------------------------------------------------------------
# per-layer kernel: pool x stride x fused threshold epilogue vs ref oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pool", [None, ("max", 2), ("avg", 2), ("max", 3)])
@pytest.mark.parametrize("stride", [(1, 1), (2, 2)])
@pytest.mark.parametrize("padding", [True, False])
def test_conv_kernel_full_epilogue_matches_ref(pool, stride, padding):
    instr = _layer(jax.random.PRNGKey(hash((pool, stride, padding)) % 1000),
                   8, 16, pool=pool, stride=stride, padding=padding,
                   const_frac=0.25)
    x = _trits(jax.random.PRNGKey(1), (2, 13, 13, 8))
    want, _ = engine.run_layer(x, instr)
    th = instr.thresholds
    got = K.ternary_conv2d_pallas(
        x, instr.weights, stride=stride, padding=padding,
        t_lo=th.t_lo, t_hi=th.t_hi, flip=th.flip, const=th.const,
        is_const=th.is_const, pool=pool, interpret=True)
    assert got.dtype == jnp.int8
    assert np.array_equal(np.asarray(want), np.asarray(got))


def test_conv_kernel_degenerate_pool_geometry_raises_clearly():
    """Pool window larger than the conv output: a named error at trace
    time, not a negative-limit lax.slice TypeError from inside the
    kernel."""
    instr = _layer(jax.random.PRNGKey(8), 8, 8, pool=("avg", 4))
    x = _trits(jax.random.PRNGKey(9), (1, 2, 2, 8))
    th = instr.thresholds
    with pytest.raises(ValueError, match="pool window 4 exceeds"):
        K.ternary_conv2d_pallas(
            x, instr.weights, t_lo=th.t_lo, t_hi=th.t_hi, flip=th.flip,
            const=th.const, is_const=th.is_const, pool=("avg", 4),
            interpret=True)


def test_conv_kernel_pool_requires_thresholds():
    instr = _layer(jax.random.PRNGKey(0), 8, 8, pool=("max", 2))
    x = _trits(jax.random.PRNGKey(1), (1, 8, 8, 8))
    with pytest.raises(ValueError, match="pooling requires"):
        K.ternary_conv2d_pallas(x, instr.weights, pool=("max", 2),
                                interpret=True)


def test_conv_kernel_legacy_three_vector_epilogue_still_works():
    """Callers without const/is_const (kernels/ops.py) keep old semantics."""
    instr = _layer(jax.random.PRNGKey(3), 8, 8)
    x = _trits(jax.random.PRNGKey(4), (1, 8, 8, 8))
    th = instr.thresholds
    got = K.ternary_conv2d_pallas(x, instr.weights, t_lo=th.t_lo,
                                  t_hi=th.t_hi, flip=th.flip,
                                  interpret=True)
    want, _ = engine.run_layer(x, instr)
    assert np.array_equal(np.asarray(want), np.asarray(got))


# ---------------------------------------------------------------------------
# degenerate (g == 0) channels resolve inside the kernels (regression:
# the fixup used to be a post-kernel jnp.where on the pallas backend only)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["pallas", "packed", "fused"])
@pytest.mark.parametrize("pool", [None, ("max", 2)])
def test_constant_channels_fixed_up_in_kernel(backend, pool):
    layers = [_layer(k, 8, 8, pool=pool, const_frac=0.5)
              for k in jax.random.split(jax.random.PRNGKey(5), 3)]
    assert any(bool(np.asarray(li.thresholds.is_const).any())
               for li in layers)
    prog = engine.CutieProgram(layers, engine.CutieInstance(n_i=8, n_o=8))
    x = _trits(jax.random.PRNGKey(6), (2, 8, 8, 8))
    want = np.asarray(CutiePipeline(prog, backend="ref").run(x))
    got = np.asarray(CutiePipeline(prog, backend=backend).run(x))
    assert np.array_equal(want, got)


# ---------------------------------------------------------------------------
# packed-decode-in-kernel bit-exactness across channel counts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cin,cout", [(5, 13), (8, 8), (13, 7), (16, 24),
                                      (20, 40)])
def test_packed_decode_in_kernel_matches_ref(cin, cout):
    """Channel counts the compiler's pad_to/DCE can emit: K*K*Cin rarely
    a multiple of 5, Cout not a power of two."""
    instr = _layer(jax.random.PRNGKey(cin * 100 + cout), cin, cout,
                   const_frac=0.2)
    x = _trits(jax.random.PRNGKey(2), (2, 9, 9, cin))
    want, _ = engine.run_layer(x, instr)
    th = instr.thresholds
    wp = codec.pack_filter_rows(instr.weights)
    assert wp.shape == (cout, -(-3 * 3 * cin // 5))
    got = K.ternary_conv2d_packed_pallas(
        x, wp, k=3, cin=cin, t_lo=th.t_lo, t_hi=th.t_hi, flip=th.flip,
        const=th.const, is_const=th.is_const, interpret=True)
    assert np.array_equal(np.asarray(want), np.asarray(got))


def test_packed_backend_on_pad_to_compiled_program():
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    g = compiler.Graph(in_channels=5, in_hw=(8, 8))
    g.conv(jax.random.normal(ks[0], (3, 3, 5, 13)),
           {"gamma": jax.random.normal(ks[2], (13,)) + 0.5})
    g.conv(jax.random.normal(ks[1], (3, 3, 13, 7)),
           {"gamma": jax.random.normal(ks[3], (7,)) + 0.5})
    x = _trits(ks[0], (1, 8, 8, 5))
    for pad_to in (None, 16):
        res = compiler.compile_graph(g, optimize=False, pad_to=pad_to)
        want = np.asarray(CutiePipeline(res.program, backend="ref").run(x))
        got = np.asarray(
            CutiePipeline(res.program, backend="packed").run(x))
        assert np.array_equal(want, got), pad_to


# ---------------------------------------------------------------------------
# the trunk megakernel
# ---------------------------------------------------------------------------


def test_trunk_kernel_uniform_layers_matches_oracle():
    layers = [_layer(k, 8, 8, const_frac=0.2)
              for k in jax.random.split(jax.random.PRNGKey(11), 5)]
    x = _trits(jax.random.PRNGKey(12), (3, 10, 10, 8))
    got = FT.fused_trunk_pallas(
        x, jnp.stack([li.weights for li in layers]),
        *_stack_thresholds(layers),
        metas=tuple((li.stride, li.pool) for li in layers), interpret=True)
    assert np.array_equal(_oracle(layers, x), np.asarray(got))


@pytest.mark.parametrize("pools,strides", [
    ([None, ("max", 2), None, ("avg", 2)],
     [(1, 1), (1, 1), (1, 1), (1, 1)]),
    ([None, None, ("max", 2)], [(2, 2), (1, 1), (1, 1)]),
    ([("avg", 4)], [(1, 1)]),
])
def test_trunk_kernel_pool_and_stride_inside_trunk(pools, strides):
    keys = jax.random.split(jax.random.PRNGKey(13), len(pools))
    layers = [_layer(k, 8, 8, pool=p, stride=s, const_frac=0.2)
              for k, p, s in zip(keys, pools, strides)]
    x = _trits(jax.random.PRNGKey(14), (2, 16, 16, 8))
    got = FT.fused_trunk_pallas(
        x, jnp.stack([li.weights for li in layers]),
        *_stack_thresholds(layers),
        metas=tuple((li.stride, li.pool) for li in layers), interpret=True)
    assert np.array_equal(_oracle(layers, x), np.asarray(got))


def test_trunk_shapes_static_inference():
    metas = (((1, 1), None), ((1, 1), ("max", 2)), ((2, 2), None))
    assert FT.trunk_shapes((16, 16), 3, metas) == [
        (16, 16), (16, 16), (8, 8), (4, 4)]


# ---------------------------------------------------------------------------
# trunk segmentation (compiler pass)
# ---------------------------------------------------------------------------


def _uniform(c, depth, seed=0, **kw):
    keys = jax.random.split(jax.random.PRNGKey(seed), depth)
    return [_layer(k, c, c, **kw) for k in keys]


def _instance(c=16):
    return engine.CutieInstance(n_i=c, n_o=c)


def test_segmentation_uniform_program_is_one_trunk():
    prog = engine.CutieProgram(_uniform(8, 4), _instance(8))
    segs = compiler.plan_segments(prog, (2, 8, 8, 8))
    assert segs == [compiler.Trunk(0, 4, fused=True,
                                   vmem_bytes=segs[0].vmem_bytes)]
    assert segs[0].vmem_bytes == compiler.trunk_vmem_bytes(
        prog.layers, (2, 8, 8, 8))


def test_segmentation_breaks_on_width_change_but_heads_may_widen():
    """A trunk head's Cin may differ (zero-padded in); width changes
    mid-run start a new trunk instead."""
    ks = jax.random.split(jax.random.PRNGKey(21), 6)
    layers = (
        [_layer(ks[0], 6, 8)]            # Cin != Cout -> heads trunk 1
        + [_layer(k, 8, 8) for k in ks[1:3]]
        + [_layer(ks[3], 8, 16)]         # width change -> heads trunk 2
        + [_layer(k, 16, 16) for k in ks[4:6]])
    prog = engine.CutieProgram(layers, _instance())
    segs = compiler.plan_segments(prog, (1, 12, 12, 6))
    assert [(s.start, s.stop, s.fused) for s in segs] == [
        (0, 3, True), (3, 6, True)]


def test_segmentation_unpadded_layer_breaks_trunk():
    layers = _uniform(8, 2, seed=22) + \
        [_layer(jax.random.PRNGKey(23), 8, 8, padding=False)] + \
        _uniform(8, 2, seed=24)
    prog = engine.CutieProgram(layers, _instance(8))
    segs = compiler.plan_segments(prog, (1, 12, 12, 8))
    assert [(s.start, s.stop, s.fused) for s in segs] == [
        (0, 2, True), (2, 3, False), (3, 5, True)]


def test_segmentation_vmem_budget_splits_trunk():
    prog = engine.CutieProgram(_uniform(8, 6, seed=25), _instance(8))
    in_shape = (1, 8, 8, 8)
    full = compiler.plan_segments(prog, in_shape)
    assert [s.fused for s in full] == [True]
    # budget that fits ~2 layers of weights + the fixed activation cost
    fixed = compiler.trunk_vmem_bytes(prog.layers[:1], in_shape) \
        - int(prog.layers[0].weights.size)
    budget = fixed + 2 * int(prog.layers[0].weights.size) + 100
    segs = compiler.plan_segments(prog, in_shape, budget)
    assert len(segs) > 1
    assert all(s.fused for s in segs if len(s) >= 2)
    assert [s.start for s in segs] + [segs[-1].stop] == sorted(
        set([s.start for s in segs] + [s.stop for s in segs]))
    # still covers every layer exactly once, in order
    cover = [i for s in segs for i in range(s.start, s.stop)]
    assert cover == list(range(len(prog.layers)))


def test_segmentation_lone_layers_stay_per_layer_and_group():
    """No two consecutive layers share a width: nothing trunks, and the
    whole run collapses into ONE per-layer segment (fewest boundaries)."""
    ks = jax.random.split(jax.random.PRNGKey(26), 3)
    layers = [_layer(ks[0], 6, 8), _layer(ks[1], 8, 16),
              _layer(ks[2], 16, 6)]
    prog = engine.CutieProgram(layers, _instance())
    segs = compiler.plan_segments(prog, (1, 8, 8, 6))
    assert [(s.start, s.stop, s.fused) for s in segs] == [(0, 3, False)]


def test_segmentation_widening_head_plus_tail():
    """Head widens into the trunk; the width-changing tail falls back."""
    ks = jax.random.split(jax.random.PRNGKey(27), 3)
    layers = [_layer(ks[0], 6, 8), _layer(ks[1], 8, 8),
              _layer(ks[2], 8, 6)]
    prog = engine.CutieProgram(layers, _instance())
    segs = compiler.plan_segments(prog, (1, 8, 8, 6))
    assert [(s.start, s.stop, s.fused) for s in segs] == [
        (0, 2, True), (2, 3, False)]


# ---------------------------------------------------------------------------
# the fused backend end-to-end
# ---------------------------------------------------------------------------


def _cifar_like_program(seed=31, c=16, cin=10):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    pools = [None, None, ("max", 2), None, ("max", 2), None, ("max", 2),
             ("avg", 4)]
    layers = [_layer(ks[0], cin, c, pool=pools[0], const_frac=0.1)]
    layers += [_layer(k, c, c, pool=p, const_frac=0.1)
               for k, p in zip(ks[1:], pools[1:])]
    return engine.CutieProgram(layers, _instance(c))


@pytest.mark.parametrize("pack_boundaries", [True, False])
def test_fused_backend_cifar_like_bit_identical(pack_boundaries):
    prog = _cifar_like_program()
    x = _trits(jax.random.PRNGKey(32), (2, 32, 32, 10))
    want = np.asarray(CutiePipeline(prog, backend="ref").run(x))
    be = FusedBackend(pack_boundaries=pack_boundaries)
    pipe = CutiePipeline(prog, backend=be)
    assert np.array_equal(np.asarray(pipe.run(x)), want)
    # the whole net — thermometer-width head included — is ONE trunk
    segs = be.plan(prog, x.shape)
    assert [(s.start, s.stop, s.fused) for s in segs] == [(0, 8, True)]


def test_fused_backend_small_budget_multi_trunk_bit_identical():
    prog = engine.CutieProgram(_uniform(8, 6, seed=33), _instance(8))
    x = _trits(jax.random.PRNGKey(34), (2, 10, 10, 8))
    want = np.asarray(CutiePipeline(prog, backend="ref").run(x))
    budget = compiler.trunk_vmem_bytes(prog.layers[:3], x.shape) + 1
    be = FusedBackend(vmem_budget=budget)
    assert len(be.plan(prog, x.shape)) > 1
    assert np.array_equal(
        np.asarray(CutiePipeline(prog, backend=be).run(x)), want)


def test_fused_backend_traced_run_matches_ref_stats():
    """A kernel_stats tracer rides the fused program itself: per-layer
    integer counters come back from inside the megakernel, and the rows
    derived from them must be identical to the ref backend's."""
    prog = _cifar_like_program(seed=35, c=8, cin=8)
    x = _trits(jax.random.PRNGKey(36), (1, 32, 32, 8))
    y_ref, rows_ref = CutiePipeline(prog, backend="ref").run(
        x, tracer=StatsTracer())
    y, rows = CutiePipeline(prog, backend="fused").run(
        x, tracer=StatsTracer())
    assert np.array_equal(np.asarray(y), np.asarray(y_ref))
    assert rows == rows_ref


def test_trunk_boundary_packed_io_matches_codec():
    """fused->fused boundaries: the producer's pack_out bytes are
    exactly the reference codec's packing of its trit output (one row
    per pixel), and the consumer's in-kernel decode reproduces the dense
    execution."""
    layers = [_layer(k, 8, 8, const_frac=0.2)
              for k in jax.random.split(jax.random.PRNGKey(37), 4)]
    x = _trits(jax.random.PRNGKey(38), (2, 9, 9, 8))
    a, b = layers[:2], layers[2:]

    def call(ls, x, **kw):
        return FT.fused_trunk_pallas(
            x, jnp.stack([li.weights for li in ls]),
            *_stack_thresholds(ls),
            metas=tuple((li.stride, li.pool) for li in ls),
            interpret=True, **kw)

    mid_dense = call(a, x)
    packed = call(a, x, pack_out=True)
    assert packed.dtype == jnp.uint8
    assert np.array_equal(
        np.asarray(packed),
        np.asarray(codec.pack_rows(mid_dense.reshape(-1, 8))))
    out = call(b, packed, packed_in=tuple(mid_dense.shape))
    assert np.array_equal(_oracle(layers, x), np.asarray(out))


def test_fused_backend_respects_scan_flag_compat():
    """scan=True pipelines still work (build_program path ignores scan)."""
    prog = engine.CutieProgram(_uniform(8, 3, seed=38), _instance(8))
    x = _trits(jax.random.PRNGKey(39), (1, 8, 8, 8))
    a = np.asarray(CutiePipeline(prog, backend="fused", scan=True).run(x))
    b = np.asarray(CutiePipeline(prog, backend="fused", scan=False).run(x))
    want = np.asarray(CutiePipeline(prog, backend="ref").run(x))
    assert np.array_equal(a, want) and np.array_equal(b, want)


def test_fused_backend_mixed_program_everything_at_once():
    """Channel growth, stride, pools, unpadded tail: segmentation +
    per-layer fallback + trunks compose bit-exactly."""
    ks = jax.random.split(jax.random.PRNGKey(41), 7)
    layers = [
        _layer(ks[0], 6, 12),
        _layer(ks[1], 12, 12, pool=("max", 2), const_frac=0.3),
        _layer(ks[2], 12, 12, stride=(2, 2)),
        _layer(ks[3], 12, 12, pool=("avg", 2)),
        _layer(ks[4], 12, 24),
        _layer(ks[5], 24, 24, padding=False),
    ]
    prog = engine.CutieProgram(layers, _instance(24))
    x = _trits(ks[6], (2, 24, 24, 6))
    want = np.asarray(CutiePipeline(prog, backend="ref").run(x))
    got = np.asarray(CutiePipeline(prog, backend="fused").run(x))
    assert np.array_equal(want, got)


def test_trunk_dataclass_invariants():
    t = compiler.Trunk(2, 5, fused=True, vmem_bytes=10)
    assert len(t) == 3
    with pytest.raises(dataclasses.FrozenInstanceError):
        t.start = 0
