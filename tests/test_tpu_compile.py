"""Compile-only rehearsals of the main-path kernels for a TPU v5e chip.

Nothing runs: each kernel is lowered and compiled at the paper's widths
(32x32 feature maps, 126 -> 128 channels) for one chip of a *described*
``v5e:2x2`` topology, so the TPU compiler (Mosaic) refuses here what it
would refuse on the chip — unit-stride-only slices, i1 selects, 8-bit
strided loads, VMEM overflow — which the interpret-mode tests cannot
see.  The topology is described inside a fixture, never at import: only
one process may load the TPU library, and every test worker imports
this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.codec import packed_size
from repro.kernels import fused_trunk as FT
from repro.kernels import ternary_conv2d as K
from repro.kernels import trit_codec as TC

C = 128          # the paper's trunk width
HW = 32          # CIFAR feature maps


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _epilogue_args(sharding, lead=()):
    """t_lo, t_hi, flip, const, is_const with the kernels' dtypes."""
    return [_spec(sharding, (*lead, C), dt) for dt in (
        jnp.float32, jnp.float32, jnp.int8, jnp.int8, jnp.int8)]


@pytest.mark.parametrize("cin,hw,n,pool,stats", [
    (126, HW, 1, None, False),          # thermometer-fed head
    (126, HW, 8, None, False),          # the same at a bucket of 8
    (C, HW, 1, ("max", 2), False),      # merged max-pool
    (C, 4, 1, ("avg", 4), False),       # the 4x4 avg-pool layer
    (C, HW, 1, None, True),             # in-kernel switching counters
], ids=["head", "head-b8", "maxpool", "avgpool4", "stats"])
def test_conv_kernel_compiles(one_chip, cin, hw, n, pool, stats):
    def f(x, w, *ep):
        return K.ternary_conv2d_pallas(x, w, **dict(zip(
            ("t_lo", "t_hi", "flip", "const", "is_const"), ep)),
            pool=pool, emit_stats=stats)

    _compile(f, _spec(one_chip, (n, hw, hw, cin), jnp.int8),
             _spec(one_chip, (3, 3, cin, C), jnp.int8),
             *_epilogue_args(one_chip))


@pytest.mark.parametrize("cin,pool", [(126, None), (C, ("max", 2))],
                         ids=["head", "maxpool"])
def test_packed_conv_kernel_compiles(one_chip, cin, pool):
    def f(x, wp, *ep):
        return K.ternary_conv2d_packed_pallas(x, wp, k=3, cin=cin, **dict(
            zip(("t_lo", "t_hi", "flip", "const", "is_const"), ep)),
            pool=pool)

    _compile(f, _spec(one_chip, (1, HW, HW, cin), jnp.int8),
             _spec(one_chip, (C, packed_size(9 * cin)), jnp.uint8),
             *_epilogue_args(one_chip))


@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
def test_fused_trunk_compiles(one_chip, packed):
    """Three layers with a max-pool; ``packed`` takes the trunk-boundary
    format in and out (5 trits/byte, one codec row per pixel)."""
    metas = (((1, 1), None), ((1, 1), ("max", 2)), ((1, 1), None))
    shape = (1, HW, HW, C)
    if packed:
        x = _spec(one_chip, (HW * HW, packed_size(C)), jnp.uint8)
    else:
        x = _spec(one_chip, shape, jnp.int8)

    def f(x, w, *ep):
        return FT.fused_trunk_pallas(
            x, w, *ep, metas=metas, packed_in=shape if packed else None,
            pack_out=packed)

    _compile(f, x, _spec(one_chip, (3, 3, 3, C, C), jnp.int8),
             *_epilogue_args(one_chip, (3,)))


def test_trit_codec_kernels_compile(one_chip):
    _compile(TC.pack_trits_pallas, _spec(one_chip, (256, 640), jnp.int8))
    _compile(TC.unpack_trits_pallas, _spec(one_chip, (256, 128), jnp.uint8))


@pytest.fixture(scope="module")
def table3_graph():
    """The paper's CNN at published widths, FC head included."""
    from repro.configs.cutie_cnn import CONFIG
    from repro.models import cutie_cnn

    params = cutie_cnn.init_params(CONFIG, jax.random.PRNGKey(0))
    return cutie_cnn.to_graph(params, CONFIG, include_head=True)


@pytest.mark.parametrize("backend", ["pallas", "packed", "fused"])
def test_table3_program_compiles(one_chip, table3_graph, backend):
    """Each backend's whole jitted CNN program at a bucket of 8 — the
    program `CutieEngine` runs — built for the chip, not the
    interpreter."""
    import dataclasses

    from repro.core import engine
    from repro.pipeline import CutiePipeline, get_backend

    inst = dataclasses.replace(engine.GF22_SCM, n_layers=9)
    pipe = CutiePipeline.compile(
        table3_graph, instance=inst, optimize=False,
        backend=get_backend(backend, interpret=False))
    shape = (8, HW, HW, 126)
    fn, _ = pipe._build(None, shape)
    lowered = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                           pipe._lowered)
    fn.lower(lowered, _spec(one_chip, shape, jnp.int8)).compile()
