"""Sharded (mesh) execution is bit-identical to single-device execution.

Pins the tentpole properties of `repro.launch.cutie_mesh` +
`CutiePipeline(mesh=...)`:

* data-parallel batch sharding for batch sizes that do NOT divide the
  mesh (the padding path),
* filter-dimension (output-channel / OCU) sharding for channel counts
  that do NOT divide the device count (zero-weight / constant-zero
  threshold padding),
* all registered execution backends under a mesh,
* engine submit -> result through a meshed ProgramExecutor, including
  bucket rounding and per-device occupancy stats.

Host topology comes from ``conftest.py``'s session-wide XLA_FLAGS; the
``host_devices`` fixture skips when it could not be applied.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.core import engine
from repro.launch.cutie_mesh import MeshSpec, pad_program_for_filter
from repro.pipeline import CutiePipeline
from repro.serving import CutieEngine


def _program(c_in, c, n_layers, seed=0, pools=None):
    keys = jax.random.split(jax.random.PRNGKey(seed), n_layers)
    instrs, cin = [], c_in
    for i, k in enumerate(keys):
        k1, k2 = jax.random.split(k)
        w = jax.random.normal(k1, (3, 3, cin, c))
        bn = {"gamma": jax.random.normal(k2, (c,)) + 0.5,
              "beta": jnp.zeros((c,)), "mean": jnp.zeros((c,)),
              "var": jnp.ones((c,))}
        instrs.append(engine.compile_layer(
            w, bn, pool=pools[i] if pools else None))
        cin = c
    inst = engine.CutieInstance(n_i=max(c_in, c), n_o=c)
    return engine.CutieProgram(instrs, inst)


@pytest.fixture(scope="module")
def uniform_prog():
    return _program(6, 6, 3)


@pytest.fixture(scope="module")
def uniform_oracle(uniform_prog, rng):
    x = rng.integers(-1, 2, (8, 8, 8, 6)).astype(np.int8)
    y = np.asarray(CutiePipeline(uniform_prog, backend="ref").run(x))
    return x, y


# -- mesh spec parsing (no devices needed) ----------------------------------


def test_meshspec_parse():
    assert MeshSpec.parse(4) == MeshSpec(data=4)
    assert MeshSpec.parse("data:2,filter:3") == MeshSpec(2, 3)
    assert MeshSpec.parse("filter:2") == MeshSpec(1, 2)
    assert MeshSpec.parse({"data": 2}) == MeshSpec(2, 1)
    assert MeshSpec.parse((2, 4)) == MeshSpec(2, 4)
    assert MeshSpec.parse(MeshSpec(1, 2)) == MeshSpec(1, 2)
    assert MeshSpec(2, 3).n_devices == 6
    with pytest.raises(ValueError):
        MeshSpec.parse("model:4")
    with pytest.raises(ValueError):
        MeshSpec.parse({"pipeline": 2})
    with pytest.raises(ValueError):
        MeshSpec(data=0)
    with pytest.raises(TypeError):
        MeshSpec.parse(3.5)


def test_meshspec_from_mesh(host_devices):
    mesh = jax.make_mesh((2, 4), ("data", "filter"),
                         axis_types=(AxisType.Auto,) * 2)
    assert MeshSpec.parse(mesh) == MeshSpec(2, 4)


def test_mesh_too_large_raises(host_devices):
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        MeshSpec(data=1024).build()


# -- filter-dimension program padding ---------------------------------------


def test_pad_program_for_filter(uniform_prog):
    layers, in_pad, final = pad_program_for_filter(uniform_prog, 4,
                                                   pad_input=True)
    assert final == 6 and in_pad == 2          # 6 -> 8 (mult of 4)
    for instr in layers:
        assert instr.weights.shape[2:] == (8, 8)
        assert instr.thresholds.t_lo.shape == (8,)
        assert bool(np.asarray(instr.thresholds.is_const)[6:].all())
        assert not np.asarray(instr.weights)[..., 6:].any()
    # without pad_input, layer 0 keeps its true input channel count
    layers, in_pad, _ = pad_program_for_filter(uniform_prog, 4)
    assert in_pad == 0 and layers[0].weights.shape[2] == 6


# -- bit-exactness vs the ref oracle ----------------------------------------


@pytest.mark.parametrize("batch", [1, 3, 5, 8])
def test_data_parallel_padding_bit_exact(host_devices, uniform_prog,
                                         uniform_oracle, batch):
    x, y_ref = uniform_oracle
    pipe = CutiePipeline(uniform_prog, backend="ref", mesh="data:4")
    y = np.asarray(pipe.run(x[:batch]))
    assert y.shape == y_ref[:batch].shape
    assert (y == y_ref[:batch]).all()


@pytest.mark.parametrize("spec", ["filter:4", "data:2,filter:2",
                                  "filter:3"])
@pytest.mark.parametrize("packed", [True, False])
def test_filter_sharding_nondividing_channels(host_devices, uniform_prog,
                                              uniform_oracle, spec, packed):
    # 6 output channels never divide 4 (or 3 evenly at every layer edge),
    # so the pack/unpack boundary sees non-multiple-of-5 shard sizes too
    x, y_ref = uniform_oracle
    pipe = CutiePipeline(uniform_prog, backend="ref", mesh=spec,
                         packed_collectives=packed)
    assert (np.asarray(pipe.run(x)) == y_ref).all()


def test_packed_collectives_cut_traffic(host_devices, uniform_prog):
    # the wire format is the one thing packed_collectives changes: same
    # bits out, ~5x fewer bytes exchanged between devices
    pipe = CutiePipeline(uniform_prog, backend="ref", mesh="filter:2")
    traffic = pipe._sharded.collective_bytes((8, 8, 8, 6))
    assert traffic["on_wire"] == traffic["packed"]
    assert 4.5 < traffic["dense"] / traffic["packed"] <= 5.0
    dense = CutiePipeline(uniform_prog, backend="ref", mesh="filter:2",
                          packed_collectives=False)
    assert dense._sharded.collective_bytes(
        (8, 8, 8, 6))["on_wire"] == traffic["dense"]


@pytest.mark.parametrize("backend", ["ref", "pallas", "packed"])
def test_all_backends_sharded(host_devices, uniform_prog, uniform_oracle,
                              backend):
    x, y_ref = uniform_oracle
    pipe = CutiePipeline(uniform_prog, backend=backend,
                         mesh="data:2,filter:2")
    assert (np.asarray(pipe.run(x[:5])) == y_ref[:5]).all()


def test_nonuniform_program_sharded(host_devices, rng):
    # pools + differing cin: unrolled (non-scan) sharded path
    prog = _program(5, 7, 3, seed=1, pools=[None, ("max", 2), ("avg", 2)])
    x = rng.integers(-1, 2, (3, 12, 12, 5)).astype(np.int8)
    y_ref = np.asarray(CutiePipeline(prog, backend="ref").run(x))
    pipe = CutiePipeline(prog, backend="ref", mesh="data:2,filter:4")
    assert not pipe.scannable
    assert (np.asarray(pipe.run(x)) == y_ref).all()


def test_scan_survives_filter_padding(host_devices, uniform_prog):
    # uniform trunk stays a lax.scan even when filter padding grows C
    pipe = CutiePipeline(uniform_prog, backend="ref", mesh="filter:4")
    assert pipe.scannable


def test_tracer_unsupported_on_mesh(host_devices, uniform_prog, rng):
    from repro.pipeline import StatsTracer

    pipe = CutiePipeline(uniform_prog, backend="ref", mesh="data:2")
    x = rng.integers(-1, 2, (2, 8, 8, 6)).astype(np.int8)
    with pytest.raises(NotImplementedError, match="tracer"):
        pipe.run(x, tracer=StatsTracer())


# -- serving through a meshed executor --------------------------------------


def test_engine_submit_result_meshed(host_devices, uniform_prog,
                                     uniform_oracle):
    x, y_ref = uniform_oracle
    eng = CutieEngine("fcfs")
    ex = eng.register("m", uniform_prog, backend="ref", mesh="data:4",
                      buckets=(1, 2, 6))
    # buckets round up to multiples of the data-parallel degree
    assert ex.buckets == (4, 8)
    handles = [eng.submit(x[i], model="m") for i in range(5)]
    for i, h in enumerate(handles):
        assert (np.asarray(h.result()) == y_ref[i]).all()
    stats = eng.stats()
    assert stats["sharding"]["m"] == {"data": 4, "filter": 1, "layer": 1,
                                      "devices": 4}
    occ = stats["per_device_occupancy"]["m"]
    assert len(occ) == 4 and occ[0] == 1.0
    # padded batches stay multiples of the data degree
    assert all(b["padded"] % 4 == 0 for b in eng.batches)


def test_engine_meshed_matches_unsharded_engine(host_devices, uniform_prog,
                                                uniform_oracle):
    x, _ = uniform_oracle
    plain = CutieEngine("fcfs")
    plain.register("m", uniform_prog, backend="ref")
    meshed = CutieEngine("fcfs")
    meshed.register("m", uniform_prog, backend="ref",
                    mesh=MeshSpec(data=2, filter=2))
    h1 = [plain.submit(x[i], model="m") for i in range(3)]
    h2 = [meshed.submit(x[i], model="m") for i in range(3)]
    for a, b in zip(h1, h2):
        assert (np.asarray(a.result()) == np.asarray(b.result())).all()
