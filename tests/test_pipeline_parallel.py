"""Pipeline-parallel layer sharding + packed-trit collectives.

Pins the PR-9 tentpole properties of `repro.launch.cutie_mesh`:

* ``"layer"`` mesh axis: trunk stages assigned one per device
  (`repro.compiler.trunks.plan_stages`), microbatched activations
  streamed through a ``ppermute`` ring — bit-identical to single-device
  ``ref`` across layer/data mesh shapes, packed and dense wire formats,
* microbatch ordering through the ring (per-sample outputs land back in
  submission order, including batches that do not divide the
  microbatch count),
* stage planning errors name the offending layer/constraint instead of
  silently running a wrong pipeline,
* serving integration: bucket rounding to the pipeline's batch quantum
  and per-stage occupancy / bubble fraction in ``engine.stats()``.

Host topology comes from ``conftest.py``'s session-wide XLA_FLAGS; the
``host_devices`` fixture skips when it could not be applied.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.compiler import trunks
from repro.core import engine
from repro.launch.cutie_mesh import MeshSpec
from repro.pipeline import CutiePipeline
from repro.serving import CutieEngine


def _uniform_program(c, n_layers, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), n_layers)
    instrs = []
    for k in keys:
        k1, k2 = jax.random.split(k)
        w = jax.random.normal(k1, (3, 3, c, c))
        bn = {"gamma": jax.random.normal(k2, (c,)) + 0.5,
              "beta": jnp.zeros((c,)), "mean": jnp.zeros((c,)),
              "var": jnp.ones((c,))}
        instrs.append(engine.compile_layer(w, bn))
    return engine.CutieProgram(instrs, engine.CutieInstance(n_i=c, n_o=c))


@pytest.fixture(scope="module")
def trunk8():
    return _uniform_program(6, 8)


@pytest.fixture(scope="module")
def trunk8_oracle(trunk8, rng):
    x = rng.integers(-1, 2, (8, 8, 8, 6)).astype(np.int8)
    y = np.asarray(CutiePipeline(trunk8, backend="ref").run(x))
    return x, y


# -- mesh spec: the layer axis ----------------------------------------------


def test_meshspec_layer_axis():
    assert MeshSpec.parse("layer:4") == MeshSpec(layer=4)
    assert MeshSpec.parse("data:2,layer:2") == MeshSpec(data=2, layer=2)
    assert MeshSpec.parse({"layer": 8}) == MeshSpec(layer=8)
    assert MeshSpec.parse((2, 1, 4)) == MeshSpec(2, 1, 4)
    assert MeshSpec(data=2, layer=4).n_devices == 8
    assert str(MeshSpec(layer=4)) == "data:1,filter:1,layer:4"
    with pytest.raises(NotImplementedError, match="do not compose"):
        MeshSpec(filter=2, layer=2)


def test_meshspec_layer_from_mesh(host_devices):
    mesh = jax.make_mesh((2, 1, 4), ("data", "filter", "layer"),
                         axis_types=(AxisType.Auto,) * 3)
    assert MeshSpec.parse(mesh) == MeshSpec(data=2, layer=4)


# -- stage planning ----------------------------------------------------------


def test_plan_stages(trunk8):
    stages = trunks.plan_stages(trunk8, (1, 8, 8, 6), 4)
    assert [(s.start, s.stop) for s in stages] == [
        (0, 2), (2, 4), (4, 6), (6, 8)]
    # each 2-layer stage is itself a fusible trunk on its device
    assert all(s.fused and s.vmem_bytes > 0 for s in stages)


def test_plan_stages_rejects_nondividing(trunk8):
    with pytest.raises(ValueError, match="do not split"):
        trunks.plan_stages(trunk8, (1, 8, 8, 6), 3)


def test_plan_stages_rejects_nonuniform():
    # a pooled layer changes the activation shape mid-ring
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    instrs = []
    for i, k in enumerate(keys):
        w = jax.random.normal(k, (3, 3, 6, 6))
        bn = {"gamma": jnp.ones((6,)), "beta": jnp.zeros((6,)),
              "mean": jnp.zeros((6,)), "var": jnp.ones((6,))}
        instrs.append(engine.compile_layer(
            w, bn, pool=("max", 2) if i == 1 else None))
    prog = engine.CutieProgram(instrs, engine.CutieInstance(n_i=6, n_o=6))
    with pytest.raises(ValueError, match="layer 1.*pool"):
        trunks.plan_stages(prog, (1, 8, 8, 6), 2)


# -- bit-exactness vs the single-device ref oracle ---------------------------


@pytest.mark.parametrize("spec", ["layer:2", "layer:4", "layer:8",
                                  "data:2,layer:2"])
def test_layer_sharding_bit_exact(host_devices, trunk8, trunk8_oracle,
                                  spec):
    x, y_ref = trunk8_oracle
    pipe = CutiePipeline(trunk8, backend="ref", mesh=spec)
    assert (np.asarray(pipe.run(x)) == y_ref).all()


def test_layer_sharding_dense_wire_bit_exact(host_devices, trunk8,
                                             trunk8_oracle):
    x, y_ref = trunk8_oracle
    pipe = CutiePipeline(trunk8, backend="ref", mesh="layer:4",
                         packed_collectives=False)
    assert (np.asarray(pipe.run(x)) == y_ref).all()


def test_microbatch_ordering_through_ring(host_devices, trunk8,
                                          trunk8_oracle):
    # every sample is distinct, the batch (7) does not divide the
    # microbatch count (3), and the padded tail is cropped — outputs
    # must come back in submission order, not ring-arrival order
    x, y_ref = trunk8_oracle
    pipe = CutiePipeline(trunk8, backend="ref", mesh="layer:4",
                         microbatches=3)
    y = np.asarray(pipe.run(x[:7]))
    assert y.shape == y_ref[:7].shape
    for i in range(7):
        assert (y[i] == y_ref[i]).all(), f"sample {i} misrouted"


@pytest.mark.parametrize("backend", ["pallas", "packed"])
def test_layer_sharding_kernel_backends(host_devices, trunk8,
                                        trunk8_oracle, backend):
    x, y_ref = trunk8_oracle
    pipe = CutiePipeline(trunk8, backend=backend, mesh="layer:2",
                         microbatches=2)
    assert (np.asarray(pipe.run(x[:4])) == y_ref[:4]).all()


def test_layer_sharding_rejects_nonuniform_program(host_devices, rng):
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    instrs = []
    for i, k in enumerate(keys):
        w = jax.random.normal(k, (3, 3, 6 if i == 0 else 4, 4))
        bn = {"gamma": jnp.ones((4,)), "beta": jnp.zeros((4,)),
              "mean": jnp.zeros((4,)), "var": jnp.ones((4,))}
        instrs.append(engine.compile_layer(w, bn))
    prog = engine.CutieProgram(instrs, engine.CutieInstance(n_i=6, n_o=4))
    with pytest.raises(ValueError, match="uniform trunk"):
        CutiePipeline(prog, backend="ref", mesh="layer:2")


# -- execution plan ----------------------------------------------------------


def test_execution_plan_pipeline_mode(host_devices, trunk8):
    pipe = CutiePipeline(trunk8, backend="ref", mesh="layer:4",
                         microbatches=8)
    plan = pipe.execution_plan()
    assert plan["mode"] == "sharded-pipeline"
    assert plan["collectives"] == "packed"
    assert plan["pipeline"]["stages"] == 4
    assert plan["pipeline"]["microbatches"] == 8
    assert plan["pipeline"]["bubble_fraction"] == pytest.approx(3 / 11)
    assert plan["pipeline"]["per_stage_occupancy"] == [8 / 11] * 4


def test_execution_plan_mesh_names_packed_fallback(host_devices, trunk8):
    with pytest.warns(UserWarning, match="packed"):
        pipe = CutiePipeline(trunk8, backend="fused", mesh="data:2")
    plan = pipe.execution_plan()
    assert plan["fallback"] == "mesh"
    assert plan["collectives"] == "packed"
    assert "packed" in plan["reason"]


# -- serving through a pipelined executor ------------------------------------


def test_engine_layer_sharded(host_devices, trunk8, trunk8_oracle):
    x, y_ref = trunk8_oracle
    eng = CutieEngine("fcfs")
    ex = eng.register("m", trunk8, backend="ref",
                      mesh=MeshSpec(layer=4), buckets=(1, 4))
    # buckets round to the batch quantum: data(1) * microbatches(8)
    assert ex.buckets == (8,)
    handles = [eng.submit(x[i], model="m") for i in range(5)]
    for i, h in enumerate(handles):
        assert (np.asarray(h.result()) == y_ref[i]).all()
    stats = eng.stats()
    shard = stats["sharding"]["m"]
    assert shard["layer"] == 4 and shard["devices"] == 4
    sched = shard["pipeline"]
    assert sched["stages"] == 4 and sched["microbatches"] == 8
    assert 0.0 < sched["bubble_fraction"] < 1.0
    assert len(sched["per_stage_occupancy"]) == 4
