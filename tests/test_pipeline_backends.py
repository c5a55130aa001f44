"""Unified pipeline API: backend equivalence, tracers, serving, shims.

The load-bearing property of `repro.pipeline`: ONE compiled CutieProgram
runs through every registered backend (`ref`, `pallas` in interpret mode,
`packed`) with bit-identical trit outputs and identical Tracer stats —
on both the scanned (uniform layer FIFO) and unrolled (mixed
stride/pool/channel) execution paths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.pipeline import (CutiePipeline, StatsTracer, SwitchingTracer,
                            available_backends, get_backend, program_shapes)

BACKENDS = sorted(available_backends())


def _rand_layer(key, cin, cout, *, pool=None, stride=(1, 1), padding=True):
    k1, k2 = jax.random.split(key)
    w = jax.random.normal(k1, (3, 3, cin, cout))
    bn = {"gamma": jax.random.normal(k2, (cout,)) + 0.5,
          "beta": jnp.zeros((cout,)), "mean": jnp.zeros((cout,)),
          "var": jnp.ones((cout,))}
    return engine.compile_layer(w, bn, pool=pool, stride=stride,
                                padding=padding)


def _uniform_program(c=8, depth=3, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), depth)
    return engine.CutieProgram([_rand_layer(k, c, c) for k in keys],
                               engine.CutieInstance(n_i=c, n_o=c))


def _mixed_program(seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    layers = [
        _rand_layer(keys[0], 8, 16),
        _rand_layer(keys[1], 16, 16, pool=("max", 2)),
        _rand_layer(keys[2], 16, 8, stride=(2, 2)),
        _rand_layer(keys[3], 8, 8, pool=("avg", 2)),
    ]
    return engine.CutieProgram(layers, engine.CutieInstance(n_i=16, n_o=16))


def _trits(key, shape):
    return jax.random.randint(key, shape, -1, 2).astype(jnp.int8)


# ---------------------------------------------------------------------------
# backend equivalence (the acceptance property)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("prog_kind", ["uniform", "mixed"])
def test_backend_bit_identical_and_same_stats(backend, prog_kind):
    prog = _uniform_program() if prog_kind == "uniform" else _mixed_program()
    x = _trits(jax.random.PRNGKey(42), (2, 8, 8, 8))

    ref_pipe = CutiePipeline(prog, backend="ref")
    y_ref, rows_ref = ref_pipe.run(x, tracer=StatsTracer())

    pipe = CutiePipeline(prog, backend=backend)
    y, rows = pipe.run(x, tracer=StatsTracer())

    assert np.array_equal(np.asarray(y), np.asarray(y_ref))
    assert set(np.unique(np.asarray(y))) <= {-1, 0, 1}
    assert rows == rows_ref
    # scan engages exactly on the uniform layer FIFO
    assert pipe.scannable == (prog_kind == "uniform")


@pytest.mark.parametrize("backend", BACKENDS)
def test_switching_tracer_identical_across_backends(backend):
    prog = _uniform_program(seed=3)
    x = _trits(jax.random.PRNGKey(7), (1, 8, 8, 8))
    _, ref_rows = CutiePipeline(prog, backend="ref").run(
        x, tracer=SwitchingTracer())
    _, rows = CutiePipeline(prog, backend=backend).run(
        x, tracer=SwitchingTracer())
    assert rows == ref_rows
    for r in ref_rows:
        assert 0.0 <= r["act_toggle"] <= 1.0
        assert 0.0 < r["weight_density"] <= 1.0
        assert r["ops"] > 0


def test_scan_matches_unrolled():
    prog = _uniform_program(seed=5)
    x = _trits(jax.random.PRNGKey(9), (2, 8, 8, 8))
    y_scan, rows_scan = CutiePipeline(prog, scan=True).run(
        x, tracer=StatsTracer())
    y_unr, rows_unr = CutiePipeline(prog, scan=False).run(
        x, tracer=StatsTracer())
    assert np.array_equal(np.asarray(y_scan), np.asarray(y_unr))
    assert rows_scan == rows_unr


# ---------------------------------------------------------------------------
# API surface
# ---------------------------------------------------------------------------


def test_compile_classmethod_and_shapes():
    key = jax.random.PRNGKey(0)
    c = 8
    bn = {"gamma": jnp.ones((c,)), "beta": jnp.zeros((c,)),
          "mean": jnp.zeros((c,)), "var": jnp.ones((c,))}
    pipe = CutiePipeline.compile(
        [(jax.random.normal(key, (3, 3, c, c)), bn),
         (jax.random.normal(key, (3, 3, c, c)), bn, {"pool": ("max", 2)})],
        instance=engine.CutieInstance(n_i=c, n_o=c))
    shapes = pipe.shapes((4, 8, 8, c))
    assert shapes == [(4, 8, 8, c), (4, 8, 8, c), (4, 4, 4, c)]
    y = pipe.run(_trits(key, (4, 8, 8, c)))
    assert y.shape == shapes[-1]
    assert program_shapes(pipe.program, (4, 8, 8, c)) == shapes


def test_get_backend_resolution():
    assert get_backend("ref").name == "ref"
    assert get_backend("pallas_interpret").name == "pallas"
    assert get_backend(get_backend("packed")).name == "packed"
    with pytest.raises(ValueError, match="unknown backend"):
        get_backend("mps")


def test_run_rejects_non_nhwc():
    pipe = CutiePipeline(_uniform_program())
    with pytest.raises(ValueError, match="N, H, W, C"):
        pipe.run(jnp.zeros((8, 8, 8), jnp.int8))


def test_measure_through_tracer_path():
    prog = _uniform_program(seed=11)
    x = _trits(jax.random.PRNGKey(1), (1, 8, 8, 8))
    en = CutiePipeline(prog).measure(x)
    assert en["avg_tops_w"] > 0
    assert len(en["layers"]) == len(prog.layers)
    assert np.array_equal(np.asarray(en["final"]),
                          np.asarray(CutiePipeline(prog).run(x)))
    # energy.model.program_energy is the same path
    from repro.energy import model as E
    en2 = E.program_energy(prog, x)
    assert en2["avg_tops_w"] == pytest.approx(en["avg_tops_w"])


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def test_engine_continuous_batching():
    prog = _uniform_program(seed=13)
    pipe = CutiePipeline(prog)
    eng = pipe.engine(buckets=(3,))

    rng = np.random.default_rng(0)
    imgs = [rng.integers(-1, 2, size=(8, 8, 8)).astype(np.int8)
            for _ in range(7)]
    uids = [eng.submit(im).uid for im in imgs]
    results = eng.run()

    assert sorted(results) == sorted(uids)
    assert eng.n_batches == 3             # ceil(7 / 3) bucketed batches
    for uid, im in zip(uids, imgs):
        want = np.asarray(pipe.run(jnp.asarray(im[None])))[0]
        assert np.array_equal(results[uid], want)

    with pytest.raises(ValueError, match="does not match serving shape"):
        eng.submit(np.zeros((4, 4, 8), np.int8))


def test_engine_tracer_covers_only_live_requests():
    """A lone request in a padded batch must not have its traced stats
    diluted by empty padding slots."""
    prog = _uniform_program(seed=23)
    pipe = CutiePipeline(prog)
    eng = pipe.engine(tracer=StatsTracer())
    img = np.asarray(_trits(jax.random.PRNGKey(3), (8, 8, 8)))
    eng.submit(img)
    eng.run()
    _, want = pipe.run(jnp.asarray(img[None]), tracer=StatsTracer())
    assert eng.traced() == [want]


def test_layer_ops_agrees_with_inferred_shape():
    """Padded strided conv on odd dims: ops must use the real (ceil)
    output extent, the one program_shapes reports."""
    from repro.pipeline import layer_out_shape

    instr = _rand_layer(jax.random.PRNGKey(29), 8, 8, stride=(2, 2))
    out_shape = layer_out_shape(instr, (1, 9, 9, 8))
    assert out_shape == (1, 5, 5, 8)
    assert engine.layer_ops(instr, (1, 9, 9, 8)) == 2 * 5 * 5 * 3 * 3 * 8 * 8


def test_engine_head_and_late_submit():
    prog = _uniform_program(seed=17)
    pipe = CutiePipeline(prog)
    eng = pipe.engine(head=lambda feats: int(feats.sum()))
    first = eng.submit(np.zeros((8, 8, 8), np.int8)).uid
    assert eng.step()
    late = eng.submit(np.ones((8, 8, 8), np.int8)).uid
    results = eng.run()
    assert set(results) == {first, late}
    assert all(isinstance(v, int) for v in results.values())


# ---------------------------------------------------------------------------
# legacy shims
# ---------------------------------------------------------------------------


def test_engine_run_program_shim_matches_pipeline():
    prog = _mixed_program(seed=19)
    x = _trits(jax.random.PRNGKey(2), (2, 8, 8, 8))
    with pytest.warns(DeprecationWarning, match="CutiePipeline"):
        y_old, stats_old = engine.run_program(prog, x, collect_stats=True)
    y_new, stats_new = CutiePipeline(prog, backend="ref").run(
        x, tracer=StatsTracer())
    assert np.array_equal(np.asarray(y_old), np.asarray(y_new))
    assert stats_old == stats_new


def test_dense_as_conv_derives_from_instance():
    w = jnp.asarray(np.random.default_rng(0).integers(
        -1, 2, size=(40, 4)), jnp.float32)
    inst = engine.CutieInstance(n_i=8, n_o=8)
    wc = engine.dense_as_conv(w, inst)
    assert wc.shape == (3, 3, 8, 4)            # k*k*n_i = 72 >= 40
    x = jnp.asarray(np.random.default_rng(1).integers(
        -1, 2, size=(40,)), jnp.int32)
    xp = jnp.pad(x, (0, 72 - 40)).reshape(1, 3, 3, 8)
    z = engine.conv2d_int(xp, wc, padding=False)
    assert np.array_equal(np.asarray(z).reshape(-1),
                          np.asarray(x @ w.astype(jnp.int32)))
    with pytest.raises(ValueError, match="exceeds OCU buffer"):
        engine.dense_as_conv(jnp.zeros((80, 4)), inst)


def test_device_probe_raises_instead_of_falling_back(monkeypatch):
    """A JAX backend that fails to initialise is an error, not a silent
    switch to interpret mode on the CPU."""
    from repro.kernels import ops
    from repro.pipeline import backends as B

    def broken():
        raise RuntimeError("backend failed to initialise")

    monkeypatch.setattr(jax, "devices", broken)
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    with pytest.raises(RuntimeError, match="failed to initialise"):
        B._on_tpu.__wrapped__()
    with pytest.raises(RuntimeError, match="failed to initialise"):
        ops.default_backend()


def test_chip_smoke_refuses_without_a_tpu():
    """``chip_smoke.py`` under JAX_PLATFORMS=cpu: non-zero exit, a
    message naming the platform it found, and no result line."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=root, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr, r.stderr[-2000:]
    assert '"ok"' not in r.stdout
