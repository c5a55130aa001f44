"""`repro.serving` v2: CutieEngine semantics.

Queue ordering under each scheduler policy, cancellation before/after
admission, multi-model routing + hot-swap, trit-domain submit
validation, bounded jit variants under random load, streaming, and
stats.  (The paged LLM executor has its own suite in
tests/test_paged_state.py.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as core_engine
from repro.pipeline import CutiePipeline, SwitchingTracer
from repro.serving import (CutieEngine, DeadlineScheduler,
                           ModelRegistry, ProgramExecutor, RequestCancelled,
                           RequestStatus, get_scheduler)


def _program(c=8, depth=2, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), depth)
    instrs = []
    for k in keys:
        k1, k2 = jax.random.split(k)
        w = jax.random.normal(k1, (3, 3, c, c))
        bn = {"gamma": jax.random.normal(k2, (c,)) + 0.5,
              "beta": jnp.zeros((c,)), "mean": jnp.zeros((c,)),
              "var": jnp.ones((c,))}
        instrs.append(core_engine.compile_layer(w, bn))
    return core_engine.CutieProgram(instrs,
                                    core_engine.CutieInstance(n_i=c, n_o=c))


def _pipe(c=8, depth=2, seed=0):
    return CutiePipeline(_program(c, depth, seed))


def _img(rng, c=8, hw=8):
    return rng.integers(-1, 2, size=(hw, hw, c)).astype(np.int8)


# ---------------------------------------------------------------------------
# scheduling policies
# ---------------------------------------------------------------------------


def test_fcfs_completes_in_submission_order():
    eng = _pipe().engine("fcfs", buckets=(1,))
    rng = np.random.default_rng(0)
    uids = [eng.submit(_img(rng)).uid for _ in range(4)]
    assert [h.uid for h in eng.stream()] == uids


def test_priority_queue_ordering():
    eng = _pipe().engine("priority", buckets=(1,))
    rng = np.random.default_rng(0)
    low = eng.submit(_img(rng), priority=0)
    high = eng.submit(_img(rng), priority=5)
    mid = eng.submit(_img(rng), priority=1)
    assert [h.uid for h in eng.stream()] == [high.uid, mid.uid, low.uid]


def test_deadline_scheduler_is_edf_with_fcfs_fallback():
    eng = _pipe().engine("deadline", buckets=(1,))
    rng = np.random.default_rng(0)
    loose = eng.submit(_img(rng), deadline=10.0)
    none = eng.submit(_img(rng))                 # no deadline: last
    tight = eng.submit(_img(rng), deadline=0.1)
    assert [h.uid for h in eng.stream()] == [tight.uid, loose.uid, none.uid]
    assert isinstance(eng.scheduler, DeadlineScheduler)


def test_batch_formation_respects_buckets_and_policy():
    """One batch takes the top-k by policy, not submission order."""
    eng = _pipe().engine("priority", buckets=(1, 2))
    rng = np.random.default_rng(0)
    hs = [eng.submit(_img(rng), priority=p) for p in (0, 3, 1, 2)]
    assert eng.step()
    done = {h.uid for h in hs if h.status is RequestStatus.DONE}
    assert done == {hs[1].uid, hs[3].uid}        # the two highest priorities


def test_get_scheduler_rejects_unknown():
    with pytest.raises(ValueError, match="unknown scheduler"):
        get_scheduler("shortest-job-first")


# ---------------------------------------------------------------------------
# cancellation
# ---------------------------------------------------------------------------


def test_cancel_before_admission():
    eng = _pipe().engine("fcfs", buckets=(1,))
    rng = np.random.default_rng(0)
    keep = eng.submit(_img(rng))
    drop = eng.submit(_img(rng))
    assert drop.cancel() is True
    assert drop.status is RequestStatus.CANCELLED
    with pytest.raises(RequestCancelled):
        drop.result()
    results = eng.run()
    assert sorted(results) == [keep.uid]
    assert eng.stats()["n_cancelled"] == 1


def test_cancel_after_completion_and_double_cancel():
    eng = _pipe().engine("fcfs")
    rng = np.random.default_rng(0)
    h = eng.submit(_img(rng))
    eng.run()
    assert h.status is RequestStatus.DONE
    assert h.cancel() is False                   # after admission: no-op
    pending = eng.submit(_img(rng))
    assert pending.cancel() is True
    assert pending.cancel() is False             # already cancelled
    assert eng.cancel(99_999) is False           # unknown uid


# ---------------------------------------------------------------------------
# multi-model routing + hot swap
# ---------------------------------------------------------------------------


def test_multi_model_routing_matches_per_model_pipelines():
    pa, pb = _pipe(c=8, seed=1), _pipe(c=4, seed=2)
    eng = CutieEngine("fcfs")
    eng.register("a", pa, buckets=(1, 2))
    eng.register("b", pb, buckets=(1, 2))
    rng = np.random.default_rng(3)
    ia = [_img(rng, c=8) for _ in range(3)]
    ib = [_img(rng, c=4) for _ in range(3)]
    ha = [eng.submit(im, model="a") for im in ia]
    hb = [eng.submit(im, model="b") for im in ib]
    eng.run()
    wa = np.asarray(pa.run(jnp.asarray(np.stack(ia))))
    wb = np.asarray(pb.run(jnp.asarray(np.stack(ib))))
    for h, w in zip(ha + hb, list(wa) + list(wb)):
        assert np.array_equal(h.request.result, w)
    with pytest.raises(ValueError, match="model= is required"):
        eng.submit(ia[0])


def test_model_hot_swap_serves_new_program():
    old, new = _pipe(seed=5), _pipe(seed=6)
    eng = CutieEngine("fcfs")
    eng.register("m", old)
    rng = np.random.default_rng(0)
    img = _img(rng)
    before = eng.submit(img, model="m").result()
    eng.register("m", new)                       # hot-swap under same name
    after = eng.submit(img, model="m").result()
    assert np.array_equal(before,
                          np.asarray(old.run(jnp.asarray(img[None])))[0])
    assert np.array_equal(after,
                          np.asarray(new.run(jnp.asarray(img[None])))[0])
    assert not np.array_equal(before, after)


def test_hot_swap_with_queued_traffic_executes_on_new_model():
    """The registry promises queued requests run on the swapped-in model."""
    old, new = _pipe(seed=5), _pipe(seed=6)
    eng = CutieEngine("fcfs")
    eng.register("m", old)
    rng = np.random.default_rng(1)
    img = _img(rng)
    h = eng.submit(img, model="m")               # queued against `old`
    eng.register("m", new)                       # swap before any step
    out = h.result()
    assert np.array_equal(out, np.asarray(new.run(jnp.asarray(img[None])))[0])


def test_failed_batch_marks_requests_failed():
    """An executor exception never propagates out of step(): the engine
    retries the request to its budget, then surfaces the error at the
    handle."""
    from repro.serving import FaultPolicy

    eng = CutieEngine("fcfs", policy=FaultPolicy(backoff_base=0.0,
                                                 quarantine_after=None))
    eng.register("m", _pipe(), head=lambda feats: 1 / 0)
    rng = np.random.default_rng(2)
    h = eng.submit(_img(rng), model="m")
    eng.step()                                   # does not raise
    assert h.status is not RequestStatus.DONE
    with pytest.raises(ZeroDivisionError):
        h.result()                               # drives retries, then fails
    assert h.status is RequestStatus.FAILED
    assert h.request.retries == eng.policy.max_retries + 1
    assert eng.stats()["faults"]["n_retries"] == eng.policy.max_retries


def test_evict_completed_bounds_retention():
    eng = _pipe().engine("fcfs")
    rng = np.random.default_rng(4)
    hs = [eng.submit(_img(rng)) for _ in range(3)]
    eng.run()
    assert eng.evict_completed() == 3
    assert eng.run() == {}                       # evicted uids forgotten
    s = eng.stats()
    assert s["n_done"] == 3 and s["n_requests"] == 3   # counters survive
    assert all(h.status is RequestStatus.DONE for h in hs)


def test_registry_accepts_graph_and_program_sources():
    from repro import compiler

    c = 6
    rng = np.random.default_rng(7)
    g = compiler.Graph(in_channels=c, in_hw=(8, 8))
    bn = {"gamma": jnp.ones((c,)), "beta": jnp.zeros((c,)),
          "mean": jnp.zeros((c,)), "var": jnp.ones((c,))}
    g.conv(jnp.asarray(rng.normal(size=(3, 3, c, c)), jnp.float32), bn)
    reg = ModelRegistry()
    ex = reg.register("graph", g, backend="ref")
    assert isinstance(ex, ProgramExecutor)
    reg.register("prog", _program())
    assert reg.names() == ["graph", "prog"]
    with pytest.raises(TypeError, match="cannot register"):
        reg.register("bad", object())
    with pytest.raises(ValueError, match="unknown model"):
        reg["nope"]


def test_compile_result_serve_entry_point():
    from repro import compiler

    c = 6
    rng = np.random.default_rng(9)
    g = compiler.Graph(in_channels=c, in_hw=(8, 8))
    bn = {"gamma": jnp.ones((c,)), "beta": jnp.zeros((c,)),
          "mean": jnp.zeros((c,)), "var": jnp.ones((c,))}
    g.conv(jnp.asarray(rng.normal(size=(3, 3, c, c)), jnp.float32), bn)
    result = compiler.compile_graph(g)
    eng = result.serve("net", scheduler="deadline")
    assert eng.models() == ["net"]
    img = rng.integers(-1, 2, size=(8, 8, c)).astype(np.int8)
    y = eng.submit(img, model="net", deadline=1.0).result()
    want = np.asarray(result.pipeline().run(jnp.asarray(img[None])))[0]
    assert np.array_equal(y, want)


# ---------------------------------------------------------------------------
# submit validation (trit domain, satellite)
# ---------------------------------------------------------------------------


def test_submit_rejects_out_of_domain_trits():
    eng = _pipe().engine()
    with pytest.raises(ValueError, match=r"\{-1, 0, \+1\}"):
        eng.submit(np.full((8, 8, 8), 2, np.int64))
    with pytest.raises(ValueError, match="not int8-coercible"):
        eng.submit(np.full((8, 8, 8), 0.5))
    with pytest.raises(TypeError, match="must be numeric"):
        eng.submit(np.full((8, 8, 8), "x"))
    with pytest.raises(ValueError, match=r"\(H, W, C\)"):
        eng.submit(np.zeros((8, 8), np.int8))
    # exact-integer floats and bools are fine (coerced, not silently cast)
    assert eng.submit(np.zeros((8, 8, 8), np.float32) - 1.0).result() \
        is not None
    assert eng.submit(np.ones((8, 8, 8), bool)).result() is not None


def _trits_of(dtype, values=(-1, 0, 1)):
    return np.resize(np.asarray(values, dtype), (8, 8, 8))


def _bad_last(dtype, value):
    arr = np.zeros((8, 8, 8), dtype)
    arr[-1, -1, -1] = value
    return arr


@pytest.mark.parametrize("make", [
    lambda: _trits_of(np.int8),
    lambda: _trits_of(np.int16),
    lambda: _trits_of(np.int64),
    lambda: _trits_of(np.uint8, (0, 1)),
    lambda: _trits_of(bool, (False, True)),
    lambda: _trits_of(np.float32, (-1.0, 0.0, 1.0, -0.0)),
    lambda: _trits_of(np.float64, (-1.0, 0.0, 1.0, -0.0)),
    lambda: np.zeros((8, 8, 0), np.int8),
    lambda: np.asfortranarray(np.resize(_trits_of(np.int8), (4, 8, 8, 8)))[2],
], ids=["int8", "int16", "int64", "uint8", "bool", "float32", "float64",
        "empty", "strided_view"])
def test_validate_accepts_trits_as_fresh_int8(make):
    arr = make()
    out = ProgramExecutor(_pipe()).validate(arr)
    assert out.dtype == np.int8
    np.testing.assert_array_equal(out, arr)
    assert not np.shares_memory(out, arr)


_DOMAIN = r"values must be in \{-1, 0, \+1\}, got "
_COERCE = "not int8-coercible: non-integral float"


@pytest.mark.parametrize("make, exc, match", [
    (lambda: np.full((8, 8, 8), 2, np.int64), ValueError,
     _DOMAIN + r"\[2\]"),
    (lambda: np.full((8, 8, 8), -2, np.int64), ValueError,
     _DOMAIN + r"\[-2\]"),
    (lambda: np.full((8, 8, 8), -128, np.int8), ValueError,
     _DOMAIN + r"\[-128\]"),
    (lambda: np.full((8, 8, 8), 255, np.uint8), ValueError,
     _DOMAIN + r"\[255\]"),
    (lambda: _trits_of(np.int64, range(-7, 8)), ValueError,
     _DOMAIN + r"\[-7, -6, -5, -4, -3\]$"),
    (lambda: np.full((8, 8, 8), 0.5), ValueError, _COERCE),
    (lambda: np.full((8, 8, 8), np.nan), ValueError, _COERCE),
    (lambda: np.full((8, 8, 8), np.inf), ValueError, _COERCE),
    (lambda: np.full((8, 8, 8), -np.inf), ValueError, _COERCE),
    (lambda: np.full((8, 8, 8), 2.0), ValueError, _DOMAIN + r"\[2\.0\]"),
    (lambda: np.full((8, 8, 8), "x"), TypeError, "must be numeric"),
    (lambda: np.zeros((8, 8, 8), np.complex64), TypeError,
     "must be numeric"),
    (lambda: np.zeros((8, 8, 8), object), TypeError, "must be numeric"),
    (lambda: np.zeros((8, 8), np.int8), ValueError, r"expected \(H, W, C\)"),
    (lambda: np.zeros((1, 8, 8, 8), np.int8), ValueError,
     r"expected \(H, W, C\)"),
    (lambda: _bad_last(np.int8, 2), ValueError, _DOMAIN + r"\[2\]"),
    (lambda: _bad_last(np.float32, -3.0), ValueError,
     _DOMAIN + r"\[-3\.0\]"),
    (lambda: _bad_last(np.float64, 0.5), ValueError, _COERCE),
], ids=["int64_2", "int64_-2", "int8_-128", "uint8_255", "five_listed",
        "half", "nan", "+inf", "-inf", "float_2", "str", "complex",
        "object", "2d", "4d", "int8_last", "float_last", "half_last"])
def test_validate_rejects_non_trits(make, exc, match):
    with pytest.raises(exc, match=match):
        ProgramExecutor(_pipe()).validate(make())


def test_mutating_a_submitted_image_leaves_its_answer():
    eng = _pipe().engine()
    img = _img(np.random.default_rng(3))
    want = eng.submit(img.copy()).result()
    flipped = eng.submit(-img).result()
    assert not np.array_equal(flipped, want)
    req = eng.submit(img)
    img *= -1
    np.testing.assert_array_equal(req.result(), want)


def test_submit_locks_serving_shape():
    eng = _pipe().engine()
    eng.submit(np.zeros((8, 8, 8), np.int8))
    with pytest.raises(ValueError, match="does not match serving shape"):
        eng.submit(np.zeros((4, 4, 8), np.int8))


# ---------------------------------------------------------------------------
# batch bucketing
# ---------------------------------------------------------------------------


def test_jit_variant_count_bounded_by_buckets_under_random_load():
    buckets = (1, 2, 4)
    pipe = _pipe(seed=11)
    eng = CutieEngine("fcfs")
    eng.register("m", pipe, buckets=buckets)
    rng = np.random.default_rng(13)
    for _ in range(12):
        for _ in range(int(rng.integers(1, 5))):
            eng.submit(_img(rng), model="m")
        eng.step()
    eng.run()
    assert pipe.n_jit_variants <= len(buckets)
    assert eng.stats()["jit_variants"]["m"] == pipe.n_jit_variants
    # padded sizes all came from the bucket set, live never exceeded them
    assert {b["padded"] for b in eng.batches} <= set(buckets)
    assert all(b["live"] <= b["padded"] for b in eng.batches)


def test_padded_batches_keep_outputs_bit_identical():
    pipe = _pipe(seed=17)
    eng = CutieEngine("fcfs")
    eng.register("m", pipe, buckets=(4,))       # 3 live + 1 padding slot
    rng = np.random.default_rng(19)
    imgs = [_img(rng) for _ in range(3)]
    hs = [eng.submit(im, model="m") for im in imgs]
    eng.run()
    want = np.asarray(pipe.run(jnp.asarray(np.stack(imgs))))
    for h, w in zip(hs, want):
        assert np.array_equal(h.request.result, w)
    assert eng.batches[0]["live"] == 3 and eng.batches[0]["padded"] == 4


# ---------------------------------------------------------------------------
# stream + stats
# ---------------------------------------------------------------------------


def test_stream_yields_every_completion_once():
    eng = _pipe().engine("fcfs", buckets=(1, 2))
    rng = np.random.default_rng(0)
    uids = {eng.submit(_img(rng)).uid for _ in range(5)}
    seen = [h.uid for h in eng.stream()]
    assert sorted(seen) == sorted(uids)
    assert list(eng.stream()) == []              # drained


def test_stats_latency_queue_depth_and_energy():
    pipe = _pipe(seed=21)
    eng = CutieEngine("deadline")
    eng.register("m", pipe, buckets=(1, 2), tracer=SwitchingTracer())
    rng = np.random.default_rng(23)
    for _ in range(4):
        eng.submit(_img(rng), model="m", deadline=30.0, tag="img")
    eng.run()
    s = eng.stats()
    assert s["n_done"] == 4 and s["n_batches"] == 2
    assert s["latency"]["p99"] is not None and s["latency"]["p99"] > 0
    assert s["latency"]["p50"] <= s["latency"]["p99"]
    assert s["queue_depth"]["max"] >= 2
    assert s["deadline_met_frac"] == 1.0
    assert s["by_tag"]["img"]["n"] == 4
    assert s["energy_uj"] > 0                    # tracer-derived switching
    assert s["batch_occupancy"] == 1.0
    assert len(eng.traced("m")) == 2


# ---------------------------------------------------------------------------
# pipeline serving front door
# ---------------------------------------------------------------------------


def test_pipeline_engine_serves_and_validates():
    pipe = _pipe(seed=25)
    eng = pipe.engine()
    assert eng.scheduler.name == "fcfs"
    rng = np.random.default_rng(0)
    img = _img(rng)
    uid = eng.submit(img).uid
    out = eng.run()
    assert np.array_equal(
        out[uid], np.asarray(pipe.run(jnp.asarray(img[None])))[0])
    with pytest.raises(ValueError, match=r"\{-1, 0, \+1\}"):
        eng.submit(np.full((8, 8, 8), 3, np.int32))
