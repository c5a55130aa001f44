"""`repro.obs` spans in the profiler's trace, and the device-read count.

Every ``TraceRecorder.span`` also opens a ``jax.profiler``
``TraceAnnotation`` named ``repro.<name>``: an engine serving a small CNN
and a small paged LLM inside ``jax.profiler.trace`` leaves each of the
engine's and executors' spans in the ``.xplane.pb``, nested as the code
nests them, whether or not the engine's JSON recorder is on.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro.configs as configs
from repro import obs
from repro.core import engine
from repro.models import transformer as TF
from repro.models.config import reduce_for_smoke
from repro.obs import TraceRecorder, validate_trace
from repro.pipeline import CutiePipeline
from repro.serving import CutieEngine, LLMExecutor, ServerConfig

CNN_SPANS = {"submit", "validate", "step", "schedule", "batch", "pad",
             "put", "run", "readback", "head"}
LLM_SPANS = {"step", "schedule", "batch", "prefill", "decode", "cow",
             "tables", "dispatch", "sample", "sync", "emit"}


def _profiled(tmp_path, fn):
    """Run ``fn`` under the profiler; the ``repro.*`` spans it left,
    name (no prefix) -> [(start, end)] ns."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        fn()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    spans: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    s = int(e.start_ns)
                    spans.setdefault(e.name[len("repro."):], []).append(
                        (s, s + int(e.duration_ns)))
    return spans


def _inside(spans, inner, outer):
    """Every ``inner`` span lies within some ``outer`` span."""
    assert spans[inner], inner
    for s, e in spans[inner]:
        assert any(a <= s and e <= b for a, b in spans[outer]), (inner,
                                                                  outer)


def _cnn_engine(trace=False, buckets=(2,)):
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    layers = []
    for k in keys:
        k1, k2 = jax.random.split(k)
        bn = {"gamma": jax.random.normal(k2, (8,)) + 0.5,
              "beta": jnp.zeros((8,)), "mean": jnp.zeros((8,)),
              "var": jnp.ones((8,))}
        layers.append(engine.compile_layer(
            jax.random.normal(k1, (3, 3, 8, 8)), bn))
    prog = engine.CutieProgram(layers, engine.CutieInstance(n_i=8, n_o=8))
    pipe = CutiePipeline(prog, backend="ref")
    return pipe.engine("fcfs", buckets=buckets, trace=trace,
                       head=lambda f: int(f.sum()))


def _images(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(-1, 2, (8, 8, 8)).astype(np.int8)
            for _ in range(n)]


def _llm_engine(trace=False):
    cfg = reduce_for_smoke(configs.get("llama3_2_1b")).replace(n_layers=1)
    params = TF.init_params(cfg, jax.random.PRNGKey(0))
    scfg = ServerConfig(paged=True, n_slots=2, max_new_tokens=3,
                        max_len=32, block_size=8)
    eng = CutieEngine("fcfs", trace=trace)
    eng.register("llm", LLMExecutor(params, cfg, scfg))
    return eng


PROMPTS = [np.arange(5) + 3, np.arange(9) + 11, np.arange(7) + 40]


def test_cnn_engine_spans_reach_the_profiler(tmp_path):
    eng = _cnn_engine()
    eng.submit(_images(1)[0])
    eng.run()                            # compile outside the profile

    def serve():
        for img in _images(3, seed=1):
            eng.submit(img)
        eng.run()

    spans = _profiled(tmp_path, serve)
    assert CNN_SPANS <= set(spans)
    assert len(spans["submit"]) == len(spans["validate"]) == 3
    _inside(spans, "validate", "submit")
    for name in ("schedule", "batch"):
        _inside(spans, name, "step")
    for name in ("pad", "put", "run", "readback", "head"):
        _inside(spans, name, "batch")
    # the profiler saw them, the engine's JSON recorder stayed off
    assert eng.trace_export()["traceEvents"] == []


def test_llm_executor_spans_reach_the_profiler(tmp_path):
    eng = _llm_engine()
    eng.submit(PROMPTS[0])
    eng.run()

    def serve():
        for p in PROMPTS:
            eng.submit(p)
        eng.run()

    spans = _profiled(tmp_path, serve)
    assert LLM_SPANS <= set(spans)
    assert len(spans["prefill"]) == len(PROMPTS)
    for name in ("prefill", "decode", "emit"):
        _inside(spans, name, "batch")
    for name in ("cow", "tables", "dispatch", "sample", "sync"):
        _inside(spans, name, "decode")
    _inside(spans, "batch", "step")
    assert eng.trace_export()["traceEvents"] == []


def test_standalone_executor_spans_reach_the_profiler(tmp_path):
    """An executor no engine bound writes to ``repro.obs.NULL``: no JSON
    events, but its spans are in the profile."""
    ex = _cnn_engine().registry["default"]
    ex.bind_obs(obs.NULL)

    class _Req:
        def __init__(self, uid, value):
            self.uid, self.value = uid, value

    reqs = [_Req(i, ex.validate(img)) for i, img in enumerate(_images(2))]
    ex.execute(reqs)
    spans = _profiled(tmp_path, lambda: ex.execute(reqs))
    assert {"pad", "put", "run", "readback", "head"} <= set(spans)
    assert obs.NULL.trace.events == []


def test_json_trace_of_the_new_spans_validates():
    eng = _cnn_engine(trace=True)
    for img in _images(3):
        eng.submit(img)
    eng.run()
    trace = eng.trace_export()
    validate_trace(trace)
    names = {e["name"] for e in trace["traceEvents"]}
    assert CNN_SPANS <= names
    ends = [e for e in trace["traceEvents"]
            if e["name"] == "batch" and e["ph"] == "E"]
    assert ends and all(e["args"]["padded"] == 2 for e in ends)


def test_json_buffer_stays_empty_with_trace_off_and_no_profiler():
    cnn, llm = _cnn_engine(), _llm_engine()
    for img in _images(3):
        cnn.submit(img)
    cnn.run()
    for p in PROMPTS:
        llm.submit(p)
    llm.run()
    assert cnn.trace_export()["traceEvents"] == []
    assert llm.trace_export()["traceEvents"] == []


def test_disabled_recorder_reads_no_clock():
    reads = []

    def clock():
        reads.append(1)
        return 0.0

    rec = TraceRecorder(enabled=False, clock=clock)
    n = len(reads)
    rec.begin("a")
    rec.end("a")
    rec.instant("b")
    rec.counter("c", {"x": 1})
    rec.thread_name(1, "t")
    with rec.span("d", live=1) as end:
        end["padded"] = 2
    assert len(reads) == n
    assert rec.events == []


def _reads(eng, site):
    return eng.obs.metrics.counter("device_reads_total").value(
        model="llm", site=site)


def test_device_reads_counted_per_live_slot_and_prompt():
    """Per decode step no ``cow`` read (positions live on the host) and
    one ``sync`` read of every slot's sampled token when any slot is
    live, and one ``prefill`` read per admitted prompt."""
    eng = _llm_engine()
    ex = eng.registry["llm"]
    handles = [eng.submit(p) for p in PROMPTS]
    steps = 0
    while eng.busy():
        before = {s: _reads(eng, s) for s in ("cow", "sync", "prefill")}
        resident = sum(r is not None for r in ex.slots)
        waiting = [h for h in handles if h.request.schedule_t is None]
        eng.step()
        admitted = sum(h.request.schedule_t is not None for h in waiting)
        live = resident + admitted
        assert _reads(eng, "prefill") - before["prefill"] == admitted
        assert _reads(eng, "cow") - before["cow"] == 0
        assert _reads(eng, "sync") - before["sync"] == (live > 0)
        steps += 1
    assert _reads(eng, "prefill") == len(PROMPTS)
    assert steps > len(PROMPTS)


@pytest.mark.parametrize("site", ["cow", "sync", "prefill"])
def test_device_reads_carry_the_model_label(site):
    eng = _llm_engine()
    eng.submit(PROMPTS[0])
    eng.run()
    snap = eng.metrics_snapshot()["device_reads_total"]["series"]
    key = f'{{model="llm",site="{site}"}}'
    if site == "cow":
        # positions are read on the host: the series stays absent or 0
        assert snap.get(key, 0) == 0
    else:
        assert snap[key] > 0
