"""The program's ``repro.*`` spans read from a hand-made trace, beside
the device's idle intervals, and the four metrics that read them."""

import os

import pytest
from jax.profiler import ProfileData

from chipbench import harness, program_spans, trace
from chipbench.tests.test_trace import MS, _line, _meta

CELL = "tiny.spans"


def _text(repro=True, second_device=False):
    # device 0: ops at [10, 12), [15, 16), [30, 34) ms; window [5, 35)
    # idle: [5, 10), [12, 15), [16, 30), [34, 35)
    dev = ("planes { id: 1 name: \"/device:TPU:0\"\n"
           + _line(1, "XLA Ops", 0, [(1, 10 * MS, 2 * MS), (1, 15 * MS, MS),
                                     (1, 30 * MS, 4 * MS)])
           + _meta({1: "fusion.1"}) + "}\n")
    if second_device:
        # device 1 busy over [8, 20): no idle inside the first batch
        dev += ("planes { id: 3 name: \"/device:TPU:1\"\n"
                + _line(1, "XLA Ops", 0, [(1, 8 * MS, 12 * MS)])
                + _meta({1: "fusion.1"}) + "}\n")
    names = {1: "chipbench.window", 2: "repro.submit", 3: "repro.validate",
             4: "repro.batch", 5: "repro.cow", 6: "repro.sync"}
    events = [(1, 5 * MS, 30 * MS)]
    if repro:
        events += [
            (2, 3 * MS, 3 * MS),               # submit cut by the window
            (2, 5 * MS + MS // 2, 2 * MS),     # submit [5.5, 7.5)
            (3, 6 * MS, 3 * MS // 2),          # validate [6, 7.5)
            (4, 8 * MS, 12 * MS),              # batch [8, 20)
            (5, 8 * MS, MS),                   # cow [8, 9)
            (6, 12 * MS, 2 * MS),              # sync [12, 14)
            (4, 20 * MS, 16 * MS),             # batch [20, 36), cut
        ]
    host = ("planes { id: 2 name: \"/host:CPU\"\n"
            + _line(1, "python3", 0, sorted(events, key=lambda e: e[1]))
            + _meta(names) + "}\n")
    return dev + host


def _space(**kw):
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(_text(**kw)))


def _run(tmp_path, monkeypatch, **kw):
    """A traced run whose trace lies where the harness writes it."""
    serialized = ProfileData.text_proto_to_serialized_xspace(_text(**kw))
    log_dir = tmp_path / ".chipbench_traces" / CELL / "plugins" / "profile"
    os.makedirs(log_dir / "1")
    (log_dir / "1" / "host.xplane.pb").write_bytes(serialized)
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    cell = harness.Cell(CELL, 1, {}, {}, [], [])
    return harness.Run(cell, None, {}, 0.0, 1.0, [], [],
                       trace=trace.reduce_profile(_space(**kw)))


def test_spans_and_idle_by_innermost_span():
    ps = program_spans.reduce(_space(), trace.reduce_profile(_space()))
    assert ps.window == (5 * MS, 35 * MS)
    assert ps.wholly_in("submit") == [(5 * MS + MS // 2, 7 * MS + MS // 2)]
    assert ps.wholly_in("batch") == [(8 * MS, 20 * MS)]
    # idle [5,10): 1 submit (the cut one, then the one inside it),
    # 1.5 validate, 0.5 none, 1 cow, 1 batch; [12,15): 2 sync, 1 batch;
    # [16,30) and [34,35): batch
    assert ps.idle_by_innermost() == pytest.approx({
        program_spans.NO_SPAN: 0.5e-3, "submit": 1e-3,
        "validate": 1.5e-3, "cow": 1e-3, "sync": 2e-3, "batch": 17e-3})


def test_a_trace_without_program_spans_reads_none(tmp_path, monkeypatch):
    assert program_spans.reduce(
        _space(repro=False), trace.reduce_profile(_space(repro=False))) \
        is None
    run = _run(tmp_path, monkeypatch, repro=False)
    for name in ("submit_ms.images", "step_idle_ms.images",
                 "step_idle_ms.tokens", "sync_idle_share.tokens"):
        assert harness.metric_reader(name)(run) is None


def test_an_untraced_run_reads_none():
    cell = harness.Cell(CELL, 1, {}, {}, [], [])
    run = harness.Run(cell, None, {}, 0.0, 1.0, [], [])
    assert program_spans.of(run) is None
    assert harness.metric_reader("submit_ms.images")(run) is None


def test_the_four_readers(tmp_path, monkeypatch):
    run = _run(tmp_path, monkeypatch)
    read = {name: harness.metric_reader(name)(run) for name in (
        "submit_ms.images", "step_idle_ms.images", "step_idle_ms.tokens",
        "sync_idle_share.tokens")}
    assert read["submit_ms.images"] == pytest.approx(2.0)
    # idle in batch [8, 20): [8, 10) + [12, 15) + [16, 20) = 9 ms
    assert read["step_idle_ms.images"] == pytest.approx(9.0)
    assert read["step_idle_ms.tokens"] == pytest.approx(9.0)
    # cow 1 + sync 2 of the batches' 9 + 10 + 1 ms idle
    assert read["sync_idle_share.tokens"] == pytest.approx(15.0)
    # read once, kept on the run
    os.remove(trace.find_xplane(os.path.join(
        str(tmp_path), ".chipbench_traces", CELL)))
    assert harness.metric_reader("submit_ms.images")(run) \
        == pytest.approx(2.0)


def test_every_device_counts(tmp_path, monkeypatch):
    run = _run(tmp_path, monkeypatch, second_device=True)
    ps = program_spans.of(run)
    assert len(ps.idle) == 2
    assert ps.idle[1] == [(5 * MS, 8 * MS), (20 * MS, 35 * MS)]
    # device 0 idles 9 ms in the batch, device 1 none: the mean
    assert harness.metric_reader("step_idle_ms.images")(run) \
        == pytest.approx(4.5)
