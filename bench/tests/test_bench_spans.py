"""The readers of the engine's own spans, ``queue_wait_p95_ms`` and
``host_ms_per_chunk``: exact values on small synthetic traces with
request records, nothing where the trace or the spans are missing, and
values from the spans a reduced engine writes on the CPU when the
harness drives it under the profiler."""

import glob
import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from bench import trace as tr
from bench.tests import dry

METRICS = Path(__file__).resolve().parents[1] / "metrics"
MS = 1_000_000          # ns per ms
CELL = "qwen3-4b-w8a8-pallas.chat-poisson"


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"reader_{name}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


queue_wait = _reader("queue_wait_p95_ms")
host_per_chunk = _reader("host_ms_per_chunk")


def _ev(name, start_ms, dur_ms, where="py"):
    return {"kind": "host", "where": where, "name": name,
            "start_ns": start_ms * MS, "dur_ns": dur_ms * MS}


def _window(*events):
    # the window opens at 1,000 ms on the trace's clock and lasts 10 s
    return tr.from_events([_ev("bench.window", 1_000, 10_000), *events])


def _req(t_first, arrival=None):
    rec = {"t_first": t_first, "window": arrival is not None}
    if arrival is not None:
        rec["arrival"] = arrival
    return rec


def _queue_ctx():
    """Three prefills end inside the window (at 2.1, 4.5 and 8.0 s from
    its opening); the first is a pre-roll request's."""
    events = [_ev("engine.prefill", 2_000, 1_100),      # ends 3,100 ms
              _ev("engine.prefill", 4_500, 1_000),      # ends 5,500 ms
              _ev("engine.prefill", 8_000, 1_000),      # ends 9,000 ms
              # ended before the window opened: the pre-roll's
              _ev("engine.prefill", 0, 900),
              # another thread's span is not the engine's
              _ev("engine.prefill", 5_000, 10, where="other")]
    reqs = [_req(2.1),                      # pre-roll: not reported
            _req(4.5, arrival=2.0),         # waited 4.5 - 2.0 - 1.0
            _req(8.0, arrival=5.5),         # waited 8.0 - 5.5 - 1.0
            _req(None, arrival=9.5),        # no first token yet
            _req(-0.5)]                     # first token before the window
    return {"trace": _window(*events), "requests": reqs, "t_close": 10.0}


def test_queue_wait_reads_due_time_to_placement():
    ctx = _queue_ctx()
    want = 1e3 * np.percentile([1.5, 1.5], 95)
    assert queue_wait(ctx) == pytest.approx(want)
    ctx["requests"][2]["arrival"] = 6.5     # waited 0.5 s
    assert queue_wait(ctx) == pytest.approx(
        1e3 * np.percentile([1.5, 0.5], 95))
    assert queue_wait(ctx) == pytest.approx(1450.0)


def test_a_resume_is_not_a_placement():
    ctx = _queue_ctx()
    before = queue_wait(ctx)
    # a resume stamps no first token and is a span of another name
    ctx["trace"] = tr.Trace([*ctx["trace"].events,
                             tr.Event(**_ev("engine.resume", 6_000, 300))])
    assert queue_wait(ctx) == pytest.approx(before)


def test_queue_wait_reads_nothing_when_the_lists_differ():
    ctx = _queue_ctx()
    ctx["requests"].append(_req(9.5, arrival=9.0))
    assert queue_wait(ctx) is None
    ctx = _queue_ctx()
    ctx["trace"] = _window(_ev("engine.step", 1_500, 100))
    assert queue_wait(ctx) is None


def _step_ctx():
    """Two steps in the window: the first admits (a prefill whose wait
    is 400 ms) and decodes (wait 300 ms); the second sleeps 50 ms before
    an arrival and decodes (wait 200 ms)."""
    events = [
        _ev("engine.step", 2_000, 1_000),
        _ev("engine.admit", 2_010, 540),
        _ev("engine.prefill", 2_020, 520),
        _ev("engine.upload", 2_030, 20),
        _ev("engine.wait", 2_100, 400),
        _ev("engine.decode", 2_600, 390),
        _ev("engine.wait", 2_650, 300),
        _ev("engine.step", 4_000, 300),
        _ev("engine.idle", 4_010, 50),
        _ev("engine.decode", 4_070, 220),
        _ev("engine.wait", 4_080, 200),
        # a step cut by the window's close is not counted
        _ev("engine.step", 10_900, 200),
        _ev("engine.decode", 10_950, 100),
    ]
    return {"trace": _window(*events), "requests": [], "t_close": 10.0}


def test_host_time_per_chunk_leaves_out_waits_and_sleeps():
    # (1,000 - 400 - 300) + (300 - 50 - 200) ms over two chunks
    assert host_per_chunk(_step_ctx()) == pytest.approx(175.0)


def test_host_time_reads_nothing_without_its_spans():
    ctx = _step_ctx()
    ctx["trace"] = tr.Trace([e for e in ctx["trace"].events
                             if e.name != "engine.decode"])
    assert host_per_chunk(ctx) is None
    ctx["trace"] = _window()
    assert host_per_chunk(ctx) is None


def test_no_trace_reads_nothing():
    for read in (queue_wait, host_per_chunk):
        assert read({"trace": None, "requests": [], "t_close": 50.0}) \
            is None


def test_both_metrics_are_declared_for_the_chat_cell():
    spec = json.loads((dry.REPO / "BENCHMARK.json").read_text())
    metrics = {m["name"]: m for m in spec["per_layer"]}
    assert metrics["queue_wait_p95_ms"]["moves"] == "ttft_p95_ms"
    assert metrics["host_ms_per_chunk"]["moves"] == "tpot_p95_ms"
    for name in ("queue_wait_p95_ms", "host_ms_per_chunk"):
        m = metrics[name]
        assert (m["unit"], m["better"], m["layer"], m["source"]) == \
            ("ms", "lower", "scheduler", "device_trace")
        assert m["workloads"] == [CELL]
    # appended after the metrics that were there
    assert [m["name"] for m in spec["per_layer"]][-2:] == \
        ["queue_wait_p95_ms", "host_ms_per_chunk"]


def test_the_readers_read_what_the_engine_writes(tmp_path):
    """The harness drives a reduced engine through a tiny cell under the
    profiler (the CPU has no device plane, so the trace is read here
    without one) and both readers find their spans."""
    import jax
    from jax.profiler import TraceAnnotation

    from bench import harness
    root = dry.copy(tmp_path)
    dry.add_tiny(root)
    cell = harness.load_cell(dry.CELL, root)
    stack = harness.prepare(cell, root)
    _, engine = harness.build(jax, stack, 2**33 + 7)
    pre, win = harness.schedules(cell, seed=2**33 + 7, seconds=2.0,
                                 vocab=cell.config["model"]["vocab_size"])
    trace_dir = tmp_path / "trace"
    drove = harness.drive(
        engine, pre, win, cell.mix["warm_s"], 2.0,
        on_open=lambda: jax.profiler.start_trace(str(trace_dir)),
        on_close=jax.profiler.stop_trace, span=TraceAnnotation)
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    t = tr.Trace(tr._read_xplane(path))
    ctx = {"trace": t, "requests": drove["requests"],
           "t_close": drove["t_close"]}
    wait_ms = queue_wait(ctx)
    host_ms = host_per_chunk(ctx)
    assert wait_ms is not None and host_ms is not None
    # one placement span for each first token stamped in the window
    inside = [e for e in t.events if e.name == "engine.prefill"
              and t.lo <= e.end_ns <= t.hi]
    assert len(inside) == sum(r["t_first"] is not None
                              and 0 <= r["t_first"] <= drove["t_close"]
                              for r in drove["requests"])
    assert 0.0 <= wait_ms <= 1e3 * drove["t_close"]
    steps = [e for e in t.events if e.name == "engine.step"
             and t.lo <= e.start_ns and e.end_ns <= t.hi]
    chunks = [e for e in t.events if e.name == "engine.decode"
              and t.lo <= e.start_ns and e.end_ns <= t.hi]
    assert 0.0 < host_ms <= 1e-6 * sum(e.dur_ns for e in steps) / len(chunks)
