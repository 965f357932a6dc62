"""The serve engine's profiler spans (``repro.serve.spans``): one traced
run of a reduced engine, read back from the profiler's ``.xplane.pb``.
The spans nest strictly under ``engine.step``, one ``engine.prefill``
marks each fresh placement and one ``engine.decode`` each decode chunk,
their metadata sums to the engine's counters, and tracing changes no
stream and no compilation.

The profiler is process-global, so this file makes its one traced run
in a module fixture."""

import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config, reduced
from repro.models import model_init
from repro.serve import Engine, ServeConfig, spans

BATCH = 2
PREFILL_LEN = 16
PROMPTS = (5, 9, 7, 12)        # four requests on two slots: two wait


def _submit_all(engine, vocab):
    rng = np.random.default_rng(3)
    return [engine.submit(rng.integers(0, vocab, n), 6) for n in PROMPTS]


def _serve(engine, vocab):
    engine.reset()
    ids = _submit_all(engine, vocab)
    done = engine.run()
    return ([done[i].tokens for i in ids], dict(engine.stats),
            dict(engine.compile_counts))


def _host_spans(trace_dir):
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    out.append({"name": e.name, "line": line.name,
                                "start": e.start_ns,
                                "end": e.start_ns + e.duration_ns,
                                "stats": {k: v for k, v in e.stats}})
    out.sort(key=lambda s: (s["start"], -s["end"]))
    return out


def _parents(events):
    """Each span's innermost enclosing span (``None`` at the top); fails
    where two spans overlap without one holding the other."""
    stack, parents = [], []
    for e in events:
        while stack and stack[-1]["end"] <= e["start"]:
            stack.pop()
        if stack:
            assert e["end"] <= stack[-1]["end"], (stack[-1], e)
        parents.append(stack[-1] if stack else None)
        stack.append(e)
    return parents


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    cfg = reduced(get_config("qwen3-4b")).replace(quant_mode="w8a8_nibble")
    params = model_init(jax.random.PRNGKey(0), cfg)
    scfg = ServeConfig(batch=BATCH, max_len=48, prefill_len=PREFILL_LEN,
                       page_size=8, decode_chunk=4)
    engine = Engine(cfg, params, scfg)
    plain = _serve(engine, cfg.vocab_size)
    trace_dir = tmp_path_factory.mktemp("engine-trace")
    with jax.profiler.trace(str(trace_dir)):
        traced = _serve(engine, cfg.vocab_size)
    return plain, traced, _host_spans(trace_dir)


def test_tracing_changes_no_stream_and_no_compile(runs):
    (streams, _, counts), (t_streams, _, t_counts), _ = runs
    assert t_streams == streams
    assert counts == t_counts == {"prefill": 1, "decode_chunk": 1}


def test_spans_nest_under_the_step(runs):
    _, _, events = runs
    assert len({e["line"] for e in events}) == 1   # the caller's thread
    parents = _parents(events)
    top = {e["name"] for e, p in zip(events, parents) if p is None}
    assert top == {spans.STEP}
    chain = {(p["name"], e["name"]) for e, p in zip(events, parents) if p}
    assert {(spans.STEP, spans.ADMIT), (spans.ADMIT, spans.PREFILL),
            (spans.PREFILL, spans.WAIT), (spans.STEP, spans.DECODE),
            (spans.DECODE, spans.WAIT)} <= chain
    # the stage phases sit only inside a placement or a chunk
    for e, p in zip(events, parents):
        if e["name"] in (spans.UPLOAD, spans.WAIT, spans.READBACK,
                         spans.EMIT):
            assert p["name"] in (spans.PREFILL, spans.DECODE), e


def test_one_prefill_span_per_fresh_request(runs):
    _, (_, stats, _), events = runs
    prefills = [e for e in events if e["name"] == spans.PREFILL]
    assert len(prefills) == len(PROMPTS)
    assert sorted(e["stats"]["tokens"] for e in prefills) == sorted(PROMPTS)
    assert {e["stats"]["rows"] for e in prefills} == {PREFILL_LEN}
    assert len({e["stats"]["req"] for e in prefills}) == len(PROMPTS)
    # two of the four waited for a slot: a decode chunk at least
    waits = sorted(e["stats"]["queue_wait_ms"] for e in prefills)
    assert waits[0] >= 0 and waits[-1] > 0
    decodes = [e for e in events if e["name"] == spans.DECODE]
    assert len(decodes) == stats["decode_chunks"]
    assert all(e["stats"]["steps"] == 4 for e in decodes)
    assert not [e for e in events if e["name"] in (spans.RESUME,
                                                   spans.EVICT)]


def test_counters_are_the_sums_of_the_span_metadata(runs):
    _, (_, stats, _), events = runs
    prefills = [e["stats"] for e in events if e["name"] == spans.PREFILL]
    decodes = [e["stats"] for e in events if e["name"] == spans.DECODE]
    assert stats["prefill_rows"] == sum(s["rows"] for s in prefills) \
        == len(PROMPTS) * PREFILL_LEN
    assert stats["decode_rows"] == sum(s["rows"] for s in decodes)
    # each chunk's active slot-steps are at most its slots times steps;
    # every token after the first was decoded once
    assert all(s["rows"] <= s["active"] * s["steps"] for s in decodes)
    assert stats["decode_rows"] == len(PROMPTS) * (6 - 1)
    assert stats["queue_wait_s"] == pytest.approx(
        sum(s["queue_wait_ms"] for s in prefills) * 1e-3, rel=1e-9)


def test_spans_per_step_are_bounded(runs):
    _, _, events = runs
    parents = _parents(events)
    steps = [e for e in events if e["name"] == spans.STEP]

    def top(e, p):
        while p is not None:
            e, p = p, parents[events.index(p)]
        return e

    per_step = {id(s): 0 for s in steps}
    for e, p in zip(events, parents):
        per_step[id(top(e, p))] += 1
    # the step, its admit and an idle sleep; five phases for each
    # placement (the span, upload, wait, readback, emit) and for the
    # chunk; an eviction per slot
    bound = 3 + 5 * (BATCH + 1) + BATCH
    assert max(per_step.values()) <= bound
