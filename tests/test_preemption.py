"""Preemptive, incrementally-paged serving: live-token page allocation,
evict-and-resume scheduling (preempted greedy streams must bit-match
uninterrupted ones), overcommitted-pool draining with zero page leaks,
and the serve-layer bugfix regressions (engine-owned compile counter,
explicit truncation, scheduler-stall detection)."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import model_init
from repro.serve import Engine, ServeConfig


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("yi-6b"))
    params = model_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _scfg(**over):
    kw = dict(batch=3, max_len=16, prefill_len=8, decode_chunk=3,
              page_size=4)
    kw.update(over)
    return ServeConfig(**kw)


def _drive(cfg, params, prompts, budgets, scfg, priorities=None):
    engine = Engine(cfg, params, scfg)
    priorities = priorities or [0] * len(prompts)
    ids = [engine.submit(p, n, priority=pr)
           for p, n, pr in zip(prompts, budgets, priorities)]
    done = engine.run()
    return engine, [done[i] for i in ids]


# ---------------------------------------------------------------------------
# Incremental allocation: overcommitted pool, zero leaks, bit-match
# ---------------------------------------------------------------------------

def test_overcommitted_pool_drains_bitmatch(model):
    """The acceptance scenario: a pool sized well below the sum of
    worst-case page counts (4 requests x 4 pages worst case, capacity
    6).  Incremental allocation + preemption must drain every request,
    return every page, keep both compiled programs single, and produce
    the exact token streams of an uncontended engine on a parity pool."""
    cfg, params = model
    rng = np.random.default_rng(0)
    budgets = [8, 8, 8, 8]
    prompts = [jnp.asarray(rng.integers(0, cfg.vocab_size, p), jnp.int32)
               for p in (4, 6, 5, 7)]

    _, want = _drive(cfg, params, prompts, budgets, _scfg())
    engine, got = _drive(cfg, params, prompts, budgets,
                         _scfg(alloc_mode="incremental", num_pages=7))
    assert [r.tokens for r in got] == [r.tokens for r in want]
    assert engine.allocator.in_use == 0            # zero page leaks
    assert engine.allocator.available == engine.allocator.capacity
    assert engine.compile_counts == {"prefill": 1, "decode_chunk": 1}
    # the pool cannot hold two worst-case requests, so finishing all
    # four forcibly exercised eviction and resume
    assert engine.stats["preemptions"] >= 1
    assert sum(r.preemptions for r in got) == engine.stats["preemptions"]
    assert 0.0 < engine.stats["occupancy"] <= 1.0


def test_overcommit_raises_concurrency_vs_reserve(model):
    """Same overcommitted pool, reserve vs incremental bookkeeping:
    booking live tokens instead of worst cases must admit more
    concurrent requests per page of pool (the benchmark's claim)."""
    cfg, params = model
    rng = np.random.default_rng(1)
    prompts = [jnp.asarray(rng.integers(0, cfg.vocab_size, 5), jnp.int32)
               for _ in range(4)]
    # worst case ceil((5+8-1)/4) = 3 pages; capacity 4 fits ONE
    # worst-case booking but two-plus live-token footprints
    res, _ = _drive(cfg, params, prompts, [8] * 4,
                    _scfg(alloc_mode="reserve", num_pages=5))
    inc, _ = _drive(cfg, params, prompts, [8] * 4,
                    _scfg(alloc_mode="incremental", num_pages=5))
    assert res.stats["concurrency"] <= 1.0 + 1e-9
    assert inc.stats["concurrency"] > res.stats["concurrency"]
    assert inc.allocator.in_use == 0 and res.allocator.in_use == 0


def test_incremental_frees_tail_pages_on_early_eos(model):
    """An early-EOS request under incremental allocation never books the
    pages its unreached tail would have needed; reserve mode books the
    worst case up front.  cache_rows records the peak booking."""
    cfg, params = model

    def run_mode(alloc_mode, eos_id=-1):
        engine = Engine(cfg, params, _scfg(
            batch=1, max_len=32, decode_chunk=2, alloc_mode=alloc_mode,
            eos_id=eos_id))
        rid = engine.submit(jnp.asarray([1, 2, 3, 4], jnp.int32), 20)
        return engine, engine.run()[rid]

    _, probe = run_mode("reserve")             # find a token it emits
    eos = probe.tokens[2]
    _, res = run_mode("reserve", eos_id=eos)
    _, inc = run_mode("incremental", eos_id=eos)
    assert res.tokens == inc.tokens            # same (short) stream
    # reserve booked ceil((4+20-1)/4)=6 pages; incremental only the
    # pages its live rows touched before stopping
    assert res.cache_rows == 24
    assert inc.cache_rows < res.cache_rows


# ---------------------------------------------------------------------------
# Preemption: evict-and-resume, bit-identical greedy resume
# ---------------------------------------------------------------------------

def test_high_priority_arrival_preempts_and_victim_resumes(model):
    """batch=1: a high-priority arrival evicts the running low-priority
    request (slot preemption); the victim later resumes and its full
    greedy stream must bit-match an uninterrupted solo run."""
    cfg, params = model
    rng = np.random.default_rng(2)
    lo_p = jnp.asarray(rng.integers(0, cfg.vocab_size, 5), jnp.int32)
    hi_p = jnp.asarray(rng.integers(0, cfg.vocab_size, 4), jnp.int32)
    scfg = _scfg(batch=1, decode_chunk=2)

    engine = Engine(cfg, params, scfg)
    lo = engine.submit(lo_p, 6)
    engine._t0 = time.perf_counter()
    engine._admit(0.0)
    assert engine._slots[0] is not None and engine._slots[0].id == lo
    # decode a couple of chunks so the victim has generated tokens to
    # carry through eviction and replay on resume
    engine._run_chunk(0.0)
    hi = engine.submit(hi_p, 5, priority=5)
    engine._admit(0.0)                         # full batch: must evict lo
    assert engine._slots[0].id == hi
    assert engine.preemptions == 1
    done = engine.run()
    assert engine.allocator.in_use == 0
    assert done[lo].preemptions == 1
    assert done[hi].t_done <= done[lo].t_done  # hi finished first

    for rid, prompt, n in ((lo, lo_p, 6), (hi, hi_p, 5)):
        ref_engine, (ref,) = _drive(cfg, params, [prompt], [n],
                                    _scfg(batch=1, decode_chunk=2))
        assert done[rid].tokens == ref.tokens, rid
    assert engine.compile_counts == {"prefill": 1, "decode_chunk": 1}


def test_no_slot_eviction_for_page_infeasible_arrival(model):
    """A high-priority arrival whose pages could never be covered even
    after evicting every strictly-weaker runner must not cost anyone
    their slot (same feasibility bound as the page-backpressure path)."""
    cfg, params = model
    # capacity 5: A (prio 10) books 3 pages, B (prio 1) books 2
    engine = Engine(cfg, params, _scfg(batch=2, decode_chunk=2,
                                       num_pages=6))
    a = engine.submit(jnp.asarray([1, 2, 3, 4, 5], jnp.int32), 8,
                      priority=10)
    b = engine.submit(jnp.asarray([6, 7, 8, 9, 10], jnp.int32), 4,
                      priority=1)
    engine._t0 = time.perf_counter()
    engine._admit(0.0)
    assert {r.id for r in engine._slots if r is not None} == {a, b}
    assert engine.allocator.available == 0
    # C needs 4 pages; evicting B recovers only 2 and A outranks C
    c = engine.submit(jnp.asarray(np.arange(1, 8), jnp.int32), 9,
                      priority=5)
    engine._admit(0.0)
    assert engine.preemptions == 0             # nobody lost a slot
    assert {r.id for r in engine._slots if r is not None} == {a, b}
    done = engine.run()                        # C admitted once B frees
    assert set(done) == {a, b, c}
    assert engine.allocator.in_use == 0


def test_arrival_during_admission_window_is_not_a_stall(model):
    """A request whose arrival lands inside the previous _admit call's
    execution window (prefill takes real milliseconds) must be admitted
    on the next loop, not misdiagnosed as a scheduler stall."""
    cfg, params = model
    engine = Engine(cfg, params, _scfg(batch=1))
    # finishes at prefill (max_new=1), so the engine goes idle with the
    # second request's arrival already in the past by wall clock
    a = engine.submit(jnp.asarray([1, 2, 3], jnp.int32), 1, arrival=0.0)
    b = engine.submit(jnp.asarray([4, 5, 6], jnp.int32), 3,
                      arrival=1e-4)
    done = engine.run()                        # must not raise "stalled"
    assert set(done) == {a, b}
    assert len(done[b].tokens) == 3
    assert engine.allocator.in_use == 0


def test_equal_priority_never_preempts(model):
    """Preemption requires *strictly* higher effective priority — an
    equal-priority arrival waits (no eviction ping-pong)."""
    cfg, params = model
    engine = Engine(cfg, params, _scfg(batch=1, decode_chunk=2))
    a = engine.submit(jnp.asarray([1, 2, 3], jnp.int32), 4, priority=2)
    engine._t0 = time.perf_counter()
    engine._admit(0.0)
    engine.submit(jnp.asarray([4, 5, 6], jnp.int32), 4, priority=2)
    engine._admit(0.0)
    assert engine._slots[0] is not None and engine._slots[0].id == a
    assert engine.preemptions == 0
    engine.run()
    assert engine.allocator.in_use == 0


def test_preempted_mid_replay_carries_full_stream(model):
    """Evicting a slot that is itself still replaying must splice the
    unreplayed tail back onto the requeued request — nothing of the
    client-visible stream is lost or duplicated."""
    cfg, params = model
    rng = np.random.default_rng(4)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, 4), jnp.int32)
    scfg = _scfg(batch=1, decode_chunk=2)
    engine = Engine(cfg, params, scfg)
    rid = engine.submit(prompt, 8)
    engine._t0 = time.perf_counter()
    engine._admit(0.0)
    for _ in range(3):                        # generate 1 + 3x2 tokens
        engine._run_chunk(0.0)
    # evict, resume, then evict again after a single replay chunk (the
    # replay lane is 2 tokens/chunk, 6 tokens pending -> mid-replay)
    engine._evict(0, 0.0)
    engine._admit(0.0)
    engine._run_chunk(0.0)
    assert engine._slot_forced[0]             # replay still pending
    engine._evict(0, 0.0)
    done = engine.run()
    _, (ref,) = _drive(cfg, params, [prompt], [8], scfg)
    assert done[rid].tokens == ref.tokens
    assert done[rid].preemptions == 2
    assert engine.allocator.in_use == 0


# ---------------------------------------------------------------------------
# Bugfix regressions
# ---------------------------------------------------------------------------

def test_counting_jit_tracks_signatures():
    from repro.serve.engine import _CountingJit

    calls = []

    def f(x, n):
        calls.append(1)
        return x * n

    g = _CountingJit(f)
    g(jnp.ones((2, 2)), 3)
    g(jnp.zeros((2, 2)), 7)                   # same signature
    assert g.compile_count == 1
    g(jnp.ones((4, 2)), 3)                    # new shape
    assert g.compile_count == 2
    g(jnp.ones((2, 2), jnp.int32), 3)         # new dtype
    assert g.compile_count == 3


def test_submit_truncation_is_explicit(model):
    cfg, params = model
    engine = Engine(cfg, params, _scfg(batch=1))
    rid = engine.submit(jnp.asarray([1, 2, 3, 4, 5], jnp.int32), 100)
    done = engine.run()
    assert done[rid].truncated                # not silently clamped
    assert len(done[rid].tokens) == 16 - 5
    ok = engine.submit(jnp.asarray([1, 2, 3], jnp.int32), 4)
    assert not engine.run()[ok].truncated


def test_generate_eos_error_names_eos(model):
    """generate()'s ragged-output error must name the actual cause (an
    EOS stop) instead of guessing — the old message fired for truncation
    too."""
    cfg, params = model
    probe = Engine(cfg, params, ServeConfig(batch=1, max_len=16))
    out = probe.generate(jnp.asarray([[1, 2, 3, 4]], jnp.int32), 6)
    eos = int(out[0, 5])                      # second generated token
    engine = Engine(cfg, params, ServeConfig(batch=1, max_len=16,
                                             eos_id=eos))
    with pytest.raises(RuntimeError, match=f"eos_id={eos}"):
        engine.generate(jnp.asarray([[1, 2, 3, 4]], jnp.int32), 6)


def test_scheduler_stall_raises_not_spins(model):
    """Backpressure with every slot idle used to be declared impossible
    and busy-spun; with overcommit it is reachable through a page leak —
    the engine must fail loudly instead."""
    cfg, params = model
    engine = Engine(cfg, params, _scfg(batch=2, num_pages=5))
    engine.allocator.alloc(3)                 # simulate a leak
    engine.submit(jnp.asarray([1, 2, 3, 4, 5], jnp.int32), 4)
    with pytest.raises(RuntimeError, match="stalled"):
        engine.run()


def test_alloc_mode_validated(model):
    cfg, params = model
    with pytest.raises(ValueError, match="alloc_mode"):
        Engine(cfg, params, _scfg(alloc_mode="lazy"))


def test_workload_reports_scheduler_stats(model):
    from repro.serve import run_timed_workload
    cfg, params = model
    engine = Engine(cfg, params, _scfg(batch=2, alloc_mode="incremental",
                                       num_pages=7))
    r = run_timed_workload(engine, cfg.vocab_size, requests=4,
                           prompt_budget=6, new_tokens=6)
    for key in ("preemptions", "occupancy", "concurrency", "pool_pages",
                "truncated"):
        assert key in r, key
    assert r["pool_pages"] == 7
    assert r["truncated"] == 0
    assert 0.0 < r["occupancy"] <= 1.0
    assert r["compile_counts"] == {"prefill": 1, "decode_chunk": 1}
    assert engine.allocator.in_use == 0
