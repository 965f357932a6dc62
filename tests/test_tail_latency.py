"""Tail-latency engineering: chunked prefill and grouped admission
through one compiled wave program (greedy streams BIT-match the
monolithic engine, the wave program compiles exactly once), the
host-tier page swap that makes preemption resume an O(pages) copy
instead of an O(generated) replay, the prefix cache's host cold tier
with a capacity cap, and the HostPagePool refcount/payload units."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config, reduced
from repro.models import model_init
from repro.serve import Engine, ServeConfig
from repro.serve.paging import HostPagePool


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


def _leaks(engine) -> int:
    engine.release_prefix_cache()
    return engine.leaked_pages()


def _prompts(vocab, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.integers(0, vocab, n), jnp.int32)
            for n in lens]


WAVE_COUNTS = {"prefill": 0, "decode_chunk": 1, "prefill_chunk": 1}


# ---------------------------------------------------------------------------
# HostPagePool units: refcounts, payload lifecycle, backpressure
# ---------------------------------------------------------------------------

def test_host_pool_alloc_store_load_free():
    pool = HostPagePool(4)
    assert pool.capacity == 4 and pool.available == 4
    pages = pool.alloc(2)
    assert pool.in_use == 2
    pool.store(pages[0], {"rows": 123})
    assert pool.load(pages[0]) == {"rows": 123}
    pool.free(pages)
    assert pool.in_use == 0 and pool.available == 4


def test_host_pool_payload_dies_with_last_holder():
    pool = HostPagePool(2)
    (p,) = pool.alloc(1)
    pool.store(p, "payload")
    pool.share([p])                     # second holder
    pool.free([p])                      # first release: payload survives
    assert pool.load(p) == "payload"
    pool.free([p])                      # last release: payload dropped
    (q,) = pool.alloc(1)                # id may be recycled...
    with pytest.raises(ValueError, match="no stored payload"):
        pool.load(q)                    # ...but never its old payload


def test_host_pool_store_load_errors():
    pool = HostPagePool(2)
    (p,) = pool.alloc(1)
    with pytest.raises(ValueError, match="no stored payload"):
        pool.load(p)                    # nothing stored yet
    pool.free([p])
    with pytest.raises(ValueError, match="no outstanding references"):
        pool.store(p, "stale")          # freed id must not resurrect
    with pytest.raises(ValueError, match="not currently allocated"):
        pool.free([p])                  # double free


def test_host_pool_backpressure():
    pool = HostPagePool(2)
    held = pool.alloc(2)
    assert pool.alloc(1) is None        # full: swap falls back to replay
    assert not pool.can_alloc(1)
    pool.free(held[:1])
    assert pool.alloc(1) is not None


@given(num_pages=st.integers(1, 6),
       ops=st.lists(st.sampled_from(["alloc", "share", "free", "store"]),
                    min_size=1, max_size=40))
@settings(max_examples=60, deadline=None)
def test_host_pool_refcount_invariants(num_pages, ops):
    """Property: under any interleaving of alloc/share/free/store, the
    pool's counters match a shadow refcount model exactly, payloads are
    readable iff stored on a page with a live holder, and a page's
    payload dies with its last reference."""
    pool = HostPagePool(num_pages)
    rc: dict[int, int] = {}             # shadow refcounts
    stored: dict[int, object] = {}      # shadow payloads
    for step, op in enumerate(ops):
        held = sorted(p for p, n in rc.items() if n > 0)
        if op == "alloc":
            got = pool.alloc(1)
            if len(held) >= num_pages:
                assert got is None      # backpressure, never overcommit
            else:
                assert got is not None
                (p,) = got
                assert rc.get(p, 0) == 0, "live id handed out twice"
                rc[p] = 1
                # a recycled id's old payload must have died already
                assert p not in stored
        elif op == "share" and held:
            p = held[step % len(held)]
            pool.share([p])
            rc[p] += 1
        elif op == "free" and held:
            p = held[step % len(held)]
            pool.free([p])
            rc[p] -= 1
            if rc[p] == 0:
                stored.pop(p, None)
                with pytest.raises(ValueError):
                    pool.load(p)        # payload died with last holder
        elif op == "store" and held:
            p = held[step % len(held)]
            pool.store(p, ("payload", step))
            stored[p] = ("payload", step)
        # the pool's view must equal the shadow model after every op
        assert pool.in_use == sum(1 for n in rc.values() if n > 0)
        assert pool.available == pool.capacity - pool.in_use
        for p, n in rc.items():
            assert pool.refcount(p) == n
        for p, payload in stored.items():
            assert pool.load(p) == payload


# ---------------------------------------------------------------------------
# Chunked prefill: bit-match vs monolithic, single wave compilation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [2, 3, 8])
def test_chunked_prefill_bitmatches_monolithic(model, chunk):
    """Every chunk width — including one that never splits (8 >= all
    prompts) — reproduces the monolithic engine's greedy streams
    through the ONE wave program; the monolithic prefill is never
    built."""
    cfg, params = model
    prompts = _prompts(cfg.vocab_size, (7, 5, 6, 4), seed=0)
    _, want = _drive(cfg, params, prompts, [6] * 4, _scfg())
    engine, got = _drive(cfg, params, prompts, [6] * 4,
                         _scfg(prefill_chunk=chunk))
    assert [r.tokens for r in got] == [r.tokens for r in want]
    assert engine.compile_counts == WAVE_COUNTS
    assert engine.stats["prefill_waves"] >= 1
    assert _leaks(engine) == 0


def test_chunked_prefill_batch1_backlog_no_stall(model):
    """Regression for the idle-wait stall check: with one slot mid-
    prefill (inactive but progressing) and a second request queued, the
    scheduler must keep running waves — the PR 4 stall RuntimeError is
    for genuinely idle engines only."""
    cfg, params = model
    prompts = _prompts(cfg.vocab_size, (7, 5), seed=1)
    _, want = _drive(cfg, params, prompts, [5, 5], _scfg(batch=1))
    engine, got = _drive(cfg, params, prompts, [5, 5],
                         _scfg(batch=1, prefill_chunk=2))
    assert [r.tokens for r in got] == [r.tokens for r in want]
    assert _leaks(engine) == 0


# ---------------------------------------------------------------------------
# Grouped admission: bit-match vs serialized, one padded wave
# ---------------------------------------------------------------------------

def test_grouped_admission_bitmatches_serialized(model):
    """A simultaneous burst admitted as one (G, prefill_len) wave emits
    the serialized engine's exact streams, in fewer prefill
    dispatches."""
    cfg, params = model
    prompts = _prompts(cfg.vocab_size, (7, 5, 6), seed=2)
    _, want = _drive(cfg, params, prompts, [6] * 3, _scfg())
    engine, got = _drive(cfg, params, prompts, [6] * 3,
                         _scfg(admit_group=3))
    assert [r.tokens for r in got] == [r.tokens for r in want]
    assert engine.compile_counts == WAVE_COUNTS
    # all three prompts fit one wave each lane: one dispatch total
    assert engine.stats["prefill_waves"] == 1
    assert _leaks(engine) == 0


def test_chunked_plus_grouped_bitmatch(model):
    """Chunked and grouped compose: several lanes advance chunk-by-
    chunk through the same program, still bit-matching monolithic
    serialized prefill."""
    cfg, params = model
    prompts = _prompts(cfg.vocab_size, (7, 6, 5, 7, 4), seed=3)
    _, want = _drive(cfg, params, prompts, [6] * 5, _scfg())
    engine, got = _drive(cfg, params, prompts, [6] * 5,
                         _scfg(prefill_chunk=3, admit_group=2))
    assert [r.tokens for r in got] == [r.tokens for r in want]
    assert engine.compile_counts == WAVE_COUNTS
    assert _leaks(engine) == 0


# ---------------------------------------------------------------------------
# Host-tier swap: O(pages) resume, bit-match, zero leaks (both pools)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant,backend", [
    ("dense", "xla"), ("dense", "pallas"),
    ("w8a8_nibble", "xla"), ("w8a8_nibble", "pallas"),
])
def test_swap_roundtrip_bitmatch(quant, backend):
    """The acceptance scenario: an overcommitted pool forces evictions
    mid-stream; with swap_mode="host" every resume restores KV rows by
    page copy (swap_in > 0, replayed decode steps saved) and the greedy
    streams still equal an uncontended parity-pool run's — across the
    quant x backend grid, with zero pages leaked on the device AND the
    host pool."""
    cfg = reduced(get_config("yi-6b")).replace(quant_mode=quant,
                                               quant_backend=backend)
    params = model_init(jax.random.PRNGKey(0), cfg)
    prompts = _prompts(cfg.vocab_size, (4, 6, 5, 7), seed=4)
    budgets = [8] * 4

    _, want = _drive(cfg, params, prompts, budgets, _scfg())
    engine, got = _drive(cfg, params, prompts, budgets,
                         _scfg(alloc_mode="incremental", num_pages=7,
                               swap_mode="host"))
    assert [r.tokens for r in got] == [r.tokens for r in want]
    assert engine.stats["preemptions"] >= 1
    assert engine.stats["swap_out"] >= 1
    assert engine.stats["swap_in"] == engine.stats["swap_out"]
    assert engine.stats["replay_steps_saved"] >= 1
    assert engine.compile_counts == {"prefill": 1, "decode_chunk": 1}
    assert _leaks(engine) == 0          # device + host pools both clean
    assert engine.host_pool.in_use == 0


def test_swap_saves_decode_steps_vs_replay(model):
    """Same overcommitted workload with swap off vs on: the page-copy
    resume must spend strictly fewer decode-chunk dispatches than
    replaying every generated token through the forced lane."""
    cfg, params = model
    prompts = _prompts(cfg.vocab_size, (4, 6, 5, 7), seed=5)
    budgets = [8] * 4
    off, got_off = _drive(cfg, params, prompts, budgets,
                          _scfg(alloc_mode="incremental", num_pages=7))
    on, got_on = _drive(cfg, params, prompts, budgets,
                        _scfg(alloc_mode="incremental", num_pages=7,
                              swap_mode="host"))
    assert [r.tokens for r in got_on] == [r.tokens for r in got_off]
    assert on.stats["replay_steps_saved"] >= 1
    assert on.stats["decode_chunks"] < off.stats["decode_chunks"]
    assert _leaks(on) == 0 and _leaks(off) == 0


def test_swap_resume_bit_stable_under_temperature(model):
    """The restore is a bit-copy, so *sampled* streams also continue
    exactly (replay already guaranteed this via index-derived RNG; swap
    must not regress it)."""
    cfg, params = model
    prompts = _prompts(cfg.vocab_size, (4, 6, 5, 7), seed=6)
    budgets = [8] * 4
    scfg = _scfg(alloc_mode="incremental", num_pages=7,
                 temperature=0.7)
    _, want = _drive(cfg, params, prompts, budgets, scfg)
    engine, got = _drive(cfg, params, prompts, budgets,
                         dataclasses.replace(scfg, swap_mode="host"))
    assert [r.tokens for r in got] == [r.tokens for r in want]
    assert engine.stats["swap_in"] >= 1
    assert _leaks(engine) == 0


def test_all_three_mechanisms_compose(model):
    """Chunked + grouped + swap on one overcommitted engine still
    bit-matches the plain engine, holds the wave compile pins, and
    leaks nothing on either pool."""
    cfg, params = model
    prompts = _prompts(cfg.vocab_size, (4, 6, 5, 7, 6), seed=7)
    budgets = [8] * 5
    _, want = _drive(cfg, params, prompts, budgets,
                     _scfg(alloc_mode="incremental", num_pages=7))
    engine, got = _drive(cfg, params, prompts, budgets,
                         _scfg(alloc_mode="incremental", num_pages=7,
                               prefill_chunk=3, admit_group=2,
                               swap_mode="host"))
    assert [r.tokens for r in got] == [r.tokens for r in want]
    assert engine.compile_counts == WAVE_COUNTS
    assert _leaks(engine) == 0


def test_spec_decode_composes_with_wave_and_swap(model):
    """Speculative decoding keeps its draft/verify pins while the wave
    program replaces the prefill, and greedy spec streams still equal
    the plain non-spec engine's."""
    cfg, params = model
    prompts = _prompts(cfg.vocab_size, (7, 5, 6), seed=8)
    budgets = [6] * 3
    _, want = _drive(cfg, params, prompts, budgets, _scfg())
    engine, got = _drive(cfg, params, prompts, budgets,
                         _scfg(spec_decode=True, spec_k=3,
                               prefill_chunk=4, swap_mode="host"))
    assert [r.tokens for r in got] == [r.tokens for r in want]
    assert engine.compile_counts == {"prefill": 0, "decode_chunk": 0,
                                     "prefill_chunk": 1, "draft": 1,
                                     "verify": 1}
    assert _leaks(engine) == 0


# ---------------------------------------------------------------------------
# Prefix-cache cold tier + capacity cap
# ---------------------------------------------------------------------------

def _shared_head_prompts(vocab, n, head_len=4, tail=3, seed=9):
    rng = np.random.default_rng(seed)
    head = rng.integers(0, vocab, head_len)
    return [jnp.asarray(np.concatenate(
        [head, rng.integers(0, vocab, tail)]), jnp.int32)
        for _ in range(n)]


def test_prefix_cache_pages_cap_reclaims(model):
    """The prefix_cache_pages cap bounds the index after drain: distinct
    prompts would otherwise pin one page each, the cap reclaims down to
    the budget (best-effort while slots run, exact once idle)."""
    cfg, params = model
    prompts = _prompts(cfg.vocab_size, (5, 6, 7, 5, 6, 7), seed=10)
    engine, _ = _drive(cfg, params, prompts, [4] * 6,
                       _scfg(batch=2, num_pages=24, prefix_cache=True,
                             prefix_cache_pages=2))
    assert len(engine.prefix_cache) <= 2
    assert engine.prefix_capacity_reclaims >= 1
    assert _leaks(engine) == 0


def test_cold_tier_demotes_and_promotes(model):
    """With the host tier attached, capacity-capped reclaim demotes
    chunks to host pages instead of dropping them, and a later request
    whose chain reaches the cold run promotes it back — counted as a
    prefix hit (the hit-rate stat composes across tiers)."""
    cfg, params = model
    vocab = cfg.vocab_size
    first, again = _shared_head_prompts(vocab, 2)  # same head, own tails
    evictors = _prompts(vocab, (5, 6, 7), seed=11)
    engine = Engine(cfg, params,
                    _scfg(batch=1, num_pages=24, prefix_cache=True,
                          prefix_cache_pages=1, swap_mode="host"))
    ids = [engine.submit(p, 4) for p in (first, *evictors, again)]
    done = engine.run()
    st = engine.stats
    # the shared 4-token head chunk was demoted by the cap, then
    # promoted back for the final request
    assert st["prefix_demotions"] >= 1
    assert st["prefix_cold_hits"] >= 1
    assert st["prefix_hits"] >= 1
    assert st["prefix_hit_rate"] > 0.0
    # promoted-prefix stream equals the same request run uncached
    _, solo = _drive(cfg, params, [again], [4], _scfg(batch=1))
    assert done[ids[-1]].tokens == solo[0].tokens
    assert _leaks(engine) == 0
    assert engine.host_pool.in_use == 0


def test_cold_tier_composes_with_chunked_prefill(model):
    """Prefix hits (hot and cold) + chunked prefill: the wave engine's
    suffix chunks start past the cached prefix and streams still
    bit-match the plain uncached engine."""
    cfg, params = model
    prompts = _shared_head_prompts(cfg.vocab_size, 3, seed=13)
    _, want = _drive(cfg, params, prompts, [4] * 3, _scfg(batch=1))
    engine, got = _drive(cfg, params, prompts, [4] * 3,
                         _scfg(batch=1, prefix_cache=True,
                               prefill_chunk=2, swap_mode="host"))
    assert [r.tokens for r in got] == [r.tokens for r in want]
    assert engine.stats["prefix_hits"] >= 1
    assert engine.compile_counts == WAVE_COUNTS
    assert _leaks(engine) == 0


# ---------------------------------------------------------------------------
# Config validation
# ---------------------------------------------------------------------------

def test_tail_knobs_validated(model):
    cfg, params = model
    with pytest.raises(ValueError, match="prefill_len"):
        Engine(cfg, params, _scfg(admit_group=2, prefill_len=0))
    with pytest.raises(ValueError, match="swap_mode must be"):
        Engine(cfg, params, _scfg(swap_mode="disk"))
    with pytest.raises(ValueError, match="prefill_chunk must be"):
        Engine(cfg, params, _scfg(prefill_chunk=-1))
    with pytest.raises(ValueError, match="admit_group must be"):
        Engine(cfg, params, _scfg(admit_group=0))
