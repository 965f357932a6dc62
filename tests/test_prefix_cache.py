"""Prefix caching with copy-on-write pages: refcounted allocation,
hash-chained prefix index with LRU reclaim, suffix-only prefill that
BIT-matches uncached runs, COW on fully covered prompts, and
evict-while-shared survival."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config, reduced
from repro.models import model_init
from repro.serve import (
    Engine,
    PageAllocator,
    PrefixCache,
    ServeConfig,
)


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("yi-6b"))
    params = model_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _scfg(**over):
    kw = dict(batch=2, max_len=16, prefill_len=8, decode_chunk=3,
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


def _shared_prompts(vocab, head_len=4, tails=(2, 3), seed=0):
    rng = np.random.default_rng(seed)
    head = rng.integers(0, vocab, head_len)
    return [jnp.asarray(np.concatenate(
        [head, rng.integers(0, vocab, t)]), jnp.int32) for t in tails]


# ---------------------------------------------------------------------------
# Allocator refcount units
# ---------------------------------------------------------------------------

def test_allocator_refcount_share_free():
    a = PageAllocator(8, reserved=1)
    pages = a.alloc(2)
    assert all(a.refcount(p) == 1 for p in pages)
    a.share(pages)                          # second holder
    assert all(a.refcount(p) == 2 for p in pages)
    a.free(pages)                           # first holder releases
    assert a.in_use == 2                    # pages survive: one holder left
    assert a.available == 5
    a.free(pages)                           # last holder releases
    assert a.in_use == 0 and a.available == 7


def test_allocator_double_decrement_raises():
    a = PageAllocator(4, reserved=1)
    pages = a.alloc(1)
    a.free(pages)
    with pytest.raises(ValueError, match="not currently allocated"):
        a.free(pages)                       # refcount already hit zero
    with pytest.raises(ValueError, match="sharing pages not"):
        a.share(pages)                      # cannot share a freed page


def test_allocator_shared_page_not_recycled_early():
    """A page with a second holder must not reappear on the free list
    until both release it."""
    a = PageAllocator(3, reserved=1)        # capacity 2
    p = a.alloc(1)
    a.share(p)
    a.free(p)
    got = a.alloc(1)
    assert got is not None and got[0] != p[0]
    assert a.alloc(1) is None               # pool exhausted; p still held
    a.free(p)
    assert a.alloc(1) == p                  # now recycled


# ---------------------------------------------------------------------------
# PrefixCache index units
# ---------------------------------------------------------------------------

def test_prefix_cache_chain_keys_commit_to_whole_prefix():
    a = PageAllocator(8, reserved=1)
    c = PrefixCache(4, a)
    k1 = c.chunk_keys(np.arange(8))
    k2 = c.chunk_keys(np.concatenate([np.arange(4) + 1, np.arange(4, 8)]))
    assert k1[0] != k2[0]
    assert k1[1] != k2[1]                   # same chunk 1 tokens, new key
    assert len(c.chunk_keys(np.arange(7))) == 1   # partial tail unindexed


def test_prefix_cache_insert_match_acquire():
    a = PageAllocator(8, reserved=1)
    c = PrefixCache(4, a)
    pages = a.alloc(2)
    keys = c.chunk_keys(np.arange(8))
    assert c.match(keys) == []
    c.insert(keys, pages)
    assert all(a.refcount(p) == 2 for p in pages)  # owner + index
    assert c.match(keys) == pages
    assert c.match(keys[:1]) == pages[:1]
    got = c.acquire(keys)
    assert got == pages
    assert all(a.refcount(p) == 3 for p in pages)
    a.free(got)
    a.free(pages)
    assert a.in_use == 2                    # index refs keep them live
    c.drop()
    assert a.in_use == 0


def test_prefix_cache_reclaim_is_lru_leaf_first():
    """An interior chunk is never dropped before its descendant, and
    pages another holder still maps (refcount > 1) are skipped."""
    a = PageAllocator(8, reserved=1)
    c = PrefixCache(2, a)
    pages = a.alloc(3)
    keys = c.chunk_keys(np.arange(6))
    c.insert(keys, pages)
    a.free(pages)                           # only the index holds them
    assert c.reclaimable() == 3
    # the leaf (chunk 2) must go before chunk 1, chunk 1 before chunk 0
    assert c.reclaim(1) == 1
    assert c.match(keys) == pages[:2]
    # a page with another holder is not reclaimable
    c.acquire(keys[:2])
    assert c.reclaimable() == 0
    assert c.reclaim(2) == 0
    a.free(pages[:2])
    assert c.reclaim(2) == 2
    assert a.in_use == 0


# ---------------------------------------------------------------------------
# Engine: suffix-only prefill, bit-match, accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant,backend", [
    ("dense", "xla"), ("dense", "pallas"),
    ("w8a8_nibble", "xla"), ("w8a8_nibble", "pallas"),
])
def test_shared_prefix_bitmatch_and_suffix_only_prefill(quant, backend):
    """The acceptance scenario: two requests sharing a page-aligned
    prompt head through a prefix-cache engine BIT-match the uncached
    engine's streams, the second admission prefills only its suffix
    (prefill-token accounting), both compiled programs stay single,
    and the allocator reports zero leaks once the index lets go."""
    cfg = reduced(get_config("yi-6b")).replace(quant_mode=quant,
                                               quant_backend=backend)
    params = model_init(jax.random.PRNGKey(0), cfg)
    prompts = _shared_prompts(cfg.vocab_size)   # 4-token head = 1 page
    budgets = [4, 4]

    _, want = _drive(cfg, params, prompts, budgets, _scfg())
    engine, got = _drive(cfg, params, prompts, budgets,
                         _scfg(prefix_cache=True))
    assert [r.tokens for r in got] == [r.tokens for r in want]
    # request 0 prefilled fully (6), request 1 only its 3-token suffix
    assert engine.prefill_tokens == 6 + 3
    assert engine.stats["prefix_hits"] == 1
    assert engine.compile_counts == {"prefill": 1, "decode_chunk": 1}
    assert engine.allocator.in_use == len(engine.prefix_cache.pages)
    engine.release_prefix_cache()
    assert engine.allocator.in_use == 0     # zero leaks


def test_shared_prefix_matches_solo_uncached_runs(model):
    """Each shared-prefix stream equals the same request run alone
    through an uncached engine — sharing must be observationally
    invisible."""
    cfg, params = model
    prompts = _shared_prompts(cfg.vocab_size, seed=3)
    engine, got = _drive(cfg, params, prompts, [4, 4],
                         _scfg(prefix_cache=True))
    for p, r in zip(prompts, got):
        _, solo = _drive(cfg, params, [p], [4], _scfg())
        assert r.tokens == solo[0].tokens


def test_cow_fires_exactly_on_fully_covered_prompt(model):
    """A prompt fully covered by cached pages triggers exactly one
    copy-on-write page duplication (the partial tail page), prefills
    exactly one token, and still bit-matches the uncached engine."""
    cfg, params = model
    rng = np.random.default_rng(7)
    p = jnp.asarray(rng.integers(0, cfg.vocab_size, 8), jnp.int32)

    _, want = _drive(cfg, params, [p, p], [4, 4], _scfg())
    engine, got = _drive(cfg, params, [p, p], [4, 4],
                         _scfg(prefix_cache=True))
    assert [r.tokens for r in got] == [r.tokens for r in want]
    assert engine.cow_copies == 1
    assert engine.prefill_tokens == 8 + 1   # full prompt, then one token
    # partial hits never COW: a 6-token prompt over 4-token pages leaves
    # a 2-token uncached tail that lands on a private page anyway
    engine2, _ = _drive(cfg, params,
                        [jnp.asarray(np.asarray(p)[:6], jnp.int32)] * 2,
                        [4, 4], _scfg(prefix_cache=True))
    assert engine2.cow_copies == 0
    assert engine2.prefill_tokens == 6 + 2


def test_cow_leaves_shared_page_intact_for_other_holder(model):
    """After a COW admission writes into its private copy, a third
    request hitting the same prefix still reads the original cached
    page — its stream must stay identical."""
    cfg, params = model
    rng = np.random.default_rng(11)
    p = jnp.asarray(rng.integers(0, cfg.vocab_size, 8), jnp.int32)
    _, want = _drive(cfg, params, [p] * 3, [4] * 3,
                     _scfg(batch=1))        # one slot: strictly serial
    engine, got = _drive(cfg, params, [p] * 3, [4] * 3,
                         _scfg(batch=1, prefix_cache=True))
    assert [r.tokens for r in got] == [r.tokens for r in want]
    assert engine.cow_copies == 2           # admissions 2 and 3
    engine.release_prefix_cache()
    assert engine.allocator.in_use == 0


def test_paged_flash_engine_shared_prefix(model):
    """The Pallas paged-decode path (attn_impl=flash) over shared
    prefix pages: greedy streams must equal the uncached flash
    engine's (argmax is stable across the prefill summation orders on
    this model, as in test_paging's flash e2e)."""
    cfg, params = model
    fcfg = cfg.replace(attn_impl="flash")
    prompts = _shared_prompts(cfg.vocab_size, seed=5)
    _, want = _drive(fcfg, params, prompts, [4, 4], _scfg())
    engine, got = _drive(fcfg, params, prompts, [4, 4],
                         _scfg(prefix_cache=True))
    assert [r.tokens for r in got] == [r.tokens for r in want]
    assert engine.stats["prefix_hits"] == 1


def test_mla_shared_prefix_bitmatch():
    """Latent-cache (deepseek MLA) pools share prefix pages too: the
    spliced latents decompress bit-identically."""
    cfg = reduced(get_config("deepseek-v3-671b"))
    params = model_init(jax.random.PRNGKey(0), cfg)
    prompts = _shared_prompts(cfg.vocab_size, seed=9)
    _, want = _drive(cfg, params, prompts, [4, 4], _scfg())
    engine, got = _drive(cfg, params, prompts, [4, 4],
                         _scfg(prefix_cache=True))
    assert [r.tokens for r in got] == [r.tokens for r in want]
    engine.release_prefix_cache()
    assert engine.allocator.in_use == 0


# ---------------------------------------------------------------------------
# Preemption interplay: evict-while-shared, reclaim under pressure
# ---------------------------------------------------------------------------

def test_evict_while_shared_survivor_keeps_pages(model):
    """Preempting a request whose prefix pages are shared must not
    yank them from the other holder: the survivor's stream and the
    victim's resumed stream both bit-match the uncached engine, and
    every page comes back once the index releases."""
    cfg, params = model
    rng = np.random.default_rng(13)
    head = rng.integers(0, cfg.vocab_size, 4)
    prompts = [jnp.asarray(np.concatenate(
        [head, rng.integers(0, cfg.vocab_size, 3)]), jnp.int32)
        for _ in range(3)]
    budgets = [8, 8, 8]
    # capacity 9 < 3 × 4-page worst case: incremental top-ups run the
    # pool dry and preempt a sharing runner mid-stream
    over = dict(batch=3, max_len=16, num_pages=10,
                alloc_mode="incremental")
    _, want = _drive(cfg, params, prompts, budgets, _scfg(batch=3))
    engine, got = _drive(cfg, params, prompts, budgets,
                         _scfg(prefix_cache=True, **over))
    assert engine.preemptions > 0           # the scenario actually fired
    assert [r.tokens for r in got] == [r.tokens for r in want]
    engine.release_prefix_cache()
    assert engine.allocator.in_use == 0
    assert engine.allocator.available == engine.allocator.capacity


def test_cold_prefix_pages_reclaimed_under_pressure(model):
    """Distinct prompts through a small pool: index entries pinned by
    nobody else are reclaimed LRU-first instead of blocking admission,
    and the run drains without a stall."""
    cfg, params = model
    rng = np.random.default_rng(17)
    prompts = [jnp.asarray(rng.integers(0, cfg.vocab_size, 6), jnp.int32)
               for _ in range(4)]
    # capacity 4 fits one 2-page request plus its pages' index pins —
    # each admission must reclaim the previous request's cold entries
    engine, got = _drive(cfg, params, prompts, [4] * 4,
                         _scfg(batch=1, num_pages=5, prefix_cache=True))
    assert all(len(r.tokens) == 4 for r in got)
    _, want = _drive(cfg, params, prompts, [4] * 4, _scfg(batch=1))
    assert [r.tokens for r in got] == [r.tokens for r in want]
    engine.release_prefix_cache()
    assert engine.allocator.in_use == 0


@given(n_chunks=st.integers(1, 6), host_pages=st.integers(1, 8))
@settings(max_examples=40, deadline=None)
def test_cold_tier_demote_promote_roundtrip(n_chunks, host_pages):
    """Property: reclaiming an ``n_chunks`` hash chain through a
    ``host_pages``-deep cold tier always leaves a contiguous head run
    of ``min(n_chunks, host_pages)`` cold entries (overflow kills the
    oldest demotions — the leaf-most chunks), every promoted payload
    reads back exactly what demotion stored, and the full round trip
    leaks neither device nor host pages."""
    from repro.serve.paging import HostPagePool

    alloc = PageAllocator(16, reserved=1)
    host = HostPagePool(host_pages)
    cache = PrefixCache(2, alloc)
    stored = {}

    def demote(page):
        hid = host.alloc(1)
        if hid is None:
            return None
        host.store(hid[0], ("rows-of", page))
        stored[hid[0]] = ("rows-of", page)
        return hid[0]

    cache.attach_cold_tier(demote, lambda hid: host.free([hid]))

    keys = cache.chunk_keys(np.arange(n_chunks * 2, dtype=np.int64))
    assert len(keys) == n_chunks
    pages = alloc.alloc(n_chunks)
    cache.insert(keys, pages)
    alloc.free(pages)                   # cache now holds the only refs

    freed = cache.reclaim(n_chunks)
    assert freed == n_chunks
    assert alloc.in_use == 0            # device side fully released
    n_cold = min(n_chunks, host_pages)
    assert cache.cold_size == n_cold
    assert host.in_use == n_cold
    # leaf-first demotion + oldest-first overflow keeps the chain head
    assert cache.match_cold(keys, 0) == n_cold

    hids = cache.pop_cold(keys[:n_cold])
    for hid in hids:
        assert host.load(hid) == stored[hid]
        host.free([hid])
    assert cache.cold_size == 0
    assert host.in_use == 0             # host side fully released
    with pytest.raises(ValueError, match="not in the cold index"):
        cache.pop_cold(keys[:1])
    cache.drop()
    assert alloc.in_use == 0


def test_resume_after_eviction_hits_own_prefix(model):
    """A preempted request's indexed prompt chunks survive its
    eviction, so its teacher-forced resume re-prefills only the
    uncached tail — visible as fewer prefill tokens than two full
    prompts."""
    cfg, params = model
    rng = np.random.default_rng(19)
    p_hi = jnp.asarray(rng.integers(0, cfg.vocab_size, 5), jnp.int32)
    p_lo = jnp.asarray(rng.integers(0, cfg.vocab_size, 8), jnp.int32)
    engine = Engine(cfg, params, _scfg(batch=1, prefix_cache=True,
                                       alloc_mode="incremental",
                                       num_pages=5))
    lo = engine.submit(p_lo, 8)
    hi = engine.submit(p_hi, 4, arrival=0.01, priority=5)
    done = engine.run()
    assert done[lo].preemptions >= 1
    # lo prefilled 8 fresh + resumed via its cached 2 full chunks: the
    # resume's suffix is < 8 tokens
    assert engine.prefill_tokens < 8 + len(p_hi) + 8
    engine.release_prefix_cache()
    assert engine.allocator.in_use == 0


# ---------------------------------------------------------------------------
# Config gating + workload plumbing
# ---------------------------------------------------------------------------

def test_exact_length_prefill_shared_prefix_bitmatch(model):
    """prefill_len=0 (exact-length prefill, the ServeConfig default):
    the suffix buffer must pad to the FULL prompt length so the context
    splice spans every cached key position — regression for the short
    sfx_len buffer that rolled the fresh keys off the end."""
    cfg, params = model
    prompts = _shared_prompts(cfg.vocab_size, seed=21)
    _, want = _drive(cfg, params, prompts, [4, 4], _scfg(prefill_len=0))
    engine, got = _drive(cfg, params, prompts, [4, 4],
                         _scfg(prefill_len=0, prefix_cache=True))
    assert [r.tokens for r in got] == [r.tokens for r in want]
    assert engine.stats["prefix_hits"] == 1
    engine.release_prefix_cache()
    assert engine.allocator.in_use == 0


def test_prefix_cache_rejects_mamba_and_int8_kv():
    cfg = reduced(get_config("jamba-v0.1-52b"))
    params = model_init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="mamba"):
        Engine(cfg, params, _scfg(prefix_cache=True))
    cfg = reduced(get_config("yi-6b")).replace(kv_cache_dtype="int8")
    params = model_init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="int8"):
        Engine(cfg, params, _scfg(prefix_cache=True))


def test_workload_shared_prefix_reports_hit_rate(model):
    from repro.serve import run_timed_workload
    cfg, params = model
    engine = Engine(cfg, params, _scfg(batch=2, max_len=24,
                                       prefix_cache=True))
    r = run_timed_workload(engine, cfg.vocab_size, requests=6,
                           prompt_budget=8, new_tokens=3,
                           shared_prefix=1.0)
    assert r["prefix_hit_rate"] > 0.0
    assert r["prefill_tokens"] > 0

    with pytest.raises(ValueError, match="shared_prefix"):
        run_timed_workload(engine, cfg.vocab_size, requests=2,
                           prompt_budget=8, new_tokens=2,
                           shared_prefix=1.5)
