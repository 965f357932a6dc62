"""Paged KV cache: allocator invariants, page-table attention parity
(the paged engine must BIT-match each sequence decoded alone over its
unpaged cache), recompile-free recycling, admission backpressure, and
the priority scheduler."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import model_init
from repro.serve import Engine, PageAllocator, PageTable, ServeConfig
from repro.serve.paging import pages_needed
from test_serve import _solo_greedy


def _setup(quant="dense", **cfg_over):
    cfg = reduced(get_config("yi-6b")).replace(quant_mode=quant, **cfg_over)
    params = model_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _run_stream(cfg, params, prompts, n_new, **scfg_over):
    kw = dict(batch=3, max_len=16, prefill_len=8, decode_chunk=3,
              page_size=4)
    kw.update(scfg_over)
    engine = Engine(cfg, params, ServeConfig(**kw))
    ids = [engine.submit(p, n_new) for p in prompts]
    done = engine.run()
    return engine, [done[i].tokens for i in ids]


# ---------------------------------------------------------------------------
# Allocator + table units
# ---------------------------------------------------------------------------

def test_allocator_alloc_free_reuse():
    a = PageAllocator(8, reserved=1)
    assert a.capacity == 7 and a.available == 7 and a.in_use == 0
    p1 = a.alloc(3)
    assert len(p1) == 3 and len(set(p1)) == 3
    assert 0 not in p1                     # reserved trash page stays home
    assert a.available == 4 and a.in_use == 3
    a.free(p1)
    assert a.available == 7 and a.in_use == 0
    # LIFO: the freshly freed pages come back first
    p2 = a.alloc(3)
    assert set(p2) == set(p1)


def test_allocator_exhaustion_backpressure():
    a = PageAllocator(4, reserved=1)
    got = a.alloc(3)
    assert got is not None
    assert a.alloc(1) is None              # None, not an exception: defer
    assert a.available == 0
    a.free(got[:1])
    assert a.alloc(1) is not None


def test_allocator_double_free_raises():
    a = PageAllocator(4, reserved=1)
    pages = a.alloc(2)
    a.free(pages)
    with pytest.raises(ValueError, match="not currently allocated"):
        a.free(pages)
    with pytest.raises(ValueError, match="not currently allocated"):
        a.free([0])                        # reserved page was never handed out


def test_pages_needed():
    assert pages_needed(0, 4) == 0
    assert pages_needed(1, 4) == 1
    assert pages_needed(4, 4) == 1
    assert pages_needed(5, 4) == 2


def test_page_table_assign_clear():
    t = PageTable(batch=2, max_pages=4, trash_page=0)
    t.assign(0, [5, 7])
    np.testing.assert_array_equal(t.row(0), [5, 7, 0, 0])
    np.testing.assert_array_equal(t.row(1), [0, 0, 0, 0])
    t.clear(0)
    np.testing.assert_array_equal(t.row(0), [0, 0, 0, 0])
    with pytest.raises(ValueError, match="exceed"):
        t.assign(0, [1, 2, 3, 4, 5])


def test_page_table_rejects_corrupting_ids():
    """assign/extend must refuse out-of-pool, reserved, duplicate and
    cross-slot-aliased page ids — the silent-corruption class where a
    buggy caller points two slots' decode writes at one page."""
    t = PageTable(batch=2, max_pages=4, trash_page=0, num_pages=8,
                  reserved=1)
    with pytest.raises(ValueError, match="out of pool range"):
        t.assign(0, [8])
    with pytest.raises(ValueError, match="out of pool range"):
        t.assign(0, [-1])
    with pytest.raises(ValueError, match="reserved"):
        t.assign(0, [0, 2])                # trash page as a live page
    with pytest.raises(ValueError, match="duplicate"):
        t.assign(0, [3, 3])
    with pytest.raises(ValueError, match="out of range"):
        t.assign(5, [2])
    t.assign(0, [3, 4])
    with pytest.raises(ValueError, match="already live in slot 0"):
        t.assign(1, [4, 5])                # aliases slot 0's live page
    t.clear(0)
    t.assign(1, [4, 5])                    # fine once slot 0 released it


def test_page_table_extend_grows_live_prefix():
    t = PageTable(batch=2, max_pages=3, trash_page=0, num_pages=8,
                  reserved=1)
    t.assign(0, [2])
    assert t.live_len(0) == 1
    t.extend(0, [3])
    np.testing.assert_array_equal(t.row(0), [2, 3, 0])
    assert t.live_len(0) == 2
    with pytest.raises(ValueError, match="already live in slot 0"):
        t.extend(0, [2])                   # duplicate within own row
    with pytest.raises(ValueError, match="already live in slot 0"):
        t.extend(1, [3])                   # cross-slot alias
    with pytest.raises(ValueError, match="exceeds the per-slot"):
        t.extend(0, [4, 5])                # 2 + 2 > max_pages 3
    t.clear(0)
    assert t.live_len(0) == 0


def test_page_table_truncate_returns_tail_and_repoints_trash():
    t = PageTable(batch=2, max_pages=4, trash_page=0, num_pages=8,
                  reserved=1)
    t.assign(0, [2, 3, 4])
    removed = t.truncate(0, 1)
    assert removed == [3, 4]               # tail pages, table order
    np.testing.assert_array_equal(t.row(0), [2, 0, 0, 0])
    assert t.live_len(0) == 1
    # the trash entries are dead, not live: extending re-grows from the
    # truncation point
    t.extend(0, [5])
    np.testing.assert_array_equal(t.row(0), [2, 5, 0, 0])


def test_page_table_truncate_noop_and_validation():
    t = PageTable(batch=1, max_pages=3, trash_page=0, num_pages=8,
                  reserved=1)
    t.assign(0, [2, 3])
    assert t.truncate(0, 2) == []          # keep >= live: nothing freed
    assert t.truncate(0, 5) == []
    assert t.live_len(0) == 2
    with pytest.raises(ValueError, match="cannot truncate"):
        t.truncate(0, -1)
    assert t.truncate(0, 0) == [2, 3]      # full rollback of the row
    assert t.live_len(0) == 0


def test_truncate_free_cycle_no_leak_no_trash_violation():
    # rollback protocol: truncate the table, free exactly the removed
    # ids — the pool must return to balance and the trash page must
    # never enter the free list
    a = PageAllocator(8, reserved=1)
    t = PageTable(batch=1, max_pages=6, trash_page=0, num_pages=8,
                  reserved=1)
    pages = a.alloc(5)
    t.assign(0, pages)
    removed = t.truncate(0, 2)
    assert removed == pages[2:]
    a.free(removed)
    assert a.in_use == 2 and a.available == 5
    with pytest.raises(ValueError):        # freed tail cannot double-free
        a.free(removed[:1])
    assert 0 not in a.alloc(5)             # trash page still reserved


def test_truncate_preserves_refcounted_shared_pages():
    # a rollback in one slot must never free pages another holder still
    # shares (prefix-cache pages sit below any rollback target, but the
    # allocator-level invariant is what guarantees it)
    a = PageAllocator(8, reserved=1)
    t = PageTable(batch=2, max_pages=4, trash_page=0, num_pages=8,
                  reserved=1)
    shared = a.alloc(1)
    a.share(shared)                        # second holder
    priv = a.alloc(2)
    t.assign(0, shared + priv, shared=set(shared))
    t.assign(1, shared, shared=set(shared))  # other slot, read-only
    removed = t.truncate(0, 1)             # roll slot 0 back to shared
    assert removed == priv
    a.free(removed)
    a.free(shared)                         # slot 0's reference
    assert a.in_use == 1                   # survives for slot 1
    np.testing.assert_array_equal(t.row(1)[:1], shared)
    a.free(shared)
    assert a.in_use == 0


# ---------------------------------------------------------------------------
# Paged decode parity: BIT-identical to each sequence decoded alone
# ---------------------------------------------------------------------------

# id -> (arch, model overrides, slots).  w8a8 cases serve one slot: the
# per-tensor activation scale depends on which rows share a dispatch.
# So do MoE cases: routed capacity drops depend on the batch.  A
# one-slot case also prefills at exact length, as the reference does:
# pad rows share the prefill dispatch the same way.
_PARITY = {
    "yi-dense-xla": ("yi-6b", {}, 3),
    "yi-dense-pallas": ("yi-6b", {"quant_backend": "pallas"}, 3),
    "yi-w8a8-xla": ("yi-6b", {"quant_mode": "w8a8_nibble"}, 1),
    "yi-w8a8-pallas": ("yi-6b", {"quant_mode": "w8a8_nibble",
                                 "quant_backend": "pallas"}, 1),
    "yi-int8-kv": ("yi-6b", {"kv_cache_dtype": "int8"}, 2),
    "deepseek-mla": ("deepseek-v3-671b", {}, 1),
    "jamba-hybrid": ("jamba-v0.1-52b", {}, 1),
    "qwen3-dense": ("qwen3-4b", {}, 3),
    "qwen3-w8a8": ("qwen3-4b", {"quant_mode": "w8a8_nibble"}, 1),
    "gemma3-dense": ("gemma3-1b", {}, 3),
    "gemma3-w8a8": ("gemma3-1b", {"quant_mode": "w8a8_nibble"}, 1),
    "llama4-moe-dense": ("llama4-maverick-400b-a17b", {}, 1),
    "llama4-moe-w8a8": ("llama4-maverick-400b-a17b",
                        {"quant_mode": "w8a8_nibble"}, 1),
    "mamba2-dense": ("mamba2-780m", {}, 3),
    "mamba2-w8a8": ("mamba2-780m", {"quant_mode": "w8a8_nibble"}, 1),
}


@pytest.mark.parametrize("case", list(_PARITY))
def test_paged_matches_solo_reference(case):
    """Requests served through the paged engine (pool scatter, gather
    through the page table, slot refill) emit, bit for bit, the greedy
    stream of each sequence decoded alone at scalar positions over its
    own unpaged cache — across backends, quant modes, the int8 KV cache,
    MLA latent pools, mamba state (exact-length prefill, per-slot SSM
    state next to the pools), local attention and MoE."""
    arch, over, slots = _PARITY[case]
    cfg = reduced(get_config(arch)).replace(**over)
    params = model_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [jnp.asarray(rng.integers(0, cfg.vocab_size, p), jnp.int32)
               for p in (3, 5, 7)]
    engine, got = _run_stream(cfg, params, prompts, 4, batch=slots,
                              prefill_len=8 if slots > 1 else 0)
    want = [_solo_greedy(params, cfg, p, 4, 16) for p in prompts]
    assert got == want, (case, got, want)
    assert engine.allocator.in_use == 0    # every page returned


# ---------------------------------------------------------------------------
# Recycling: refill + page reuse without recompiles or leaks
# ---------------------------------------------------------------------------

def test_paged_refill_no_recompile_no_leak():
    """More requests than slots with mixed lengths/budgets: slots refill
    onto RECYCLED pages (the pool is sized so late requests must reuse
    early requests' pages) with both compiled programs intact, every
    page returned, and output equal to a parity pool's, where no page
    is ever recycled mid-run."""
    cfg, params = _setup()
    rng = np.random.default_rng(1)
    spec = [(4, 6), (8, 3), (5, 7), (6, 1), (3, 5)]
    prompts = [jnp.asarray(rng.integers(0, cfg.vocab_size, p), jnp.int32)
               for p, _ in spec]

    def drive(num_pages):
        engine = Engine(cfg, params, ServeConfig(
            batch=2, max_len=24, prefill_len=8, decode_chunk=4,
            page_size=4, num_pages=num_pages))
        ids = [engine.submit(p, n) for p, (_, n) in zip(prompts, spec)]
        done = engine.run()
        return engine, [done[i].tokens for i in ids]

    _, want = drive(num_pages=0)          # parity pool
    # 13 pages = trash + two concurrent worst-case requests (2 × 6);
    # five requests therefore cannot run without recycling
    engine, got = drive(num_pages=13)
    assert got == want
    assert engine.compile_counts == {"prefill": 1, "decode_chunk": 1}
    assert engine.allocator.in_use == 0
    assert engine.allocator.available == engine.allocator.capacity


def test_paged_admission_backpressure_serializes():
    """A pool that only fits one request at a time: admission defers
    instead of OOMing, every request still completes, and the decode
    stream is unchanged from the roomy-pool run."""
    cfg, params = _setup()
    rng = np.random.default_rng(3)
    prompts = [jnp.asarray(rng.integers(0, cfg.vocab_size, 5), jnp.int32)
               for _ in range(3)]

    def drive(num_pages):
        engine = Engine(cfg, params, ServeConfig(
            batch=3, max_len=16, prefill_len=8, decode_chunk=3,
            page_size=4, num_pages=num_pages))
        ids = [engine.submit(p, 4) for p in prompts]
        done = engine.run()
        return engine, [done[i].tokens for i in ids]

    # pages_for(5 prompt + 4 new) = ceil(8/4) = 2 → capacity 2 fits one
    _, want = drive(num_pages=0)           # roomy parity pool
    engine, got = drive(num_pages=3)
    assert got == want
    assert engine.allocator.in_use == 0


def test_paged_request_too_big_for_pool_raises():
    cfg, params = _setup()
    engine = Engine(cfg, params, ServeConfig(
        batch=1, max_len=16, prefill_len=8, decode_chunk=2,
        page_size=4, num_pages=2))
    with pytest.raises(ValueError, match="pool"):
        engine.submit(jnp.asarray([1, 2, 3, 4, 5], jnp.int32), 8)


def test_paged_cache_rows_scale_with_live_tokens():
    """The HBM claim: a short request reserves only its pages (prompt +
    decode-written rows rounded to page_size), not a max_len slab."""
    from repro.models import init_caches
    from repro.models.transformer import _SEQ_CACHE_KEYS
    cfg, params = _setup()
    engine = Engine(cfg, params, ServeConfig(
        batch=1, max_len=32, prefill_len=8, decode_chunk=2, page_size=4))
    rid = engine.submit(jnp.asarray([1, 2, 3], jnp.int32), 2)
    done = engine.run()
    # 3 prompt rows + 1 decode write = 4 rows → exactly 1 page
    assert done[rid].cache_rows == 4
    assert done[rid].cache_rows < engine.scfg.max_len
    # same per-token bytes as the unpaged (1, max_len) cache: the layout
    # moves rows, not widths
    slab = jax.eval_shape(lambda: init_caches(cfg, 1, 32))
    slab_bytes = sum(
        leaf.size * leaf.dtype.itemsize for path, leaf in
        jax.tree_util.tree_flatten_with_path(slab)[0]
        if path[-1].key in _SEQ_CACHE_KEYS)
    assert engine.cache_token_bytes == slab_bytes // 32


def test_paged_vs_dense_hbm_per_request():
    """Workload-level accounting: cache_kb_per_req sits measurably below
    a max_len slab's per-request share on short requests."""
    from repro.serve import run_timed_workload
    cfg, params = _setup()
    scfg = ServeConfig(batch=2, max_len=32, prefill_len=8, decode_chunk=4,
                       page_size=4)
    engine = Engine(cfg, params, scfg)
    paged = run_timed_workload(engine, cfg.vocab_size, requests=4,
                               prompt_budget=8, new_tokens=4)
    # a batch × max_len slab holds 32 rows per request; the pool books
    # at most ceil(11/4)=3 pages = 12 rows
    slab_kb_per_req = scfg.max_len * engine.cache_token_bytes / 1024
    assert paged["cache_kb_per_req"] < slab_kb_per_req / 2


# ---------------------------------------------------------------------------
# Pallas paged-decode kernel (fast path)
# ---------------------------------------------------------------------------

def test_paged_flash_decode_kernel_matches_gather_reference():
    from repro.kernels.ops import paged_flash_decode
    from repro.models.attention import attention_core, gather_pages
    rng = np.random.default_rng(0)
    b, kvh, g, d, num_pages, ps, mp = 3, 2, 2, 16, 13, 4, 4
    h = kvh * g
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    k_pool = jnp.asarray(rng.standard_normal((num_pages, ps, kvh, d)),
                         jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((num_pages, ps, kvh, d)),
                         jnp.float32)
    table = jnp.asarray(rng.permutation(num_pages)[:b * mp]
                        .reshape(b, mp), jnp.int32)
    q_pos = jnp.asarray([3, 7, 14], jnp.int32)

    out = paged_flash_decode(q, k_pool, v_pool, table, q_pos, scale=0.25)
    k_full = gather_pages(k_pool, table)
    v_full = gather_pages(v_pool, table)
    k_pos = jnp.broadcast_to(jnp.arange(mp * ps)[None], (b, mp * ps))
    ref = attention_core(q, k_full, v_full, q_pos[:, None], k_pos,
                         scale=0.25, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_paged_flash_engine_end_to_end():
    """attn_impl=flash routes paged decode through the page-walking
    Pallas kernel; the engine must still produce the same greedy stream
    as the XLA gather reference (same math, flash summation order —
    greedy argmax is stable across the two on this model)."""
    cfg, params = _setup(attn_impl="flash")
    rng = np.random.default_rng(4)
    prompts = [jnp.asarray(rng.integers(0, cfg.vocab_size, p), jnp.int32)
               for p in (3, 6)]
    ref_cfg, _ = _setup()                  # chunked reference
    _, want = _run_stream(ref_cfg, params, prompts, 4, batch=2)
    _, got = _run_stream(cfg, params, prompts, 4, batch=2)
    assert got == want


# ---------------------------------------------------------------------------
# Priority scheduler
# ---------------------------------------------------------------------------

def test_priority_orders_admission():
    """With one slot, the high-priority request is admitted first even
    though it was submitted last."""
    cfg, params = _setup()
    engine = Engine(cfg, params, ServeConfig(batch=1, max_len=16,
                                             prefill_len=8, decode_chunk=2))
    rng = np.random.default_rng(5)
    lo = [engine.submit(jnp.asarray(rng.integers(0, cfg.vocab_size, 4),
                                    jnp.int32), 3) for _ in range(2)]
    hi = engine.submit(jnp.asarray(rng.integers(0, cfg.vocab_size, 4),
                                   jnp.int32), 3, priority=5)
    done = engine.run()
    assert done[hi].t_first < min(done[i].t_first for i in lo)
    # equal-priority requests keep FIFO order (arrival, then submission)
    assert done[lo[0]].t_first < done[lo[1]].t_first


def test_priority_aging_prevents_starvation():
    """_PriorityQueue unit: with aging, a long-waiting low-priority
    request eventually outranks a fresh high-priority one."""
    from repro.serve.engine import _PriorityQueue, Request

    def req(rid, prio, arrival):
        return Request(id=rid, prompt=np.zeros(1, np.int32),
                       max_new_tokens=1, arrival=arrival, priority=prio)

    q = _PriorityQueue(aging_s=1.0)
    q.push(req(0, 0, arrival=0.0))
    q.push(req(1, 3, arrival=9.5))
    # at t=10 the low-priority request has aged +10 levels > 3
    assert q.pop(10.0).id == 0
    assert q.pop(10.0).id == 1

    q2 = _PriorityQueue(aging_s=0.0)       # aging off: strict priority
    q2.push(req(0, 0, arrival=0.0))
    q2.push(req(1, 3, arrival=0.0))
    assert q2.pop(10.0).id == 1

    # arrival gating: the future request is invisible
    q3 = _PriorityQueue()
    q3.push(req(0, 5, arrival=99.0))
    q3.push(req(1, 0, arrival=0.0))
    assert q3.pop(1.0).id == 1
    assert q3.pop(1.0) is None


def test_priority_backpressure_veto_keeps_request():
    from repro.serve.engine import _PriorityQueue, Request
    q = _PriorityQueue()
    r = Request(id=0, prompt=np.zeros(1, np.int32), max_new_tokens=1)
    q.push(r)
    assert q.pop(0.0, admit=lambda _: False) is None
    assert len(q) == 1                     # vetoed, not dropped
    assert q.pop(0.0).id == 0


# ---------------------------------------------------------------------------
# Workload input validation (serve/workload.py bugfix)
# ---------------------------------------------------------------------------

def test_workload_validates_inputs():
    from repro.serve import run_timed_workload

    class _StubEngine:                     # never reached past validation
        pass

    with pytest.raises(ValueError, match="requests must be >= 1"):
        run_timed_workload(_StubEngine(), 256, requests=0,
                           prompt_budget=8, new_tokens=4)
    with pytest.raises(ValueError, match="prompt_budget must be >= 2"):
        run_timed_workload(_StubEngine(), 256, requests=4,
                           prompt_budget=1, new_tokens=4)
    with pytest.raises(ValueError, match="new_tokens"):
        run_timed_workload(_StubEngine(), 256, requests=4,
                           prompt_budget=8, new_tokens=0)
    with pytest.raises(ValueError, match="priority_mix"):
        run_timed_workload(_StubEngine(), 256, requests=4,
                           prompt_budget=8, new_tokens=4,
                           priority_mix=1.5)


# ---------------------------------------------------------------------------
# Engine config validation
# ---------------------------------------------------------------------------

def test_paged_engine_validates_page_geometry():
    from repro.launch.serve import build_parser
    cfg, params = _setup()
    with pytest.raises(ValueError, match="multiple of page_size"):
        Engine(cfg, params, ServeConfig(batch=1, max_len=18, page_size=4))
    # the pool is the one layout: the launcher refuses any other
    for mode in ("sparse", "dense"):
        with pytest.raises(SystemExit):
            build_parser().parse_args(["--cache-mode", mode])
    assert build_parser().parse_args([]).cache_mode == "paged"
