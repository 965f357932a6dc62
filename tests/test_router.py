"""DP serve router: least-loaded placement honoring priorities and
prefix-cache affinity, fleet-level stat aggregation, and bit-match of
routed greedy streams against the single-engine baseline.

Everything here runs on the one real CPU device — the router's engine
replicas share it (TP sharding and one-replica-per-device placement
have their own forced-host subprocess tests in test_mesh_serve.py).
Dense quant throughout the bit-match cases: w8a8 activation scales are
per-tensor over the batch, so changing which requests a replica
co-batches (the whole point of placement) would legitimately shift
quantized streams.
"""

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import model_init
from repro.serve import Engine, Router, ServeConfig
from repro.serve.workload import _pct, run_timed_workload


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("yi-6b"))
    params = model_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _scfg(**over):
    kw = dict(batch=2, max_len=16, prefill_len=8, decode_chunk=3,
              page_size=4, alloc_mode="incremental")
    kw.update(over)
    return ServeConfig(**kw)


def _prompts(vocab, n=6, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, rng.integers(3, 8)) for _ in range(n)]


def test_router_streams_bitmatch_single_engine(model):
    """The fleet is observationally one engine: every routed greedy
    stream equals the solo engine's for the same submissions, keyed by
    the router's global ids."""
    cfg, params = model
    prompts = _prompts(cfg.vocab_size)

    solo = Engine(cfg, params, _scfg())
    ids = [solo.submit(p, 6) for p in prompts]
    solo_done = solo.run()
    want = [solo_done[i].tokens for i in ids]

    router = Router(cfg, params, _scfg(), replicas=2)
    gids = [router.submit(p, 6) for p in prompts]
    done = router.run()
    assert [done[g].tokens for g in gids] == want
    assert router.leaked_pages() == 0
    assert router.compile_counts == {"prefill": 1, "decode_chunk": 1}


def test_router_jsq_spreads_uniform_arrivals(model):
    """Simultaneous arrivals on an idle fleet split evenly — the
    join-shortest-queue key counts queued plus running requests."""
    cfg, params = model
    router = Router(cfg, params, _scfg(), replicas=2)
    for p in _prompts(cfg.vocab_size, n=8, seed=1):
        router.submit(p, 4)
    router.run()
    assert router.placements == [4, 4]
    st = router.stats
    assert st["dp_replicas"] == 2
    assert sum(st["placements"]) == 8
    assert [r["placed"] for r in st["per_replica"]] == [4, 4]


def test_router_places_high_priority_first(model):
    """With both classes queued at t=0, the router's own priority
    queue hands the high-priority request to a replica first, even
    though the low-priority one was submitted earlier."""
    cfg, params = model
    router = Router(cfg, params, _scfg(), replicas=2)
    lo = router.submit(_prompts(cfg.vocab_size)[0], 4, priority=0)
    hi = router.submit(_prompts(cfg.vocab_size)[1], 4, priority=1)
    router.run()
    assert router.placement_order[:2] == [hi, lo]


def test_router_prefix_affinity_follows_cached_pages(model):
    """A request whose prompt head is already cached on one replica
    routes there over the JSQ tiebreak — driven in two drain cycles so
    the affinity decision sees a populated index, with no wall-clock
    dependence."""
    cfg, params = model
    rng = np.random.default_rng(5)
    head = rng.integers(0, cfg.vocab_size, 4)          # one full page
    mk = lambda: np.concatenate(
        [head, rng.integers(0, cfg.vocab_size, 3)])

    router = Router(cfg, params, _scfg(prefix_cache=True), replicas=2)
    router.submit(mk(), 4)
    router.run()                     # cycle 1: seeds one replica's index
    seeded = router.placements.index(1)
    for _ in range(3):
        router.submit(mk(), 4)
    router.run()                     # cycle 2: all follow the cache
    assert router.placements[seeded] == 4
    assert router.affinity_hits[seeded] == 3
    st = router.stats
    assert st["per_replica"][seeded]["affinity_hit_rate"] == 0.75
    router.release_prefix_cache()
    assert router.leaked_pages() == 0


@pytest.mark.parametrize("replicas,n_dev,want", [
    (1, 4, [0]), (4, 4, [0, 1, 2, 3]), (3, 2, [0, 1, 0]),
    (8, 4, [0, 1, 2, 3, 0, 1, 2, 3]), (2, 1, [0, 0])])
def test_unmeshed_replicas_take_devices_round_robin(replicas, n_dev, want):
    """tp=1 replicas get one device each, round-robin over the devices
    given: more replicas than devices spread over all of them instead
    of piling onto the first."""
    devices = [f"dev{i}" for i in range(n_dev)]
    groups = Router._device_groups(_scfg(), replicas, devices)
    assert groups == [[devices[i]] for i in want]


def test_router_rejects_bad_sizing(model):
    cfg, params = model
    with pytest.raises(ValueError, match="replicas must be >= 1"):
        Router(cfg, params, _scfg(), replicas=0)
    # every tp=2 replica needs its own disjoint 2-device group; ask for
    # one group more than the process can seat and the router must
    # refuse rather than oversubscribe shards.  (Relative to
    # jax.device_count() because the count is process-global state: 1
    # standalone, but importing repro.launch.dryrun anywhere earlier in
    # the pytest run forces a 512-device host platform.)
    too_many = jax.device_count() // 2 + 1
    with pytest.raises(ValueError, match="devices"):
        Router(cfg, params, _scfg(tp=2), replicas=too_many)


def test_workload_driver_runs_a_router_fleet(model):
    """run_timed_workload drives a Router unchanged: per-replica
    warmup keeps the compile pins at one per stage, the result rows
    carry the fleet topology, and the pool drains leak-free."""
    cfg, params = model
    router = Router(cfg, params, _scfg(prefix_cache=True), replicas=2)
    r = run_timed_workload(router, cfg.vocab_size, requests=6,
                           prompt_budget=8, new_tokens=4,
                           shared_prefix=0.5)
    assert r["dp_replicas"] == 2
    # replicas take devices round-robin, so two replicas span two devices
    # where there are two, else share the one (jax.device_count() is 1
    # standalone, 512 if repro.launch.dryrun was imported earlier)
    assert r["device_count"] == min(2, jax.device_count())
    assert r["mesh_shape"] == [1, 1]
    assert len(r["per_replica"]) == 2
    assert sum(p["placed"] for p in r["per_replica"]) == 6
    assert r["compile_counts"] == {"prefill": 1, "decode_chunk": 1}
    # the engines' scheduler counters, summed over the fleet: every
    # placement dispatched the slot budget's rows, every token after the
    # first was decoded
    assert r["prefill_rows"] >= 6 * 8
    assert r["decode_rows"] >= 6 * (4 - 1)
    assert r["queue_wait_s"] >= 0.0
    router.release_prefix_cache()
    assert router.leaked_pages() == 0


def test_jsq_load_counts_the_running_requests(model):
    """A replica's load is its queue plus ``Engine.running()``: the
    requests placed in its slots."""
    cfg, params = model
    router = Router(cfg, params, _scfg(), replicas=2)
    for p in _prompts(cfg.vocab_size, n=3, seed=2):
        router.submit(p, 8)          # more than the first step decodes
    router.start()
    router.step()
    running = [eng.running() for eng in router.replicas]
    assert sorted(map(len, running)) == [1, 2]
    assert all(r.tokens for rs in running for r in rs)
    assert [router._load(eng) for eng in router.replicas] == \
        [len(rs) for rs in running]
    router.run()
    assert not any(eng.running() for eng in router.replicas)


def test_workload_priority_split_survives_empty_class(model):
    """priority_mix=1.0 makes every request high priority; the low
    class is empty and its percentile must come back None (a stable
    schema), not NaN or a crash."""
    cfg, params = model
    engine = Engine(cfg, params, _scfg())
    r = run_timed_workload(engine, cfg.vocab_size, requests=3,
                           prompt_budget=8, new_tokens=4,
                           priority_mix=1.0)
    assert r["lo_req_p50_ms"] is None
    assert r["hi_req_p50_ms"] is not None


def test_pct_helper_nan_safe():
    assert _pct(np.asarray([]), 50) is None
    assert _pct(None, 99) is None
    assert _pct(np.asarray([1.0, 3.0]), 50) == 2.0
