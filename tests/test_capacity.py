"""Tier-1 gate for the analytic capacity model (``repro.capacity``):
closed-form predictor units with hand-computed expectations,
monotonicity properties of the knob space, the autotuner's
admissibility logic, the analytic-vs-engine cache-bytes cross-check,
and the model-vs-measured replay of every committed
``benchmarks/BENCH_serve.json`` row — the same check
``tools/autotune.py --validate`` runs, so a model change that breaks
agreement with the committed measurements fails here first."""

import os
import sys

import pytest

from repro.capacity import (
    CapacityError,
    Knobs,
    StageCosts,
    WorkloadShape,
    analytic_cache_token_bytes,
    expected_tokens_per_round,
    predict,
)
from repro.capacity.validate import (
    TOLERANCE,
    load_bench,
    validate_rows,
)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
import spec_report  # noqa: E402

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "BENCH_serve.json")

# prompt_budget=2 pins every drawn prompt length to exactly 2 tokens
# (lengths are uniform in [max(2, budget // 2), budget]), which is what
# makes the closed forms below exact rather than distributional
_SHAPE2 = dict(prompt_budget=2, stagger_s=0.0)
_COSTS = StageCosts(prefill_s=0.01, decode_chunk_s=0.004)


# ---------------------------------------------------------------------------
# Closed-form units
# ---------------------------------------------------------------------------

def test_zero_arrival_batch_closed_form():
    """Two simultaneous requests, parity pool: two serialized prefills
    (t=0.01, 0.02), then two batched decode chunks of 4 steps cover the
    remaining 8 tokens each — wall 0.028 s for 18 tokens."""
    shape = WorkloadShape(requests=2, new_tokens=9, **_SHAPE2)
    knobs = Knobs(batch=2, max_len=16, decode_chunk=4)
    r = predict(knobs, shape, _COSTS)
    assert r["feasible"]
    assert r["tokens"] == 18
    assert r["decode_chunks"] == 2
    assert r["preemptions"] == 0
    assert r["wall_s"] == pytest.approx(0.028)
    assert r["tok_per_s"] == pytest.approx(18 / 0.028)
    # TTFTs are 10 ms and 20 ms; the interpolated p50 is their midpoint
    assert r["ttft_p50_ms"] == pytest.approx(15.0)
    assert r["ttft_p99_ms"] == pytest.approx(19.9)


def test_single_stream_decode_closed_form():
    """One request decoding alone: prefill emits token 1, two 8-step
    chunks emit the other 16 — wall 0.018 s, TTFT exactly the prefill
    latency."""
    shape = WorkloadShape(requests=1, new_tokens=17, **_SHAPE2)
    knobs = Knobs(batch=2, max_len=32, decode_chunk=8)
    r = predict(knobs, shape, _COSTS)
    assert r["feasible"]
    assert r["tokens"] == 17
    assert r["decode_chunks"] == 2
    assert r["wall_s"] == pytest.approx(0.018)
    assert r["tok_per_s"] == pytest.approx(17 / 0.018)
    assert r["ttft_p50_ms"] == pytest.approx(10.0)
    assert r["ttft_p99_ms"] == pytest.approx(10.0)


def test_saturated_pool_serializes_closed_form():
    """A reserve-mode pool holding exactly one placement (capacity 3 =
    pages_needed(2 + 9 - 1)) serializes two requests: the second admits
    only after the first frees its pages, so the wall doubles."""
    shape = WorkloadShape(requests=2, new_tokens=9, **_SHAPE2)
    knobs = Knobs(batch=2, max_len=16, decode_chunk=4,
                  page_size=4, num_pages=4, alloc_mode="reserve")
    r = predict(knobs, shape, _COSTS)
    assert r["feasible"]
    assert r["tokens"] == 18
    assert r["preemptions"] == 0
    # each request alone: prefill 0.01 + two 4-step chunks 0.008
    assert r["wall_s"] == pytest.approx(0.036)
    assert r["ttft_p99_ms"] == pytest.approx(28.0, rel=0.01)
    assert r["pool_pages"] == 4


def test_pool_too_small_raises():
    """A request that can never fit the pool is a submit-time
    CapacityError (mirroring Engine.validate), not a silent stall."""
    shape = WorkloadShape(requests=1, new_tokens=9, **_SHAPE2)
    knobs = Knobs(batch=2, max_len=16, decode_chunk=4,
                  page_size=4, num_pages=2, alloc_mode="reserve")
    with pytest.raises(CapacityError, match="pool"):
        predict(knobs, shape, _COSTS)


def test_spec_emission_matches_geometric_model():
    """Speculative prediction integerizes the geometric closed form
    exactly: total emitted tokens equal the request budgets, and the
    round count tracks new_tokens / E[tokens per round]."""
    alpha, k = 0.8, 4
    shape = WorkloadShape(requests=2, new_tokens=17, **_SHAPE2)
    knobs = Knobs(batch=2, max_len=32, decode_chunk=8,
                  page_size=4, spec_decode=True, spec_k=k)
    costs = StageCosts(prefill_s=0.01, draft_s=0.002, verify_s=0.004)
    r = predict(knobs, shape, costs, acceptance=alpha)
    assert r["feasible"]
    assert r["tokens"] == 34
    e = expected_tokens_per_round(alpha, k)
    per_req_rounds = 16 / e          # 16 post-prefill tokens each
    assert r["spec_rounds"] == pytest.approx(per_req_rounds, abs=1.5)
    assert 1.0 <= r["tokens_per_step"] <= k + 1


# ---------------------------------------------------------------------------
# Monotonicity properties
# ---------------------------------------------------------------------------

def test_more_pages_never_lower_tok_s():
    """Growing the page pool (all else fixed) never lowers predicted
    throughput — backpressure and preemptions can only relax."""
    shape = WorkloadShape(requests=8, prompt_budget=8, new_tokens=8)
    costs = StageCosts(prefill_s=0.005, decode_chunk_s=0.002,
                       overhead_s=0.0005)
    prev = 0.0
    for pages in (9, 13, 17, 25, 33, 65):
        r = predict(Knobs(batch=4, max_len=32, decode_chunk=4,
                          page_size=4, num_pages=pages,
                          alloc_mode="incremental"),
                    shape, costs)
        assert r["feasible"], pages
        assert r["tok_per_s"] >= prev - 1e-9, pages
        prev = r["tok_per_s"]


def test_larger_decode_chunk_never_worse_throughput():
    """With an affine chunk cost (per-step work plus fixed dispatch
    overhead), a larger decode_chunk amortizes the overhead over more
    steps and predicted throughput is non-decreasing."""
    shape = WorkloadShape(requests=4, prompt_budget=8, new_tokens=16)
    prev = 0.0
    for dc in (1, 2, 4, 8, 16):
        costs = StageCosts(prefill_s=0.005,
                           decode_chunk_s=0.001 * dc + 0.0005)
        r = predict(Knobs(batch=4, max_len=32, decode_chunk=dc),
                    shape, costs)
        assert r["feasible"], dc
        assert r["tok_per_s"] >= prev - 1e-9, dc
        prev = r["tok_per_s"]


# ---------------------------------------------------------------------------
# Autotuner admissibility
# ---------------------------------------------------------------------------

def test_autotune_search_objectives():
    from repro.capacity.tune import knob_grid, search
    from repro.configs import get_config, reduced

    cfg = reduced(get_config("yi-6b"))
    shape = WorkloadShape(requests=4, prompt_budget=8, new_tokens=8)
    cells = knob_grid(shape, batch=2, max_len=32, prefill_len=8,
                      small=True)
    assert len(cells) == len(set(cells)), "grid must be duplicate-free"

    results, winner = search(cfg, shape, cells,
                             objective="max-tok-s", ttft_slo_ms=None,
                             alpha=0.8)
    assert winner is not None and winner["admissible"]
    best = max(r["prediction"]["tok_per_s"] for r in results
               if r["admissible"])
    assert winner["prediction"]["tok_per_s"] == pytest.approx(best)

    results, winner = search(cfg, shape, cells,
                             objective="min-pages", ttft_slo_ms=None,
                             alpha=0.8)
    assert winner is not None
    assert winner["prediction"]["preemptions"] == 0
    # no admissible cell has a smaller pool
    for r in results:
        if r["admissible"]:
            assert (r["knobs"].resolved_num_pages
                    >= winner["knobs"].resolved_num_pages)


# ---------------------------------------------------------------------------
# Analytic cache bytes vs the live engine
# ---------------------------------------------------------------------------

def test_analytic_cache_bytes_match_engine():
    import jax
    from repro.configs import get_config, reduced
    from repro.models import model_init
    from repro.serve import Engine, ServeConfig

    cfg = reduced(get_config("yi-6b"))
    params = model_init(jax.random.PRNGKey(0), cfg)
    engine = Engine(cfg, params, ServeConfig(
        batch=2, max_len=16, prefill_len=8, page_size=4))
    assert analytic_cache_token_bytes(cfg) == int(
        engine.cache_token_bytes)


# ---------------------------------------------------------------------------
# Model-vs-measured: the committed bench is a regression fixture
# ---------------------------------------------------------------------------

def test_bench_predictions_within_tolerance():
    """Replay every committed row's prediction from its embedded
    calibration blob and hold the gated rows to the documented
    tolerance — identical to ``tools/autotune.py --validate``."""
    ok, checks = validate_rows(load_bench(BENCH))
    gated = [c for c in checks if c["gated"]]
    assert len(gated) >= 20, "the gated regression surface shrank"
    drifted = [
        (c["workload"], c["quant"], c["backend"], c["alloc"], c["tail"],
         name, m)
        for c in gated for name, m in c["metrics"].items()
        if not m["ok"]]
    assert ok and not drifted, drifted


def test_bench_gating_covers_expected_cells():
    """The gate must span the scheduler paths the model claims:
    arrival modes, both quant paths, spec decode and the swap tail."""
    _, checks = validate_rows(load_bench(BENCH))
    gated = [c for c in checks if c["gated"]]
    assert {c["workload"] for c in gated} >= {
        "uniform", "staggered", "overcommit", "bursty", "burst_tail"}
    assert any(c["spec"] == "on" for c in gated)
    assert any(c["tail"] == "on" for c in gated)
    # multi-device and prefix-cache rows never gate (unmodeled)
    for c in checks:
        if c["workload"] == "mesh":
            assert not c["gated"]


def test_tolerance_policy_shape():
    """The documented policy: both metrics bounded, TTFT carries an
    absolute floor so millisecond rows don't fail on jitter."""
    assert set(TOLERANCE) == {"tok_per_s", "ttft_p50_ms"}
    rel, floor = TOLERANCE["ttft_p50_ms"]
    assert floor > 0.0
    assert 0.0 < rel < 1.0


# ---------------------------------------------------------------------------
# spec_report --bench promotion (satellite of the capacity gate)
# ---------------------------------------------------------------------------

def test_spec_report_bench_validation_passes():
    """The spec-report acceptance check — measured acceptance_rate vs
    the acceptance implied by tokens_per_step through the shared
    geometric model — holds on the committed bench."""
    lines, ok = spec_report.validate_bench(BENCH)
    assert ok, "\n".join(lines)
    assert any("OK" in line for line in lines)
