"""Self-speculative decoding: the nibble-quantized program drafts,
ONE dense multi-token forward verifies.  Greedy spec streams must
BIT-match the non-spec dense engine token-for-token (across the quant ×
backend grid, and across a preemption mid-stream), rollback must be a
pure page-table operation (zero leaks after drain), the compiled
program set must stay pinned at one draft + one verify, and the
``tools/spec_report.py`` planning model must agree with itself.

Satellite: the index-derived per-slot RNG makes *sampled* (non-spec)
streams bit-stable under evict-and-resume too — preemption can no
longer fork a temperature stream's future.
"""

import sys
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import spec_split
from repro.models import model_init
from repro.serve import Engine, ServeConfig

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
import spec_report


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


def _drive(cfg, params, prompts, budgets, scfg):
    engine = Engine(cfg, params, scfg)
    ids = [engine.submit(p, n) for p, n in zip(prompts, budgets)]
    done = engine.run()
    return engine, [done[i] for i in ids]


def _prompts(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.integers(0, cfg.vocab_size,
                                     int(rng.integers(3, 7))), jnp.int32)
            for _ in range(n)]


# ---------------------------------------------------------------------------
# Greedy spec ≡ non-spec dense, across the quant × backend grid
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant,backend", [
    ("dense", "xla"), ("dense", "pallas"),
    ("w8a8_nibble", "xla"), ("w8a8_nibble", "pallas"),
])
def test_spec_greedy_bitmatches_dense_engine(model, quant, backend, ):
    """The acceptance contract: whatever drafts the quantized program
    proposes, the emitted greedy stream is exactly the non-spec dense
    engine's — the draft only changes *when* tokens appear, never
    *which*."""
    cfg, params = model
    prompts = _prompts(cfg, 3)
    budgets = [6, 6, 6]
    _, want = _drive(cfg, params, prompts, budgets,
                     _scfg(quant_mode="dense", quant_backend=backend))
    engine, got = _drive(
        cfg, params, prompts, budgets,
        _scfg(quant_mode="dense", quant_backend=backend,
              alloc_mode="incremental", spec_decode=True, spec_k=3,
              spec_quant_mode=quant))
    assert [r.tokens for r in got] == [r.tokens for r in want]
    assert engine.allocator.in_use == 0            # zero page leaks
    assert engine.compile_counts == {"prefill": 1, "decode_chunk": 0,
                                     "draft": 1, "verify": 1}
    st = engine.stats
    assert st["spec_rounds"] > 0
    assert 0.0 <= st["acceptance_rate"] <= 1.0
    assert 1.0 <= st["tokens_per_step"] <= 3 + 1
    # no replay happened, so every token except each request's
    # prefill-emitted first one went through a round
    assert engine.spec_tokens == sum(len(r.tokens) - 1 for r in got)


def test_spec_temperature_drains_and_accounts(model):
    """temperature > 0 exercises the rejection-sampling verify path:
    the run must drain, leak nothing, and keep the accounting coupled
    (every emitted token was emitted by some round)."""
    cfg, params = model
    prompts = _prompts(cfg, 3, seed=5)
    engine, got = _drive(
        cfg, params, prompts, [6, 6, 6],
        _scfg(temperature=0.8, alloc_mode="incremental",
              spec_decode=True, spec_k=3,
              spec_quant_mode="w8a8_nibble"))
    assert all(len(r.tokens) == 6 for r in got)
    assert engine.allocator.in_use == 0
    assert engine.spec_tokens == 15        # 18 emitted − 3 prefill firsts
    assert 0.0 <= engine.stats["acceptance_rate"] <= 1.0


# ---------------------------------------------------------------------------
# Preemption: spec streams resume, sampled non-spec streams stay bit-stable
# ---------------------------------------------------------------------------

def test_spec_stream_preempted_mid_draft_resumes_bitmatch(model):
    """A spec request evicted between speculation rounds resumes via
    prefill + forced-draft replay and must still emit the exact
    non-spec dense stream (forced drafts are force-accepted committed
    history, excluded from acceptance stats)."""
    cfg, params = model
    rng = np.random.default_rng(7)
    lo_p = jnp.asarray(rng.integers(0, cfg.vocab_size, 5), jnp.int32)
    hi_p = jnp.asarray(rng.integers(0, cfg.vocab_size, 4), jnp.int32)
    scfg = _scfg(batch=1, alloc_mode="incremental", spec_decode=True,
                 spec_k=3, spec_quant_mode="w8a8_nibble")

    engine = Engine(cfg, params, scfg)
    lo = engine.submit(lo_p, 7)
    engine._t0 = time.perf_counter()
    engine._admit(0.0)
    assert engine._slots[0] is not None and engine._slots[0].id == lo
    # one speculation round so the victim carries emitted tokens (more
    # than one draft round's worth gets replayed through forced lanes)
    engine._run_spec_round(0.0)
    assert len(engine._slots[0].tokens) >= 1
    proposed_before = engine.spec_proposed
    hi = engine.submit(hi_p, 5, priority=5)
    engine._admit(0.0)                         # full batch: must evict lo
    assert engine._slots[0].id == hi
    assert engine.preemptions == 1
    done = engine.run()
    assert engine.allocator.in_use == 0
    assert done[lo].preemptions == 1

    for rid, prompt, n in ((lo, lo_p, 7), (hi, hi_p, 5)):
        _, (ref,) = _drive(cfg, params, [prompt], [n],
                           _scfg(batch=1, quant_mode="dense"))
        assert done[rid].tokens == ref.tokens, rid
    assert engine.compile_counts == {"prefill": 1, "decode_chunk": 0,
                                     "draft": 1, "verify": 1}
    # replayed tokens never re-enter the acceptance statistics: the
    # fresh-proposal count cannot exceed rounds × k even though the
    # victim's whole stream went through the draft lanes twice
    assert engine.spec_proposed <= engine.spec_rounds * 3
    assert engine.spec_proposed > proposed_before


def test_sampled_stream_bitstable_under_preemption(model):
    """Satellite: the index-derived per-request RNG makes *sampled*
    non-spec streams resume bit-identically after eviction — the draw
    for token i of request r depends only on (r.id, i), not on batch
    composition or how many chunks ran."""
    cfg, params = model
    rng = np.random.default_rng(11)
    lo_p = jnp.asarray(rng.integers(0, cfg.vocab_size, 5), jnp.int32)
    hi_p = jnp.asarray(rng.integers(0, cfg.vocab_size, 4), jnp.int32)

    # uninterrupted reference: both requests sampled side by side
    # (ids 0 and 1, same submission order as the preempted run)
    ref_engine = Engine(cfg, params, _scfg(temperature=0.7))
    r_lo = ref_engine.submit(lo_p, 6)
    r_hi = ref_engine.submit(hi_p, 5)
    ref = ref_engine.run()

    engine = Engine(cfg, params, _scfg(batch=1, temperature=0.7))
    lo = engine.submit(lo_p, 6)
    engine._t0 = time.perf_counter()
    engine._admit(0.0)
    engine._run_chunk(0.0)                     # generate, then get evicted
    hi = engine.submit(hi_p, 5, priority=5)
    engine._admit(0.0)
    assert engine.preemptions == 1
    done = engine.run()

    assert done[lo].tokens == ref[r_lo].tokens
    assert done[hi].tokens == ref[r_hi].tokens
    assert done[lo].preemptions == 1


# ---------------------------------------------------------------------------
# Validation / config plumbing
# ---------------------------------------------------------------------------

def test_spec_split_pins_dense_verifier():
    cfg = reduced(get_config("yi-6b")).replace(quant_mode="w4a8_nibble")
    draft, verify = spec_split(cfg)
    assert draft.quant_mode == "w4a8_nibble"   # deployment drafts itself
    assert verify.quant_mode == "dense"
    draft2, _ = spec_split(cfg, "w8a8_nibble")
    assert draft2.quant_mode == "w8a8_nibble"
    with pytest.raises(ValueError, match="unknown draft quant mode"):
        spec_split(cfg, "int2")


def test_spec_rejects_mamba_and_bad_k(model):
    cfg, params = model
    mcfg = reduced(get_config("mamba2-780m"))
    mparams = model_init(jax.random.PRNGKey(0), mcfg)
    with pytest.raises(ValueError, match="mamba"):
        Engine(mcfg, mparams, ServeConfig(batch=1, max_len=16,
                                          spec_decode=True))
    with pytest.raises(ValueError, match="spec_k"):
        Engine(cfg, params, _scfg(spec_decode=True, spec_k=0))


def test_workload_arrival_mode_validown(model):
    from repro.serve import run_timed_workload
    cfg, params = model
    engine = Engine(cfg, params, _scfg())
    with pytest.raises(ValueError, match="arrival_mode"):
        run_timed_workload(engine, cfg.vocab_size, requests=2,
                           prompt_budget=6, new_tokens=2,
                           arrival_mode="chaotic")


def test_bursty_workload_reports_tail_columns(model):
    """Bursty arrivals + Pareto lengths drain through the spec engine;
    the report must carry the new tail/spec columns."""
    from repro.serve import run_timed_workload
    cfg, params = model
    engine = Engine(cfg, params,
                    _scfg(alloc_mode="incremental", spec_decode=True,
                          spec_k=3, spec_quant_mode="w8a8_nibble"))
    r = run_timed_workload(engine, cfg.vocab_size, requests=4,
                           prompt_budget=6, new_tokens=4,
                           stagger_s=0.005, seed=3,
                           arrival_mode="bursty")
    assert r["arrival_mode"] == "bursty"
    assert r["spec"] is True
    for col in ("ttft_p99_ms", "itl_p50_ms", "itl_p99_ms",
                "acceptance_rate", "tokens_per_step",
                "spec_rollback_pages"):
        assert col in r, col
    assert r["tokens"] == 16
    assert r["compile_counts"] == {"prefill": 1, "decode_chunk": 0,
                                   "draft": 1, "verify": 1}
    assert engine.allocator.in_use == 0


# ---------------------------------------------------------------------------
# tools/spec_report.py: the planning model
# ---------------------------------------------------------------------------

def test_spec_report_expected_tokens_endpoints():
    assert spec_report.expected_tokens_per_round(0.0, 4) == 1.0
    assert spec_report.expected_tokens_per_round(1.0, 4) == 5.0
    # strictly increasing in alpha
    vals = [spec_report.expected_tokens_per_round(a, 4)
            for a in (0.1, 0.3, 0.5, 0.7, 0.9)]
    assert all(b > a for a, b in zip(vals, vals[1:]))
    with pytest.raises(ValueError):
        spec_report.expected_tokens_per_round(1.5, 4)
    with pytest.raises(ValueError):
        spec_report.expected_tokens_per_round(0.5, 0)


def test_spec_report_inversion_roundtrip():
    for k in (2, 4, 8):
        for alpha in (0.0, 0.25, 0.5, 0.8, 0.95, 1.0):
            tps = spec_report.expected_tokens_per_round(alpha, k)
            back = spec_report.acceptance_from_tokens_per_step(tps, k)
            assert abs(back - alpha) < 1e-6, (k, alpha)
    with pytest.raises(ValueError):
        spec_report.acceptance_from_tokens_per_step(0.5, 4)
    with pytest.raises(ValueError):
        spec_report.acceptance_from_tokens_per_step(6.0, 4)


def test_spec_report_speedup_model():
    # free drafts + full acceptance: (k+1)-for-1
    assert spec_report.speedup(1.0, 4, c_draft=1e-9) == \
        pytest.approx(5.0, rel=1e-3)
    # zero acceptance with costly drafts is a slowdown
    assert spec_report.speedup(0.0, 4, c_draft=0.5) < 1.0
    with pytest.raises(ValueError):
        spec_report.speedup(0.5, 4, c_draft=0.0)


def test_spec_report_validates_bench_rows(tmp_path):
    import json
    # a self-consistent row (tokens_per_step generated from its own
    # acceptance) passes; a decoupled row is flagged
    tps = spec_report.expected_tokens_per_round(0.8, 4)
    good = {"results": [{"workload": "uniform", "spec": "on",
                         "tokens_per_step": tps,
                         "acceptance_rate": 0.8}]}
    bad = {"results": [{"workload": "uniform", "spec": "on",
                        "tokens_per_step": tps,
                        "acceptance_rate": 0.3}]}
    p = tmp_path / "bench.json"
    p.write_text(json.dumps(good))
    _, ok = spec_report.validate_bench(str(p))
    assert ok
    p.write_text(json.dumps(bad))
    lines, ok = spec_report.validate_bench(str(p))
    assert not ok and any("DRIFT" in ln for ln in lines)
    p.write_text(json.dumps({"results": []}))
    _, ok = spec_report.validate_bench(str(p))
    assert not ok                          # no spec rows = not validated
