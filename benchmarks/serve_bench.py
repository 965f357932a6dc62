"""Serving throughput benchmark: the engine-level view of the paper.

PR 1 made the nibble kernels single-pass; this benchmark measures where
that shows up end to end — tokens/second and per-request latency out of
the continuous-batching engine, per workload shape:

* ``uniform``   — all requests arrive at t=0 (lockstep-like best case);
* ``staggered`` — arrivals spaced by a fixed gap, so slots free up and
                  refill mid-stream (the continuous-batching case; the
                  per-slot position vector is what makes it possible).

Grid: {dense, w8a8_nibble} × {xla, pallas} × {uniform, staggered} ×
{dense, paged} cache on a reduced config, plus an **overcommitted
pool** pair: the same paged pool sized well below the sum of worst-case
page counts, driven once with ``alloc_mode="reserve"`` (admission must
serialize on worst-case bookings) and once with
``alloc_mode="incremental"`` (pages booked per live token,
evict-and-resume preemption when the pool runs dry).  The
``concurrency`` and ``occupancy`` columns are the point: incremental
admits more concurrent requests per page of pool.

A **prefix-cache pair** rides along: a shared-system-prompt workload
(75% of requests begin with one fixed prompt head) through the same
paged engine with ``prefix_cache`` off and on.  ``prefix_hit_rate`` is
the fraction of prompt tokens served from cached read-only pages
instead of being re-prefilled, and the ``ttft_p50_ms`` delta is what
that saves the median request.

A **self-speculative pair** (off/on, uniform and bursty arrivals)
measures speculative decoding: the baseline runs the dense engine at
``decode_chunk=1`` (one forward per token — the standard comparison
for speculative decoding, since a spec round replaces per-token
forwards with one drafted batch), the spec side drafts ``spec_k=4``
tokens with the w8a8 nibble program and verifies them in ONE dense
multi-token forward.  ``acceptance_rate`` and ``tokens_per_step`` are
the spec columns; greedy acceptance keeps the emitted streams
bit-identical to the baseline's.

A **tail-latency pair** (``workload=burst_tail``) drives the bursty
heavy-tail workload over the overcommitted incremental pool with the
tail mechanisms off vs on — chunked wave prefill (``prefill_chunk=4``),
grouped admission (``admit_group=4``) and the host-tier page swap
(``swap_mode="host"``).  The p99 TTFT/ITL columns are the headline;
``swap_out``/``swap_in``/``replay_steps_saved`` count the swap traffic
and the replayed decode steps it saved.

CPU wall-clock is a functional proxy (pallas runs in interpret mode —
correctness, not speed); the uniform-vs-staggered *ratio*, the latency
percentiles and the per-request cache HBM column are the transferable
signal.  ``cache_kb_per_req`` is the point of the paged cache: dense
reserves the full ``max_len`` slab per request, paged reserves only the
pages its live tokens touch.  Results land in ``BENCH_serve.json``.

    PYTHONPATH=src python benchmarks/serve_bench.py [--json out.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

import jax

ARCH = "yi-6b"
SLOTS = 4
PROMPT_BUDGET = 16
NEW_TOKENS = 16
REQUESTS = 8
STAGGER_S = 0.05
PAGE_SIZE = 4
# the slot budget is provisioned for a worst case twice the actual
# workload (as a production deployment must be): dense reserves the
# whole slab per request, paged reserves only live pages — the gap is
# the cache_kb_per_req column
MAX_LEN = 2 * (PROMPT_BUDGET + NEW_TOKENS)
# overcommitted pool: every request's worst case is ceil((16+16-1)/4)
# = 8 pages, so 4 slots want 32 + trash; 17 pages (capacity 16 = two
# worst-case requests) forces reserve-mode admission to serialize while
# incremental mode keeps more slots live off the same pool
OVERCOMMIT_PAGES = 17
GRID = [("dense", "xla"), ("dense", "pallas"),
        ("w8a8_nibble", "xla"), ("w8a8_nibble", "pallas")]

SHARED_PREFIX = 0.75
SPEC_K = 4
SPEC_DRAFT = "w8a8_nibble"

_HEADER = ("workload,quant,backend,cache,alloc,prefix,spec,tail,pool_pages,"
           "requests,slots,tok_per_s,req_p50_ms,req_p99_ms,ttft_p50_ms,"
           "ttft_p99_ms,itl_p50_ms,itl_p99_ms,cache_kb_per_req,occupancy,"
           "concurrency,preemptions,swap_out,swap_in,replay_steps_saved,"
           "prefix_hit_rate,acceptance_rate,"
           "tokens_per_step,compile_s,device_count,mesh,dp_replicas,"
           "predicted_tok_s,predicted_ttft_p50_ms,prediction_err_pct")


def _attach_capacity(row, engine, scfg, *, requests, stagger,
                     shared_prefix, arrival_mode, prefix_cache, tp, dp):
    """Predict the row's own workload with the analytic capacity model
    (calibrated per-dispatch stage costs from this very engine) and
    embed the full replay blob, so ``tools/autotune.py --validate`` and
    ``tests/test_capacity.py`` can re-check model-vs-measured from the
    committed JSON alone.  Mesh/router rows (tp/dp > 1) carry no
    prediction — the capacity model covers the single-device engine."""
    row.update({"predicted_tok_s": None, "predicted_ttft_p50_ms": None,
                "prediction_err_pct": None})
    if tp > 1 or dp > 1:
        return
    from repro.capacity import Knobs, WorkloadShape, predict
    from repro.capacity.calibrate import calibrate_engine
    shape = WorkloadShape(requests=requests, prompt_budget=PROMPT_BUDGET,
                          new_tokens=NEW_TOKENS, stagger_s=stagger,
                          shared_prefix=shared_prefix,
                          arrival_mode=arrival_mode)
    knobs = Knobs.from_serve_config(scfg)
    costs = calibrate_engine(engine)
    acceptance = (float(row["acceptance_rate"]) if scfg.spec_decode
                  else None)
    ctb = int(engine.cache_token_bytes)
    pred = predict(knobs, shape, costs, cache_token_bytes=ctb,
                   acceptance=acceptance)
    row["capacity"] = {
        # gated rows are the model-vs-measured regression surface; the
        # prefix-cache rows stay ungated (page sharing is unmodeled)
        "gated": not prefix_cache and shared_prefix == 0.0,
        "knobs": knobs.to_dict(), "shape": shape.to_dict(),
        "costs": costs.to_dict(), "acceptance": acceptance,
        "cache_token_bytes": ctb,
        "predicted": {k: (round(v, 4) if isinstance(v, float) else v)
                      for k, v in pred.items()},
    }
    if pred.get("feasible") and "tok_per_s" in pred:
        row["predicted_tok_s"] = round(pred["tok_per_s"], 1)
        row["predicted_ttft_p50_ms"] = round(pred["ttft_p50_ms"], 1)
        row["prediction_err_pct"] = round(
            100.0 * abs(pred["tok_per_s"] - row["tok_per_s"])
            / max(row["tok_per_s"], 1e-9), 1)


def _bench_one(cfg, params, quant, backend, workload, cache_mode,
               alloc_mode="reserve", num_pages=None, prefix_cache=False,
               shared_prefix=0.0, arrival_mode="uniform", decode_chunk=8,
               spec=False, tp=1, dp=1, prefill_chunk=0, admit_group=1,
               swap_mode="off", requests=REQUESTS):
    from repro.serve import Engine, Router, ServeConfig, run_timed_workload
    scfg = ServeConfig(batch=SLOTS, max_len=MAX_LEN,
                       prefill_len=PROMPT_BUDGET, decode_chunk=decode_chunk,
                       alloc_mode=alloc_mode, prefix_cache=prefix_cache,
                       quant_mode=quant, quant_backend=backend,
                       cache_mode=cache_mode, page_size=PAGE_SIZE,
                       num_pages=num_pages, spec_decode=spec,
                       spec_k=SPEC_K,
                       spec_quant_mode=SPEC_DRAFT if spec else None,
                       tp=tp, prefill_chunk=prefill_chunk,
                       admit_group=admit_group, swap_mode=swap_mode)
    if dp > 1:
        engine = Router(cfg, params, scfg, replicas=dp)
    else:
        engine = Engine(cfg, params, scfg)
    stagger = STAGGER_S if (workload in ("staggered", "mesh")
                            or arrival_mode == "bursty") else 0.0
    r = run_timed_workload(engine, cfg.vocab_size, requests=requests,
                           prompt_budget=PROMPT_BUDGET,
                           new_tokens=NEW_TOKENS, stagger_s=stagger,
                           shared_prefix=shared_prefix,
                           arrival_mode=arrival_mode)
    counts = r.pop("compile_counts")
    # compile counts come from the engine's own signature tracker; a
    # negative value would mean introspection is unavailable (it never
    # is for the engine counter, but degrade to a warning rather than
    # killing the whole benchmark the way the old jax-private probe did)
    warn = None
    # the pinned per-mode contract: spec engines build exactly one
    # draft and one verify program and never the plain decode chunk;
    # wave engines (chunked/grouped prefill) build exactly one wave
    # program and never the monolithic prefill
    wave = prefill_chunk > 0 or admit_group > 1
    if wave and spec:
        expected = {"prefill": 0, "decode_chunk": 0, "prefill_chunk": 1,
                    "draft": 1, "verify": 1}
    elif wave:
        expected = {"prefill": 0, "decode_chunk": 1, "prefill_chunk": 1}
    elif spec:
        expected = {"prefill": 1, "decode_chunk": 0, "draft": 1,
                    "verify": 1}
    else:
        expected = {"prefill": 1, "decode_chunk": 1}
    if any(v < 0 for v in counts.values()):
        warn = "# warning: compile-count introspection unavailable"
    elif counts != expected:
        raise RuntimeError(f"engine recompiled during benchmark: {counts} "
                           f"(expected {expected})")
    # a paged drain must hand every page back once the prefix index
    # lets go — a leak in a benchmark run invalidates its numbers
    if cache_mode == "paged":
        engine.release_prefix_cache()
        leaked = engine.leaked_pages()
        if leaked:
            raise RuntimeError(f"page leak: {leaked} page(s) still booked "
                               f"after drain")
    row = {"workload": workload, "quant": quant, "backend": backend,
           "cache": cache_mode, "alloc": alloc_mode if cache_mode == "paged"
           else "-", "prefix": "on" if prefix_cache else "-", **r}
    row["spec"] = "on" if spec else "-"
    row["tail"] = "on" if (wave or swap_mode != "off") else "-"
    _attach_capacity(row, engine, scfg, requests=requests, stagger=stagger,
                     shared_prefix=shared_prefix, arrival_mode=arrival_mode,
                     prefix_cache=prefix_cache, tp=tp, dp=dp)
    return row, warn


def _csv(r):
    mesh = f"{r['mesh_shape'][0]}x{r['mesh_shape'][1]}"
    return (f"{r['workload']},{r['quant']},{r['backend']},{r['cache']},"
            f"{r['alloc']},{r['prefix']},{r['spec']},{r.get('tail', '-')},"
            f"{r['pool_pages'] or '-'},{r['requests']},"
            f"{r['slots']},{r['tok_per_s']},{r['req_p50_ms']},"
            f"{r['req_p99_ms']},{r['ttft_p50_ms']},{r['ttft_p99_ms']},"
            f"{r['itl_p50_ms']},{r['itl_p99_ms']},{r['cache_kb_per_req']},"
            f"{r['occupancy']},{r['concurrency']},{r['preemptions']},"
            f"{r.get('swap_out', 0)},{r.get('swap_in', 0)},"
            f"{r.get('replay_steps_saved', 0)},"
            f"{r['prefix_hit_rate']},{r['acceptance_rate']},"
            f"{r['tokens_per_step']},{r['compile_s']},"
            f"{r['device_count']},{mesh},{r['dp_replicas']},"
            f"{r.get('predicted_tok_s') or '-'},"
            f"{r.get('predicted_ttft_p50_ms') or '-'},"
            f"{r.get('prediction_err_pct') or '-'}")


MESH_TRIO = [(1, 1), (2, 1), (1, 2)]          # (tp, dp) per row


def run(json_path: str | None = None):
    from repro.configs import get_config, reduced
    from repro.models import model_init

    # the mesh rows run on the devices present: refuse up front, before
    # any row is measured, when there are too few of them
    n_dev = len(jax.devices())
    need = max(tp * dp for tp, dp in MESH_TRIO)
    if n_dev < need:
        raise RuntimeError(
            f"the mesh rows need {need} devices, {n_dev} present; on a CPU "
            f"host run with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8")
    cfg = reduced(get_config(ARCH))
    params = model_init(jax.random.PRNGKey(0), cfg)
    yield _HEADER
    rows = []
    for quant, backend in GRID:
        for workload in ("uniform", "staggered"):
            for cache_mode in ("dense", "paged"):
                r, warn = _bench_one(cfg, params, quant, backend, workload,
                                     cache_mode)
                rows.append(r)
                if warn:
                    yield warn
                yield _csv(r)
    # overcommitted pool: same pool, reserve vs incremental bookkeeping,
    # plus incremental with the host-tier swap — its preemptions resume
    # by page copy, so swap_out/swap_in fire and replay_steps_saved
    # shows up as fewer decode-chunk dispatches for the same streams
    for alloc_mode, swap in (("reserve", "off"), ("incremental", "off"),
                             ("incremental", "host")):
        r, warn = _bench_one(cfg, params, "dense", "xla", "overcommit",
                             "paged", alloc_mode=alloc_mode,
                             num_pages=OVERCOMMIT_PAGES, swap_mode=swap)
        rows.append(r)
        if warn:
            yield warn
        yield _csv(r)
    # prefix caching: shared-system-prompt workload, cache off vs on —
    # the hit-rate column and the ttft delta are the payoff
    for prefix_cache in (False, True):
        r, warn = _bench_one(cfg, params, "dense", "xla", "shared",
                             "paged", prefix_cache=prefix_cache,
                             shared_prefix=SHARED_PREFIX)
        rows.append(r)
        if warn:
            yield warn
        yield _csv(r)
    # self-speculative decoding: off/on at decode_chunk=1 (the dense
    # baseline pays one forward per token — the standard speculative
    # comparison) under uniform and bursty arrivals; greedy spec
    # streams are bit-identical to the baseline's, so tok_per_s,
    # acceptance_rate and tokens_per_step are the whole story
    for arrival in ("uniform", "bursty"):
        for spec in (False, True):
            r, warn = _bench_one(cfg, params, "dense", "xla", arrival,
                                 "paged", alloc_mode="incremental",
                                 arrival_mode=arrival, decode_chunk=1,
                                 spec=spec)
            rows.append(r)
            if warn:
                yield warn
            yield _csv(r)
    # tail-latency pair: the same bursty heavy-tail workload over the
    # same overcommitted incremental pool, with the tail mechanisms off
    # (monolithic prefill, replay-resume) vs on (4-token chunked wave
    # prefill, 4-wide grouped admission, host-tier page swap).  The
    # p99 TTFT/ITL columns are the headline; swap_out/swap_in/
    # replay_steps_saved show where the win comes from
    # 2x the grid's request count: with only 8 requests the p99 columns
    # are the per-run maximum and burst luck dominates the comparison
    for tail in (False, True):
        r, warn = _bench_one(cfg, params, "dense", "xla", "burst_tail",
                             "paged", alloc_mode="incremental",
                             num_pages=OVERCOMMIT_PAGES,
                             arrival_mode="bursty", requests=2 * REQUESTS,
                             prefill_chunk=4 if tail else 0,
                             admit_group=4 if tail else 1,
                             swap_mode="host" if tail else "off")
        rows.append(r)
        if warn:
            yield warn
        yield _csv(r)
    # mesh trio: the same shared-prefix staggered workload as a
    # single-device baseline, TP-sharded (one engine over a (1, 2)
    # mesh), and DP-replicated (two engines behind the router, with
    # per-replica prefix-affinity hit rates in the JSON row), on the
    # devices present (checked at the top)
    for tp, dp in MESH_TRIO:
        r, warn = _bench_one(cfg, params, "w8a8_nibble", "xla", "mesh",
                             "paged", alloc_mode="incremental",
                             prefix_cache=True, shared_prefix=SHARED_PREFIX,
                             tp=tp, dp=dp)
        rows.append(r)
        if warn:
            yield warn
        yield _csv(r)
    if json_path:
        payload = {
            "note": "Continuous-batching engine throughput on the reduced "
                    f"{ARCH} config (CPU functional proxy; pallas = "
                    "interpret mode). uniform = all arrivals at t=0; "
                    "staggered = arrivals every "
                    f"{int(STAGGER_S * 1e3)}ms, exercising slot refill "
                    "via per-slot decode positions. Latencies are "
                    "per-request (arrival to completion). The slot "
                    f"budget (max_len={MAX_LEN}) is provisioned for a "
                    "worst case 2x the workload; cache=paged uses "
                    f"page_size={PAGE_SIZE} pools + page-table "
                    "indirection and cache_kb_per_req is the per-request "
                    "KV reservation (dense: the max_len slab; paged: "
                    "allocated pages only). occupancy = mean fraction of "
                    "pool pages in use per decode chunk; concurrency = "
                    "mean admitted requests per chunk. The overcommit "
                    f"rows share one {OVERCOMMIT_PAGES}-page pool — "
                    "below the 4-slot worst-case sum of 33 pages (and "
                    "far below the 65-page dense-parity default): "
                    "alloc=reserve must serialize admissions on "
                    "worst-case bookings, alloc=incremental books pages "
                    "per live token (evict-and-resume preemption when "
                    "the pool runs dry) and sustains more concurrent "
                    "requests per page of pool; the third overcommit "
                    "row adds swap_mode=host — the same preemptions "
                    "resume by host-tier page copy (swap_out/swap_in), "
                    "and replay_steps_saved decode steps disappear from "
                    "the run while the streams stay bit-identical. On "
                    "this CPU proxy the copy costs more wall-clock than "
                    "the replay it saves (a tiny model makes replayed "
                    "decode steps nearly free — they ride along in "
                    "chunks that run anyway — while the host round-trip "
                    "pays real per-event dispatches); the counters, not "
                    "the swap row's tok_per_s, are the transferable "
                    "signal: at HBM scale each replayed step is a full "
                    "forward and the copy is O(pages). The "
                    "workload=shared pair "
                    f"gives {int(SHARED_PREFIX * 100)}% of requests one "
                    "fixed system-prompt head: prefix=on shares its "
                    "pages read-only across requests (refcounted, "
                    "copy-on-write tail) and prefix_hit_rate is the "
                    "fraction of prompt tokens served from cached pages "
                    "instead of re-prefilled. The spec=on rows run "
                    f"self-speculative decoding (spec_k={SPEC_K}, "
                    f"{SPEC_DRAFT} draft, dense verify) against a "
                    "decode_chunk=1 dense baseline — one forward per "
                    "token, the standard speculative-decoding "
                    "comparison; acceptance_rate = fresh drafts accepted "
                    "/ proposed, tokens_per_step = tokens emitted per "
                    "sequence per draft+verify round, and greedy "
                    "acceptance keeps spec streams bit-identical to the "
                    "baseline's. bursty arrivals cluster Poisson bursts "
                    "with Pareto heavy-tail prompt lengths at the same "
                    "mean load (ttft_p99_ms / itl percentile columns). "
                    "The workload=burst_tail pair runs that bursty "
                    f"workload over the same {OVERCOMMIT_PAGES}-page "
                    "overcommitted incremental pool with the "
                    "tail-latency mechanisms off vs on (tail=on: "
                    "prefill_chunk=4 chunked wave prefill interleaving "
                    "decode between prompt slices, admit_group=4 "
                    "grouped admission, swap_mode=host parking evicted "
                    "slots' KV pages in a host pool so resume is a page "
                    "copy instead of a token replay) — greedy streams "
                    "are bit-identical between the two rows, "
                    "ttft_p99_ms/itl_p99_ms are the headline, and "
                    "swap_out/swap_in/replay_steps_saved count the swap "
                    "traffic and the decode steps the page-copy resume "
                    "did not have to replay. "
                    "Every row records its topology: device_count, "
                    "mesh_shape = the per-engine (data, model) mesh, and "
                    "dp_replicas = engine replicas behind the router "
                    "(1 / [1, 1] / 1 for plain single-device rows). The "
                    "workload=mesh trio re-runs the shared-prefix "
                    "staggered workload on the devices present (a "
                    "forced 8-device host platform on CPU, which the "
                    "whole run then shares: rows measured so are not "
                    "comparable with rows from a 1-device process, and "
                    "the dp=2 replicas sit on two devices): a "
                    "single-device baseline, tp=2 (weights "
                    "and paged KV pools sharded over the mesh's model "
                    "axis), and dp=2 (two replicas behind the admission "
                    "router — its row carries per_replica placement and "
                    "prefix-affinity hit rates). CPU wall-clock across "
                    "forced-host shards is a functional proxy, not a "
                    "speedup claim. Every single-device row also carries "
                    "the analytic capacity model's prediction "
                    "(predicted_tok_s / predicted_ttft_p50_ms / "
                    "prediction_err_pct) plus the full replay blob "
                    "(knobs, workload shape, calibrated per-dispatch "
                    "stage costs) under its 'capacity' key; rows with "
                    "capacity.gated=true are the model-vs-measured "
                    "regression surface that tools/autotune.py "
                    "--validate and tests/test_capacity.py replay — see "
                    "docs/capacity.md for the tolerance policy.",
            "arch": ARCH,
            "results": rows,
        }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=1)
        yield f"# wrote {json_path}"


def main():
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="also write results to this JSON file")
    args = ap.parse_args()
    enable_compile_cache()
    for row in run(json_path=args.json):
        print(row)


if __name__ == "__main__":
    main()
