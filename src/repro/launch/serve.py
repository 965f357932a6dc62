"""Serving launcher: continuous-batching engine on a reduced config, or
at the architecture's published widths with ``--full-width``.

Batch mode (legacy lockstep generate):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b \
        --batch 4 --prompt-len 32 --new-tokens 32 --quant w8a8_nibble

Request-level workloads (continuous batching: per-slot positions, slot
refill, per-request latency), optionally over the paged KV cache and
with a priority mix:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b \
        --workload staggered --requests 16 --stagger-ms 50 \
        --cache-mode paged --page-size 8 --priority-mix 0.25

Overcommitted pool (incremental page allocation + evict-and-resume
preemption: pages are booked per live token, `--num-pages` may sit
below the sum of worst-case page counts):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b \
        --workload uniform --requests 16 --cache-mode paged \
        --page-size 8 --alloc-mode incremental --num-pages 24

Prefix caching (shared system prompt served from refcounted read-only
pages; only the uncached suffix is prefilled):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b \
        --workload staggered --requests 16 --cache-mode paged \
        --page-size 8 --prefix-cache --shared-prefix 0.75

Self-speculative decoding (the quantized program drafts --spec-k
tokens, one dense multi-token forward verifies them; greedy streams
stay bit-identical to the non-spec dense engine) under a bursty
heavy-tail workload:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b \
        --workload bursty --requests 16 --stagger-ms 50 \
        --cache-mode paged --alloc-mode incremental \
        --spec-decode --spec-k 4 --spec-quant w8a8_nibble

Tail-latency engineering (chunked prefill and grouped admission through
one shared wave program, plus a host-tier page swap that makes
preemption resume an O(pages) copy) on an overcommitted bursty
workload:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b \
        --workload bursty --requests 16 --stagger-ms 50 \
        --cache-mode paged --alloc-mode incremental --num-pages 24 \
        --prefill-chunk 8 --admit-group 4 --swap-mode host

Published widths (``get_config(arch)`` unreduced, random weights drawn
from a seed; sized for an accelerator, not for a CPU):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b \
        --full-width --workload uniform --requests 16 --batch 8 \
        --prompt-len 1024 --new-tokens 64 --cache-mode paged \
        --page-size 16

Compile time is reported separately from steady-state throughput (a
warmup pass triggers every compilation before the timed run).
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model_init
from repro.serve import Engine, Router, ServeConfig


def _parse_mesh(spec: str | None):
    """``"DATAxMODEL"`` (e.g. ``2x4``) → per-engine mesh shape tuple."""
    if spec is None:
        return None
    try:
        dp, tp = spec.lower().split("x")
        return (int(dp), int(tp))
    except ValueError:
        raise SystemExit(f"--mesh expects DATAxMODEL (e.g. 1x2), "
                         f"got {spec!r}")


def model_config(args):
    """The model config ``args`` selects: the published widths with
    ``--full-width``, else the reduced CPU variant."""
    cfg = get_config(args.arch)
    if not args.full_width:
        cfg = reduced(cfg)
    return cfg.replace(quant_mode=args.quant)


def init_params(cfg, seed: int = 0):
    """Random weights for ``cfg`` from ``seed``.  Jitted, so only the
    bf16 leaves materialise: eager init would hold each weight's float32
    draw (3.6 GB for one stacked qwen3-4b MLP weight) alongside."""
    return jax.jit(model_init, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)


def build(args, *, reference: bool = False, params=None):
    """Build the serving stack for ``args``; returns ``(cfg, params,
    engine)``.  ``params`` reuses weights already drawn for the same
    arch and widths (every quant mode serves the one bf16 tree).
    ``reference=True`` builds the plain baseline from the same argument
    set — single-device (tp=1, dp=1, no mesh) AND with every
    tail-latency mechanism off (monolithic prefill, serialized
    admission, replay-only resume), so --verify proves chunked/grouped
    prefill and the host-tier swap against the unmodified engine, not
    just against themselves."""
    tp = 1 if reference else args.tp
    dp = 1 if reference else args.dp
    mesh_shape = None if reference else _parse_mesh(args.mesh)
    prefill_chunk = 0 if reference else args.prefill_chunk
    admit_group = 1 if reference else args.admit_group
    swap_mode = "off" if reference else args.swap_mode
    cfg = model_config(args)
    if params is None:
        params = init_params(cfg)
    max_len = args.prompt_len + args.new_tokens
    if args.cache_mode == "paged":
        # the paged pool is page-granular; round the budget up
        max_len += (-max_len) % args.page_size
    scfg = ServeConfig(batch=args.batch,
                       max_len=max_len,
                       prefill_len=args.prompt_len,
                       temperature=args.temperature,
                       decode_chunk=args.decode_chunk,
                       priority_aging_s=args.priority_aging_s,
                       alloc_mode=args.alloc_mode,
                       prefix_cache=args.prefix_cache,
                       quant_backend=args.quant_backend,
                       cache_mode=args.cache_mode,
                       page_size=args.page_size,
                       num_pages=args.num_pages or None,
                       spec_decode=args.spec_decode,
                       spec_k=args.spec_k,
                       spec_quant_mode=args.spec_quant,
                       prefill_chunk=prefill_chunk,
                       admit_group=admit_group,
                       swap_mode=swap_mode,
                       host_pages=args.host_pages,
                       prefix_cache_pages=args.prefix_cache_pages,
                       tp=tp,
                       mesh_shape=mesh_shape)
    if dp > 1:
        return cfg, params, Router(cfg, params, scfg, replicas=dp)
    return cfg, params, Engine(cfg, params, scfg)


def run_batch(args, cfg, engine):
    """Lockstep generate: every slot starts and stops together."""
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    if prompts.shape[0] != engine.scfg.batch:
        raise ValueError(f"prompt batch {prompts.shape[0]} != engine "
                         f"slot count {engine.scfg.batch}")
    # warmup: trigger prefill + decode-chunk compilation before timing
    t0 = time.perf_counter()
    engine.generate(prompts, min(args.new_tokens, 2)).block_until_ready()
    t_compile = time.perf_counter() - t0

    t0 = time.perf_counter()
    out = engine.generate(prompts, args.new_tokens)
    out.block_until_ready()
    dt = time.perf_counter() - t0
    toks = args.batch * args.new_tokens
    print(f"arch={cfg.name} quant={args.quant} backend={args.quant_backend} "
          f"workload=batch")
    print(f"  compile+warmup: {t_compile:.2f}s   "
          f"(compilations: {engine.compile_counts})")
    print(f"  steady-state:   {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s, batch={args.batch})")
    print("  sample token ids:", out[0, -16:].tolist())


def run_workload(args, cfg, engine, collect_streams=False):
    from repro.serve import run_timed_workload
    stagger = args.stagger_ms / 1000.0 \
        if args.workload in ("staggered", "bursty") else 0.0
    return run_timed_workload(engine, cfg.vocab_size,
                              requests=args.requests,
                              prompt_budget=args.prompt_len,
                              new_tokens=args.new_tokens, stagger_s=stagger,
                              priority_mix=args.priority_mix,
                              shared_prefix=args.shared_prefix,
                              arrival_mode="bursty"
                              if args.workload == "bursty" else "uniform",
                              collect_streams=collect_streams)


def _check_leaks(args, engine):
    """Every page must be back in the allocator once the prefix index
    lets go — a leak here is an engine bug, so fail loudly."""
    if args.cache_mode != "paged":
        return
    engine.release_prefix_cache()
    leaked = engine.leaked_pages()
    if leaked:
        raise SystemExit(f"page leak: {leaked} page(s) still booked "
                         f"after drain + prefix-cache release")


def _verify(args, cfg, r):
    """Re-run the identical workload on a single-device tp=1/dp=1
    reference and demand token-for-token equality.  Greedy only — and
    dense-only when dp > 1: w8a8 activation scales are per-tensor over
    the batch, so changing which requests share a decode chunk (which
    dp placement does) legitimately shifts quantized streams."""
    if args.temperature > 0:
        raise SystemExit("--verify needs greedy streams "
                         "(--temperature 0)")
    if args.dp > 1 and args.quant != "dense":
        raise SystemExit("--verify with --dp > 1 needs --quant dense: "
                         "batched activation quantization is batch-"
                         "composition-dependent, so placement changes "
                         "quantized streams")
    cfg_ref, _, ref = build(args, reference=True)
    ref_r = run_workload(args, cfg_ref, ref, collect_streams=True)
    _check_leaks(args, ref)
    if r["streams"] != ref_r["streams"]:
        bad = [i for i in r["streams"]
               if r["streams"][i] != ref_r["streams"][i]]
        raise SystemExit(f"verify FAILED: {len(bad)}/{len(r['streams'])} "
                         f"stream(s) diverge from the single-device "
                         f"reference (request ids {bad[:8]})")
    print(f"  verify: {len(r['streams'])} streams bit-match the "
          f"single-device reference")


def _workload_shape(args):
    from repro.capacity import WorkloadShape
    stagger = args.stagger_ms / 1000.0 \
        if args.workload in ("staggered", "bursty") else 0.0
    return WorkloadShape(requests=args.requests,
                         prompt_budget=args.prompt_len,
                         new_tokens=args.new_tokens, stagger_s=stagger,
                         priority_mix=args.priority_mix,
                         shared_prefix=args.shared_prefix,
                         arrival_mode="bursty"
                         if args.workload == "bursty" else "uniform")


def _predict(args, cfg, engine, r):
    """Calibrate the live engine and print the capacity model's
    prediction for the workload that was just measured."""
    if args.dp > 1 or args.tp > 1 or args.mesh:
        print("  capacity model: (skipped — covers the single-device "
              "engine; tp/dp rows carry no prediction)")
        return
    from repro.capacity import predict
    from repro.capacity.calibrate import calibrate_engine
    costs = calibrate_engine(engine)
    p = predict(engine.scfg, _workload_shape(args), costs,
                cache_token_bytes=int(engine.cache_token_bytes),
                acceptance=(r["acceptance_rate"]
                            if args.spec_decode else None))
    if not p["feasible"] or "tok_per_s" not in p:
        print(f"  capacity model: infeasible — "
              f"{p['infeasible_reason']}")
        return
    err = 100.0 * abs(p["tok_per_s"] - r["tok_per_s"]) \
        / max(r["tok_per_s"], 1e-9)
    print(f"  capacity model: predicted {p['tok_per_s']:.1f} tok/s "
          f"(measured {r['tok_per_s']:.1f}, {err:.0f}% off), "
          f"ttft p50={p['ttft_p50_ms']:.0f}ms "
          f"p99={p['ttft_p99_ms']:.0f}ms, "
          f"preemptions {p['preemptions']}, "
          f"cache {p['cache_kb_per_req']:.1f} KiB/req")


def run_autotune(args):
    """--autotune: knob-grid search over the analytic capacity model
    for this launcher invocation's workload shape — prints the
    prediction table and the winning ServeConfig kwargs, no model
    run."""
    import json as _json

    from repro.capacity.tune import knob_grid, search, table_lines
    if args.workload == "batch":
        raise SystemExit("--autotune plans request workloads "
                         "(uniform/staggered/bursty), not batch mode")
    cfg = model_config(args)
    shape = _workload_shape(args)
    max_len = args.prompt_len + args.new_tokens
    max_len += (-max_len) % args.page_size
    cells = knob_grid(shape, batch=args.batch, max_len=max_len,
                      prefill_len=args.prompt_len)
    results, winner = search(cfg, shape, cells,
                             objective=args.autotune,
                             ttft_slo_ms=args.ttft_slo_ms, alpha=0.8)
    print(f"# autotune: {len(cells)} cells, objective={args.autotune}"
          + (f", ttft_slo={args.ttft_slo_ms}ms"
             if args.ttft_slo_ms else ""))
    for line in table_lines(results, winner):
        print(line)
    if winner is None:
        print("# no admissible configuration")
        return 1
    print("# winning ServeConfig kwargs:")
    print(_json.dumps(winner["knobs"].to_dict(), indent=1))
    return 0


def run_requests(args, cfg, engine):
    """Request-level workload: ``uniform`` submits everything at t=0,
    ``staggered`` spaces arrivals by --stagger-ms, ``bursty`` clusters
    Poisson bursts at the same mean load with Pareto heavy-tail prompt
    lengths (slots refill mid-stream in all three)."""
    r = run_workload(args, cfg, engine, collect_streams=args.verify)
    _check_leaks(args, engine)
    print(f"arch={cfg.name} quant={args.quant} backend={args.quant_backend} "
          f"cache={args.cache_mode} workload={args.workload} "
          f"requests={args.requests} slots={args.batch}")
    print(f"  topology: {r['device_count']} device(s), per-engine mesh "
          f"{tuple(r['mesh_shape'])}, dp_replicas={r['dp_replicas']}")
    print(f"  compile+warmup: {r['compile_s']:.2f}s   "
          f"(compilations: {r['compile_counts']})")
    print(f"  steady-state:   {r['tokens']} tokens in {r['wall_s']:.2f}s "
          f"({r['tok_per_s']:.1f} tok/s)")
    print(f"  request latency p50={r['req_p50_ms']:.0f}ms "
          f"p99={r['req_p99_ms']:.0f}ms   "
          f"ttft p50={r['ttft_p50_ms']:.0f}ms "
          f"p99={r['ttft_p99_ms']:.0f}ms   "
          f"itl p50={r['itl_p50_ms']:.1f}ms p99={r['itl_p99_ms']:.1f}ms")
    print(f"  cache HBM/request: {r['cache_kb_per_req']:.1f} KiB")
    if args.spec_decode:
        print(f"  spec decode: k={args.spec_k} "
              f"draft={args.spec_quant or args.quant} "
              f"acceptance={r['acceptance_rate']:.0%} "
              f"tokens/step={r['tokens_per_step']:.2f} "
              f"rollback_pages={r['spec_rollback_pages']}")
    if args.cache_mode == "paged":
        print(f"  pool: {r['pool_pages']} pages, mean occupancy "
              f"{r['occupancy']:.0%}, mean concurrency "
              f"{r['concurrency']:.2f}, preemptions {r['preemptions']}")
    if args.prefix_cache:
        print(f"  prefix cache: hit rate {r['prefix_hit_rate']:.0%} of "
              f"prompt tokens, {r['prefill_tokens']} tokens prefilled")
    if args.prefill_chunk or args.admit_group > 1:
        print(f"  wave prefill: {r['prefill_waves']} waves "
              f"(chunk={args.prefill_chunk or args.prompt_len} "
              f"group={args.admit_group}), "
              f"{r['decode_chunks']} decode chunks")
    if args.swap_mode == "host":
        print(f"  host swap: {r['swap_out']} out / {r['swap_in']} in, "
              f"{r['replay_steps_saved']} replay steps saved, "
              f"{r['prefix_cold_hits']} cold prefix pages promoted")
    if "per_replica" in r:
        for pr in r["per_replica"]:
            print(f"  replica {pr['replica']}: {pr['placed']} placed, "
                  f"affinity hit rate {pr['affinity_hit_rate']:.0%}, "
                  f"prefix hit rate {pr['prefix_hit_rate']:.0%}, "
                  f"preemptions {pr['preemptions']}")
    if r["truncated"]:
        print(f"  WARNING: {r['truncated']} request(s) truncated at the "
              f"max_len budget")
    if "hi_req_p50_ms" in r:
        # an empty priority class reports None, not a number
        hi = r["hi_req_p50_ms"]
        lo = r["lo_req_p50_ms"]
        hi_s = "n/a (no hi requests)" if hi is None else f"p50={hi:.0f}ms"
        lo_s = "n/a (no lo requests)" if lo is None else f"p50={lo:.0f}ms"
        print(f"  priority split:  hi {hi_s}  lo {lo_s}")
    if args.predict:
        _predict(args, cfg, engine, r)
    if args.verify:
        _verify(args, cfg, r)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--full-width", action="store_true",
                    help="serve the arch at its published widths "
                         "(get_config unreduced, random seeded weights); "
                         "default is the reduced CPU variant")
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slot count")
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="slot prompt budget (requests pad up to this)")
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--decode-chunk", type=int, default=8,
                    help="tokens per jitted decode dispatch")
    ap.add_argument("--workload", default="batch",
                    choices=["batch", "uniform", "staggered", "bursty"],
                    help="batch = lockstep generate; uniform/staggered/"
                         "bursty = request queue with slot refill "
                         "(bursty clusters Poisson-burst arrivals with "
                         "Pareto heavy-tail prompt lengths)")
    ap.add_argument("--requests", type=int, default=8,
                    help="request count for queued workloads")
    ap.add_argument("--stagger-ms", type=float, default=50.0,
                    help="arrival spacing for the staggered workload; "
                         "mean inter-arrival for bursty")
    ap.add_argument("--quant", default="dense",
                    choices=["dense", "w8a8_nibble", "w4a8_nibble", "lut"])
    ap.add_argument("--quant-backend", default="xla",
                    choices=["xla", "pallas"],
                    help="pallas = fused single-pass kernels "
                         "(ops.quant_matmul, in-kernel dequant epilogue)")
    ap.add_argument("--cache-mode", default="dense",
                    choices=["dense", "paged"],
                    help="paged = shared page pools + page-table "
                         "indirection (cache HBM scales with live tokens)")
    ap.add_argument("--page-size", type=int, default=8,
                    help="tokens per KV page (paged cache mode)")
    ap.add_argument("--alloc-mode", default="reserve",
                    choices=["reserve", "incremental"],
                    help="reserve = book worst-case pages at admission; "
                         "incremental = book live-token pages per decode "
                         "chunk with evict-and-resume preemption "
                         "(allows an overcommitted --num-pages)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="KV pool size in pages (0 = parity with the "
                         "dense slab); set below the worst-case sum to "
                         "overcommit with --alloc-mode incremental")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share read-only prompt-prefix pages across "
                         "requests (paged cache mode): admission maps "
                         "cached page-aligned chunks and prefills only "
                         "the uncached suffix, copy-on-writing a fully "
                         "covered prompt's tail page")
    ap.add_argument("--shared-prefix", type=float, default=0.0,
                    help="fraction of workload requests that begin with "
                         "one fixed system-prompt head of prompt-len/2 "
                         "tokens (the workload prefix caching serves)")
    ap.add_argument("--priority-mix", type=float, default=0.0,
                    help="fraction of workload requests submitted at "
                         "priority 1 (rest 0); reports per-class latency")
    ap.add_argument("--priority-aging-s", type=float, default=1.0,
                    help="queue-wait seconds per +1 effective priority "
                         "(anti-starvation aging; 0 = strict priorities)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="self-speculative decoding: the quantized "
                         "program drafts --spec-k tokens per slot, one "
                         "dense multi-token forward verifies them; "
                         "rejected tails roll back as a page-table "
                         "truncation.  Greedy streams stay bit-equal "
                         "to the non-spec dense engine")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens per speculation round")
    ap.add_argument("--spec-quant", default=None,
                    choices=["dense", "qat", "w8a8_nibble", "w4a8_nibble",
                             "lut"],
                    help="draft-side quant mode (default: the engine's "
                         "--quant; the verifier always runs dense)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: split every prompt into "
                         "chunks of this many tokens, one chunk per "
                         "scheduler step interleaved with decode "
                         "chunks (0 = monolithic one-dispatch prefill; "
                         "paged cache only)")
    ap.add_argument("--admit-group", type=int, default=1,
                    help="grouped admission: up to this many prefilling "
                         "requests advance per wave as one padded "
                         "batch through the single wave program "
                         "(paged cache only)")
    ap.add_argument("--swap-mode", default="off",
                    choices=["off", "host"],
                    help="host = on eviction copy the victim's live KV "
                         "pages to a host-memory cold pool and restore "
                         "them on resume (O(pages) copy instead of "
                         "O(generated) replay); also gives the prefix "
                         "cache a host cold tier")
    ap.add_argument("--host-pages", type=int, default=0,
                    help="host cold-pool capacity in pages for "
                         "--swap-mode host (0 = twice the device pool)")
    ap.add_argument("--prefix-cache-pages", type=int, default=0,
                    help="capacity cap on pages the prefix index may "
                         "pin; overflow reclaims LRU leaf-first, "
                         "demoting to the host cold tier when "
                         "--swap-mode host (0 = uncapped)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel shards per engine: weights and "
                         "paged KV pools shard over a (1, tp) device "
                         "mesh's \"model\" axis (greedy streams stay "
                         "token-identical to tp=1)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel engine replicas behind one "
                         "admission router (least-loaded placement with "
                         "priority ordering and prefix-cache affinity); "
                         "each replica gets its own disjoint --tp-sized "
                         "device group")
    ap.add_argument("--mesh", default=None,
                    help="per-engine mesh shape DATAxMODEL (e.g. 1x2); "
                         "overrides --tp when set")
    ap.add_argument("--verify", action="store_true",
                    help="re-run the workload on a single-device tp=1/"
                         "dp=1 reference and require token-for-token "
                         "stream equality (greedy only; dense quant "
                         "when --dp > 1)")
    ap.add_argument("--predict", action="store_true",
                    help="after the measured run, calibrate the "
                         "engine's per-dispatch stage costs and print "
                         "the analytic capacity model's prediction for "
                         "the same workload next to the measurement "
                         "(single-device request workloads)")
    ap.add_argument("--autotune", default=None, metavar="OBJECTIVE",
                    choices=["max-tok-s", "min-pages"],
                    help="skip the run: search the serving knob grid "
                         "with the analytic capacity model for this "
                         "workload shape and print the winning "
                         "ServeConfig (objectives: max-tok-s under "
                         "--ttft-slo-ms, min-pages at zero predicted "
                         "preemptions)")
    ap.add_argument("--ttft-slo-ms", type=float, default=None,
                    help="p99 TTFT SLO an --autotune max-tok-s winner "
                         "must meet")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.workload == "batch" and args.dp > 1:
        raise SystemExit("--dp applies to request workloads "
                         "(uniform/staggered/bursty), not batch mode")
    if args.autotune:
        return run_autotune(args)
    enable_compile_cache()
    cfg, _, engine = build(args)
    if args.workload == "batch":
        run_batch(args, cfg, engine)
    else:
        run_requests(args, cfg, engine)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
