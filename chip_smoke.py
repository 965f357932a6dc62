"""Smoke test on the TPU: serve qwen3-4b at its published widths through
``repro.launch.serve``'s own ``build`` and ``run_workload``.

    python chip_smoke.py               # one chip: three serving modes
    python chip_smoke.py --four-chips  # four chips: dp=4 and tp=4 vs one

One chip.  Random bf16 weights from a seed (about 8.0 GB), 16 requests
with prompts of 512-1024 tokens and 64 new tokens each over the paged
KV cache (8 slots x 1088 tokens), served in three modes: dense/xla,
w8a8_nibble/xla and w8a8_nibble/pallas.  Checks:

* every request completes with all its tokens;
* no page is leaked once the engine drains;
* each mode compiled exactly the pinned programs;
* the dense engine's tokens of one request are the argmax of a plain
  teacher-forced ``forward`` over the same tokens, to within a stated
  logit tolerance (bf16 prefill/decode vs full-sequence arithmetic);
* the w8a8 pallas and xla greedy streams are bit-identical (both are
  exact int32 accumulations with the same scales);
* the pallas decode program, re-lowered from its recorded signature,
  holds a Mosaic kernel (``tpu_custom_call``), so an interpret-mode run
  cannot pass.

Four chips.  The same dense/xla workload on one chip, then on dp=4
(one replica per chip behind the router: streams must be bit-identical,
on four distinct devices), then on tp=4 (one engine sharded over a
(1, 4) mesh: the psum reorders float sums, so a stream may part from
the one-chip stream only where the one-chip logits hold a near-tie, at
the same tolerance).

The script fails (non-zero exit, no result line) when JAX finds no TPU.
The last line of a passing run is the JSON result.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ARCH = "qwen3-4b"
SERVE_ARGS = ["--arch", ARCH, "--full-width", "--workload", "uniform",
              "--requests", "16", "--batch", "8", "--prompt-len", "1024",
              "--new-tokens", "64", "--cache-mode", "paged",
              "--page-size", "16", "--decode-chunk", "8"]
MODES = [("dense", "xla"), ("w8a8_nibble", "xla"),
         ("w8a8_nibble", "pallas")]
PINS = {"prefill": 1, "decode_chunk": 1}
# a generated token counts as the reference's choice when its logit is
# within LOGIT_TOL x (std of that position's logits) of the maximum;
# an off-by-one position or a wrong cache page lands several std away
LOGIT_TOL = 0.1
GB = 1e9


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _tpu_devices(jax, count: int):
    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"needs a TPU; JAX found {devices[0].platform!r}")
    check(len(devices) >= count,
          f"needs {count} TPU chip(s); JAX found {len(devices)}")
    return devices


def _hbm_gb(device, key="peak_bytes_in_use") -> str:
    stats = device.memory_stats() or {}
    peak = stats.get(key)
    return "not reported" if peak is None else f"{peak / GB:.2f} GB"


def _hbm_per_chip(devices, phase) -> None:
    """Per chip after ``phase``: HBM in use now, and the peak so far."""
    print(f"{phase}: HBM in use per chip "
          f"{[_hbm_gb(d, 'bytes_in_use') for d in devices]}, peak so far "
          f"{[_hbm_gb(d) for d in devices]}", flush=True)


def _serve(serve, argv, params, label):
    """Build one stack from launcher flags and drive the timed workload;
    checks completion, page leaks and compile pins.  Returns
    ``(result, engine, params, args, cfg)``."""
    args = serve.build_parser().parse_args(argv)
    cfg, params, engine = serve.build(args, params=params)
    r = serve.run_workload(args, cfg, engine, collect_streams=True)
    streams = r["streams"]
    check(len(streams) == args.requests and r["truncated"] == 0
          and all(len(s) == args.new_tokens for s in streams.values()),
          f"{label}: not every request completed with "
          f"{args.new_tokens} tokens")
    engine.release_prefix_cache()
    leaked = engine.leaked_pages()
    check(leaked == 0, f"{label}: {leaked} page(s) leaked")
    check(r["compile_counts"] == PINS,
          f"{label}: compile counts {r['compile_counts']} != pins {PINS}")
    print(f"{label}: {r['device_count']} device(s), compile+warmup "
          f"{r['compile_s']:.2f} s, {r['tokens']} tokens in "
          f"{r['wall_s']:.3f} s ({r['tok_per_s']} tok/s), ttft "
          f"p50={r['ttft_p50_ms']} ms p99={r['ttft_p99_ms']} ms, itl "
          f"p50={r['itl_p50_ms']} ms, compile counts "
          f"{r['compile_counts']}, leaked pages 0", flush=True)
    return r, engine, params, args, cfg


def _reference_logits(serve, jax, jnp, params, cfg, args, index, stream):
    """Teacher-forced full-sequence logits for workload request
    ``index`` followed by its generated ``stream``: one plain
    ``forward`` over a fixed-length padded sequence (one compile)."""
    from repro.models.transformer import forward
    from repro.serve.workload import draw_workload
    prompt = draw_workload(cfg.vocab_size, requests=args.requests,
                           prompt_budget=args.prompt_len,
                           new_tokens=args.new_tokens).prompts[index]
    seq = list(prompt) + list(stream[:-1])
    length = args.prompt_len + args.new_tokens
    tokens = jnp.zeros((1, length), jnp.int32).at[0, :len(seq)].set(
        jnp.asarray(seq, jnp.int32))
    logits, _ = jax.jit(forward, static_argnums=1)(
        params, cfg.replace(quant_mode="dense"), tokens)
    first = len(prompt) - 1
    return logits[0, first:first + len(stream)]


def _shortfalls(jnp, logits, stream):
    """Per position: (max logit - logit of the emitted token) / std."""
    tok = jnp.asarray(stream, jnp.int32)
    chosen = jnp.take_along_axis(logits, tok[:, None], axis=1)[:, 0]
    return (logits.max(axis=1) - chosen) / logits.std(axis=1)


def one_chip(serve, jax, jnp, devices) -> None:
    device = devices[0]
    t0 = time.perf_counter()
    cfg = serve.model_config(serve.build_parser().parse_args(SERVE_ARGS))
    params = jax.block_until_ready(serve.init_params(cfg))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    n_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    print(f"params: {ARCH} at published widths (d_model {cfg.d_model}, "
          f"{cfg.n_layers} layers, vocab {cfg.vocab_size}), {n_params} "
          f"params, {n_bytes / GB:.2f} GB, jitted init "
          f"{time.perf_counter() - t0:.2f} s", flush=True)
    streams = {}
    for quant, backend in MODES:
        label = f"{quant}/{backend}"
        r, engine, params, args, cfg = _serve(
            serve, SERVE_ARGS + ["--quant", quant,
                                 "--quant-backend", backend],
            params, label)
        streams[label] = r["streams"]
        if quant == "dense":
            logits = _reference_logits(serve, jax, jnp, params, cfg, args, 0,
                                       r["streams"][0])
            check(bool(jnp.isfinite(logits).all()),
                  "reference logits are not finite")
            short = _shortfalls(jnp, logits, r["streams"][0])
            exact = int((short == 0).sum())
            worst = float(short.max())
            print(f"check reference: request 0, {exact}/{len(short)} tokens "
                  f"are the exact argmax of a teacher-forced forward, "
                  f"largest shortfall {worst:.4f} std (tolerance "
                  f"{LOGIT_TOL})", flush=True)
            check(worst <= LOGIT_TOL,
                  f"dense engine tokens part from the reference forward by "
                  f"{worst:.4f} std of the logits")
        if backend == "pallas":
            prog = engine.stage_programs()["decode_chunk"]
            (sig,) = prog.signatures
            text = prog.jit_fn.lower(*type(prog).abstract_args(sig)).as_text()
            check("tpu_custom_call" in text,
                  "pallas decode program holds no tpu_custom_call")
            print("check kernel: pallas decode_chunk lowers to "
                  "tpu_custom_call", flush=True)
        del engine
        gc.collect()  # engines sit in reference cycles: free the KV pool now
        print(f"{label}: peak HBM so far {_hbm_gb(device)}", flush=True)
    a, b = streams["w8a8_nibble/xla"], streams["w8a8_nibble/pallas"]
    same = sum(a[i] == b[i] for i in a)
    check(same == len(a), f"w8a8 pallas vs xla: only {same}/{len(a)} "
                          f"streams bit-identical")
    print(f"check w8a8 pallas == xla: {same}/{len(a)} streams "
          f"bit-identical", flush=True)
    print(f"peak HBM: {_hbm_gb(device)}", flush=True)


def four_chips(serve, jax, jnp, devices) -> None:
    check(len(devices) == 4, f"--four-chips needs exactly 4 chips, JAX "
                             f"found {len(devices)}")
    dense = SERVE_ARGS + ["--quant", "dense", "--quant-backend", "xla"]
    # the one-chip reference runs first and is freed before the fleet is
    # built; the weights are shared (replica 0's copy is the same buffer)
    ref, engine, params, args, cfg = _serve(serve, dense, None,
                                            "one chip dense/xla")
    del engine
    gc.collect()  # engines sit in reference cycles: free the KV pool now
    _hbm_per_chip(devices, "after one chip")
    r, router, params, _, _ = _serve(serve, dense + ["--dp", "4"], params,
                                     "dp=4 dense/xla")
    placed = [eng.devices for eng in router.replicas]
    check(router.device_count == 4 and len(set(placed)) == 4,
          f"dp=4 replicas are not on four distinct devices: {placed}")
    same = sum(r["streams"][i] == ref["streams"][i] for i in ref["streams"])
    check(same == len(ref["streams"]),
          f"dp=4: only {same}/{len(ref['streams'])} streams bit-identical "
          f"to one chip")
    print(f"check dp=4: replicas on {[str(p[0]) for p in placed]}, "
          f"{same}/{len(ref['streams'])} streams bit-identical to one chip",
          flush=True)
    del router
    gc.collect()
    _hbm_per_chip(devices, "after dp=4")
    r, engine, _, _, _ = _serve(serve, dense + ["--tp", "4"], params,
                                "tp=4 dense/xla")
    check(engine.mesh_shape == (1, 4) and engine.device_count == 4,
          f"tp=4 engine mesh {engine.mesh_shape} over "
          f"{engine.device_count} device(s)")
    del engine
    gc.collect()
    _hbm_per_chip(devices, "after tp=4")
    same, worst = 0, 0.0
    for i, want in ref["streams"].items():
        got = r["streams"][i]
        if got == want:
            same += 1
            continue
        # first parting token: the one-chip logits there must hold a
        # near-tie between the two choices
        p = next(t for t in range(len(want)) if got[t] != want[t])
        logits = _reference_logits(serve, jax, jnp, params, cfg, args, i,
                                   want[:p + 1])
        short = float(_shortfalls(jnp, logits[p:p + 1], [got[p]])[0])
        worst = max(worst, short)
        print(f"tp=4 request {i} parts at token {p}: one-chip logit "
              f"shortfall {short:.4f} std", flush=True)
        check(short <= LOGIT_TOL,
              f"tp=4 request {i} parts from one chip at token {p} by "
              f"{short:.4f} std (tolerance {LOGIT_TOL})")
    print(f"check tp=4: {same}/{len(ref['streams'])} streams bit-identical "
          f"to one chip; partings at near-ties only (largest shortfall "
          f"{worst:.4f} std, tolerance {LOGIT_TOL})", flush=True)
    _hbm_per_chip(devices, "after the reference forwards")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only dp=4 and tp=4 against one chip")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        import jax
        import jax.numpy as jnp

        from repro.launch import serve
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"FAIL: {e} (run from the root of the repository)",
              file=sys.stderr)
        return 2
    try:
        devices = _tpu_devices(jax, 4 if args.four_chips else 1)
        print(f"device: platform={devices[0].platform} "
              f"kind={devices[0].device_kind} count={len(devices)}",
              flush=True)
        print(f"compile cache: {enable_compile_cache()}", flush=True)
        (four_chips if args.four_chips else one_chip)(serve, jax, jnp,
                                                      devices)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
