"""Readings that set a cell's ``correct`` limits, on the chip at the
cell's own size and load, several seeds in one process.  The benchmark's
own runs do not run this.

The control (the default): the program with its own next lower
precision switched on.  The configuration serves int8 weights
(``w8a8_nibble``) and names ``w4a8_nibble``, int4 weights through the
same kernel, as its control.  Each seed is one whole run of the cell
(``bench.harness.run``) with the engine built in that mode: the same
traffic, window, reference and checks as a benchmark run, so each line
is a result line, whose ``correct`` has to come out false.

    python3 -m bench.control --workload <cell> --seconds 51 \\
        --seeds 101 102 103

A witness (``--witness MODE ...``): the program serves the window as in
a benchmark run; then, at the compared positions of the same prompts and
served tokens, the program's own ``MODE`` path, teacher-forced over one
sequence with no cache and no decode batch
(``repro.models.transformer.forward``), puts a token first, and the
reference reads its gap.  Where the served tokens fall far below the
reference while the same precision teacher-forced does not, the fault
lies in the served path, not in the precision.  ``--config`` and
``--traffic`` name a cell that ``BENCHMARK.json`` does not hold.

    python3 -m bench.control --config yi-6b-w8a8-pallas \\
        --traffic chat-poisson --seconds 51 --seeds 81 \\
        --witness w8a8_nibble w4a8_nibble
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import numpy as np


def forced_gaps(jax, ref, fwd, weights, config, reqs, picked,
                max_len) -> list:
    """Reference gaps, one array per request, of the token that
    ``fwd(weights, tokens)`` (teacher-forced logits) puts first at the
    positions where the served tokens are compared."""
    from bench import harness
    out = []
    for i in picked:
        seq, at = harness.forced(reqs[i], max_len)
        first = np.asarray(jax.numpy.argmax(fwd(weights, seq[None]),
                                            axis=-1), np.int32)
        g = np.asarray(ref.token_gaps(weights, config["model"],
                                      config["qk_norm"], seq, first))
        out.append(g[at])
    return out


def witness(cell, seeds, seconds: float, modes, root=None,
            require_tpu: bool = True):
    """Yield, per seed, the served tokens' readings and each teacher-forced
    ``mode``'s readings at the same positions."""
    from bench import harness
    import jax
    root = harness.ROOT if root is None else root
    harness.device_info(jax, cell.chips, require_tpu)
    stack = harness.prepare(cell, root)
    from repro.models.transformer import forward
    config, ref = stack.config, stack.ref
    vocab = config["model"]["vocab_size"]
    max_len = config["serving"]["max_len"]
    fwds = {m: jax.jit(lambda p, t, c=stack.cfg.replace(
        quant_mode=m, quant_backend=config["serving"]["quant_backend"]):
        forward(p, c, t)[0][0]) for m in modes}
    for seed in seeds:
        weights, engine = harness.build(jax, stack, seed)
        pre, win = harness.schedules(cell, seed=seed, seconds=seconds,
                                     vocab=vocab)
        reqs = harness.drive(engine, pre, win, cell.mix["warm_s"],
                             seconds)["requests"]
        del engine
        gc.collect()
        picked = harness.sample(reqs, seed)
        served = harness.served_gaps(ref, weights, config, reqs, picked,
                                     max_len)
        out = {"seed": seed, "requests": len(picked),
               "tokens": int(sum(g.size for g in served)),
               "served": harness.gap_readings(served)}
        for m, fwd in fwds.items():
            out[m] = harness.gap_readings(forced_gaps(
                jax, ref, fwd, weights, config, reqs, picked, max_len))
        yield out
        del weights
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--config")
    ap.add_argument("--traffic")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--witness", nargs="+", metavar="MODE")
    args = ap.parse_args(argv)
    from bench import harness
    if args.workload:
        cell = harness.load_cell(args.workload)
    else:
        cell = harness.make_cell(args.config, args.traffic)
    harness.cache_dir()
    try:
        if args.witness:
            for r in witness(cell, args.seeds, args.seconds, args.witness):
                print(json.dumps(r), flush=True)
            return 0
        for seed in args.seeds:
            r = harness.run(cell, seed=seed, seconds=args.seconds,
                            trace=False, t_start=time.perf_counter(),
                            control=True)
            print(json.dumps({"seed": seed, **r}), flush=True)
            gc.collect()
    except harness.RunError as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
