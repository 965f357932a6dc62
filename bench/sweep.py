"""Knee sweep: drive one cell's configuration and traffic at several
arrival rates in one process, to find the highest rate the system
sustains.  Run once per configuration, on the chip, when the cell's
rate is set (the rate then goes into the traffic file):

    python3 -m bench.sweep --workload <cell> --seed <n> --seconds <s> \\
        --rates 0.3 0.4 0.5

Each rate gets a pre-roll and a window of its own on one engine, built
and warmed up once.  For each it prints one JSON line: requests due,
failed, tok_s and the tails, the requests waiting for their first token
at the window's close, and for each third of the window the mean TTFT
of the requests due in it and the tokens per second stamped in it, and
whether the rate is sustained.  A rate is sustained where no backlog
builds: at most one request (the last due) still waits for its first
token at the close, and no third's mean TTFT reaches ``BACKLOG_S``,
about twice what a request waits on an unqueued server (the decode
chunk in flight, a prefill ahead of it, its own).  The knee is the
highest rate sustained along with every lower one.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

BACKLOG_S = 4.0


def _thirds(reqs: list, seconds: float) -> tuple:
    ttft, tok_s = [], []
    for k in range(3):
        lo, hi = k * seconds / 3, (k + 1) * seconds / 3
        w = [r["t_first"] - r["arrival"] for r in reqs
             if r["window"] and r["t_first"] is not None
             and lo <= r["arrival"] < hi]
        ttft.append(float(np.mean(w)) if w else None)
        tok_s.append(sum(lo <= t < hi for r in reqs for t in r["t_tokens"])
                     / (hi - lo))
    return ttft, tok_s


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.load_cell(args.workload)
    harness.cache_dir()
    import jax
    try:
        harness.device_info(jax, cell.chips)
    except harness.RunError as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 2
    _, engine = harness.build(jax, harness.prepare(cell), args.seed)
    vocab = cell.config["model"]["vocab_size"]
    for rate in args.rates:
        pre, win = harness.schedules(cell, seed=args.seed,
                                     seconds=args.seconds, vocab=vocab,
                                     rate=rate)
        engine.reset()
        drove = harness.drive(engine, pre, win, cell.mix["warm_s"],
                              args.seconds)
        reqs = drove["requests"]
        close = drove["t_close"]
        e2e = harness.end_to_end(reqs, args.seconds, drove["t_last"], close)
        waiting = sum(r["window"] and (r["t_first"] is None
                                       or r["t_first"] > close)
                      for r in reqs)
        ttft, tok_s = _thirds(reqs, args.seconds)
        # a third in which no request was due shows no backlog
        sustained = waiting <= 1 and all(t is None or t < BACKLOG_S
                                         for t in ttft)
        print(json.dumps({
            "rate": rate, "due": len(win),
            "failed": sum(r["window"] and r["t_first"] is None
                          for r in reqs),
            **e2e, "waiting_at_close": int(waiting),
            "concurrency": drove["stats"]["concurrency"],
            "ttft_mean_s_by_third": ttft, "tok_s_by_third": tok_s,
            "sustained": sustained}),
            flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
