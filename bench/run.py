"""Benchmark entry point: one run of one cell on the chips of this
machine.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run from the root of a checkout.  The last line of standard output is
the result, one JSON object; the last lines of standard error name each
number that decided ``correct`` beside its limit.  With ``--trace 0`` the
metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.  A machine
without the chips the cell asks for, or with a device kind that
``bench/peaks.json`` does not list, gets a non-zero exit and no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness
    try:
        cell = harness.load_cell(args.workload)
        harness.cache_dir()
        result = harness.run(cell, seed=args.seed, seconds=args.seconds,
                             trace=bool(args.trace), t_start=T_START)
    except harness.RunError as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
