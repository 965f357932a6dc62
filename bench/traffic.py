"""The one traffic generator: a mix file of parameters in, a request
schedule out, drawn from ``--seed``.

A mix file (``bench/traffic/<name>.json``) holds::

    {"arrivals": {"kind": "stratified_poisson",
                  "rate_per_s": {"<configuration>": 0.8, ...}},
     "prompt_len": {"dist": "lognormal", "median": 1000, "sigma": 0.7,
                    "min": 16, "max": 2048},
     "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                    "min": 8, "max": 512},
     "warm_s": 30}

``warm_s`` is the pre-roll: seconds of the same traffic, drawn apart,
that the harness serves before the window opens.

The schedule is a stratified draw, not a random one: its prompt lengths,
output lengths and inter-arrival gaps are the distributions' quantiles at
``(i + 0.5) / n`` (the gaps those of the exponential distribution of a
Poisson process at the given rate).  Each set is laid out in one fixed
order that interleaves short and long (position ``j`` holds the quantile
ranked by the fractional part of ``(j + 1) * c``, with a different
irrational ``c`` per set, so the three are not correlated).  So every
seed serves the same requests at the same due times, and a window's work
does not change with the seed: the seed draws the prompts' token ids
(and the harness draws the weights from it).  ``n`` is the number of
requests due in the window: ``rate_per_s * seconds``, rounded.  Arrival
``k`` is due at the ``k``-th partial sum of the gaps, scaled so that all
``n`` fall inside ``[0, seconds)``.

The rate is fixed per configuration, at about four fifths of the knee
that a sweep on the chip found for it (``bench/sweep.py``), so the mix
keys it by configuration name.  A mix file may name a ``"base"`` mix and
override its keys: a new configuration under the same traffic comes with
a file of its own and leaves this one as it is.

The lognormal lengths and the exponential gaps follow
``repro.serve.workload.draw_workload``; this copy adds output lengths,
arrivals at a given rate, and the stratified sets.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

__all__ = ["Schedule", "load_mix", "rate_for", "draw", "quantiles"]

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass
class Schedule:
    """One window's requests, due at ``arrivals`` seconds after the
    window opens."""
    arrivals: np.ndarray        # (n,) float64, sorted
    prompts: list               # n int32 arrays
    output_lens: np.ndarray     # (n,) int64

    def __len__(self) -> int:
        return len(self.prompts)


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    """The parameters of mix ``name``, read from its file, over those of
    the mix it names as ``"base"``."""
    path = Path(directory) / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    mix = json.loads(path.read_text())
    base = mix.pop("base", None)
    return {**load_mix(base, directory), **mix} if base else mix


def rate_for(mix: dict, config: str) -> float:
    """The arrival rate, in requests per second, of ``config`` under
    ``mix``."""
    rates = mix["arrivals"]["rate_per_s"]
    if config not in rates:
        raise KeyError(f"the traffic mix sets no rate for configuration "
                       f"{config!r}")
    return float(rates[config])


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of a length distribution, as
    integers clipped to ``[min, max]``."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.asarray([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


# one irrational step per set: prompt lengths, output lengths, gaps
_STEPS = (0.6180339887498949, 0.4142135623730951, 0.7320508075688772)


def _order(values: np.ndarray, step: float) -> np.ndarray:
    """``values`` (sorted) in the fixed interleaved order for ``step``."""
    n = len(values)
    return np.asarray(values)[np.argsort(((np.arange(n) + 1) * step) % 1.0,
                                         kind="stable")]


def _gaps(spec: dict, rate: float, n: int) -> np.ndarray:
    if spec["kind"] != "stratified_poisson":
        raise ValueError(f"unknown arrival process {spec['kind']!r}")
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def draw(mix: dict, *, rate: float, seed, seconds: float,
         vocab_size: int) -> Schedule:
    """The schedule of one window at ``rate`` requests per second;
    ``seed`` (an integer or a tuple of them) draws the token ids."""
    n = max(1, round(rate * seconds))
    rng = np.random.default_rng(seed)
    prompt_lens = _order(quantiles(mix["prompt_len"], n), _STEPS[0])
    output_lens = _order(quantiles(mix["output_len"], n), _STEPS[1])
    gaps = _order(np.sort(_gaps(mix["arrivals"], rate, n)), _STEPS[2])
    csum = np.cumsum(gaps)
    # the n-th arrival lands one mean gap before the window closes
    arrivals = (csum - gaps[0]) * seconds / (csum[-1] - gaps[0]
                                             + gaps.mean())
    prompts = [rng.integers(0, vocab_size, int(k), dtype=np.int32)
               for k in prompt_lens]
    return Schedule(arrivals=arrivals, prompts=prompts,
                    output_lens=output_lens)
