"""A temporary copy of the benchmark with a tiny cell of its own, for
the CPU dry path of the tests: the harness's run with the look for a
chip skipped, on a reduced qwen3-4b (d_model 64, one layer) through the
XLA backend.  Nothing here is on the chip path."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
CELL = "tiny-w8a8.chat-tiny"
# stand-in peaks for the CPU: the readers need numbers, the CPU has none
PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12,
         "hbm_bytes_per_s": 819e9}
TINY = {
    "source": "reduced(qwen3-4b) of the program, for tests only",
    "reference": "dense_decoder", "arch": "qwen3-4b", "full_width": False,
    "model": {"num_hidden_layers": 1, "hidden_size": 64,
              "intermediate_size": 128, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
              "rms_norm_eps": 1e-6, "rope_theta": 1000000,
              "tie_word_embeddings": True, "hidden_act": "silu"},
    "qk_norm": True,
    "serving": {"quant": "w8a8_nibble", "quant_backend": "xla",
                "cache_mode": "paged", "page_size": 16, "slots": 4,
                "prefill_len": 32, "max_len": 48},
    "control": {"quant": "w4a8_nibble"},
    "limits": {"gap_max_std": 0.5, "tokens_checked": 16}}
# the reading the tiny cell compares, as a configuration names it
READING = next(k for k in TINY["limits"] if k != "tokens_checked")
MIX = {"base": "chat-poisson",
       "arrivals": {"kind": "stratified_poisson",
                    "rate_per_s": {"tiny-w8a8": 4.0}},
       "prompt_len": {"dist": "lognormal", "median": 16, "sigma": 0.7,
                      "min": 2, "max": 32},
       "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.8,
                      "min": 2, "max": 16},
       "warm_s": 1.0}


def copy(tmp_path: Path) -> Path:
    """A checkout of the benchmark as committed, without its tests."""
    root = Path(tmp_path) / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "testdata"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    (root / "src").symlink_to(REPO / "src")
    return root


def add_tiny(root: Path, metric: dict | None = None) -> None:
    """Add the tiny configuration, its mix and its cell (and ``metric``
    to the per-layer list) as new files and new entries."""
    (root / "bench" / "configs" / "tiny-w8a8.json").write_text(
        json.dumps(TINY))
    (root / "bench" / "traffic" / "chat-tiny.json").write_text(
        json.dumps(MIX))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-w8a8", "source": TINY["source"],
                            "file": "bench/configs/tiny-w8a8.json",
                            "reduced": [], "why": "tests"})
    spec["workloads"].append({"name": CELL, "config": "tiny-w8a8",
                              "traffic": "chat-tiny", "chips": 1,
                              "why": "tests"})
    for m in spec["per_layer"]:
        m["workloads"].append(CELL)
    if metric is not None:
        spec["per_layer"].append(metric)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def run(root: Path, *, seed: int = 2**33 + 5, seconds: float = 2.0,
        trace: bool = False, control: bool = False) -> dict:
    from bench import harness
    cell = harness.load_cell(CELL, root)
    return harness.run(cell, seed=seed, seconds=seconds, trace=trace,
                       t_start=0.0, root=root, require_tpu=False,
                       peaks=PEAKS, log=lambda _m: None, control=control)
