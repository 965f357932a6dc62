import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.tests import dry


def test_peaks_table_names_v5e_with_its_source():
    p = harness.load_peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["int8_ops"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16 * 2**30
    assert "TPU v5e" in p["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(harness.RunError, match="not in"):
        harness.load_peaks("TPU v9 imaginary")


def test_cpu_platform_raises():
    import jax
    with pytest.raises(harness.RunError, match="needs a TPU"):
        harness.device_info(jax, 1)
    with pytest.raises(harness.RunError, match="chip"):
        harness.device_info(jax, len(jax.devices()) + 1, require_tpu=False)


def _run_cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "qwen3-4b-w8a8-pallas.chat-poisson", "--seed", str(2**33 + 9),
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def _no_result(proc):
    for line in proc.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert "correct" not in obj


def test_cli_without_a_tpu_exits_nonzero_without_a_result():
    proc = _run_cli(dry.REPO)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    _no_result(proc)


def test_cli_in_a_bare_benchmark_directory_exits_nonzero(tmp_path):
    shutil.copytree(dry.REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(dry.REPO / "BENCHMARK.json", tmp_path)
    proc = _run_cli(tmp_path)
    assert proc.returncode != 0
    _no_result(proc)
