"""A configuration, a traffic mix and a per-layer metric are added as
new files (and new entries in ``BENCHMARK.json``), and the harness runs
them on its CPU dry path with every existing file left as it was."""

import hashlib
import json

import pytest

from bench.tests import dry

READER = '''"""Requests due in the window that were served a first token:
a metric added by a file of its own."""


def read(ctx):
    return sum(r["window"] and r["t_first"] is not None
               for r in ctx["requests"])
'''
METRIC = {"name": "started_requests", "unit": "requests",
          "better": "higher", "source": "program_counter",
          "layer": "scheduler", "moves": "tok_s", "workloads": [dry.CELL]}


def _digests(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in (root / "bench").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = dry.copy(tmp_path_factory.mktemp("dryrun"))
    before = _digests(root)
    spec_before = json.loads((root / "BENCHMARK.json").read_text())
    dry.add_tiny(root, METRIC)
    (root / "bench" / "metrics" / "started_requests.py").write_text(READER)
    after = _digests(root)
    assert all(after[k] == v for k, v in before.items())
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads", "end_to_end"):
        assert spec[key][:len(spec_before[key])] == spec_before[key]
    return root


def test_untraced_run_reports_the_end_to_end_metrics(root):
    r = dry.run(root)
    assert r["correct"] is True, r["checks"]
    declared = json.loads((dry.REPO / "BENCHMARK.json").read_text())
    assert set(r["metrics"]) == {m["name"] for m in declared["end_to_end"]}
    assert {"tok_s", "setup_s"} <= set(r["metrics"])
    assert r["metrics"]["tok_s"]["unit"] == "tokens/s"
    assert r["metrics"]["tok_s"]["value"] > 0
    assert r["device"]["platform"] == "cpu"


def test_traced_run_reports_the_added_metric(root):
    r = dry.run(root, trace=True)
    assert r["correct"] is True, r["checks"]
    m = r["metrics"]
    assert m["started_requests"] == {"value": 8.0, "unit": "requests"}
    assert {"concurrency", "mfu"} <= set(m)
    # no device plane on the CPU: the trace's readers read nothing
    assert not {"decode_step_ms", "nibble_roofline", "idle_share",
                "prefill_pad_share"} & set(m)
    assert 0 < m["mfu"]["value"] < 100
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert list(r)[-1] == "checks"
