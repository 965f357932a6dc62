"""staticcheck gate: rule units (violation + clean twin per rule),
whole-tree pass on HEAD, and the static-flops-vs-cycle-model tolerance
check on the benched shapes."""

import json
import subprocess
import sys
import textwrap
import warnings
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.serve.engine import _CountingJit  # noqa: E402
from repro.staticcheck import jaxpr_rules, runner  # noqa: E402
from repro.staticcheck.ast_rules import run_ast_rules  # noqa: E402
from repro.staticcheck.findings import (Finding, apply_baseline,  # noqa: E402
                                        load_baseline)
from repro.staticcheck.flops import walk_jaxpr  # noqa: E402
from repro.core.cycle_model import cycles_per_operand  # noqa: E402

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# AST rule units: each rule flags an injected violation and passes its
# clean twin
# ---------------------------------------------------------------------------

def _lint(tmp_path, source, relname="src/repro/mod.py"):
    p = tmp_path / relname
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    return [f.rule for f in run_ast_rules(tmp_path / "src",
                                          repo_root=tmp_path)]


def test_sc101_item_on_traced(tmp_path):
    bad = """
        import jax
        @jax.jit
        def f(x):
            return x.item()
    """
    good = """
        import jax
        @jax.jit
        def f(x):
            return x.sum()
    """
    assert "SC101" in _lint(tmp_path / "bad", bad)
    assert _lint(tmp_path / "good", good) == []


def test_sc102_cast_on_traced(tmp_path):
    bad = """
        import jax
        @jax.jit
        def f(x):
            return x * float(x[0])
    """
    good = """
        import jax
        @jax.jit
        def f(x):
            scale = float(1.5)
            return x * scale
    """
    assert "SC102" in _lint(tmp_path / "bad", bad)
    assert _lint(tmp_path / "good", good) == []


def test_sc103_numpy_on_traced(tmp_path):
    bad = """
        import jax
        import numpy as np
        @jax.jit
        def f(x):
            return np.asarray(x)
    """
    good = """
        import jax
        import numpy as np
        @jax.jit
        def f(x):
            iota = np.arange(4)
            return x + iota
    """
    assert "SC103" in _lint(tmp_path / "bad", bad)
    assert _lint(tmp_path / "good", good) == []


def test_sc104_branch_on_traced(tmp_path):
    bad = """
        import jax
        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
    """
    good_shape = """
        import jax
        @jax.jit
        def f(x):
            if x.shape[0] > 1:
                return x
            return -x
    """
    good_static = """
        import functools
        import jax
        @functools.partial(jax.jit, static_argnames=("flag",))
        def f(x, flag):
            if flag:
                return x
            return -x
    """
    good_none = """
        import jax
        @jax.jit
        def f(x, y=None):
            if y is not None:
                return x + y
            return x
    """
    assert "SC104" in _lint(tmp_path / "bad", bad)
    assert _lint(tmp_path / "g1", good_shape) == []
    assert _lint(tmp_path / "g2", good_static) == []
    assert _lint(tmp_path / "g3", good_none) == []


def test_sc105_host_sync_in_serve(tmp_path):
    bad = """
        import jax
        def step(x):
            return jax.device_get(x)
    """
    good = """
        import numpy as np
        def step(x):
            return np.asarray(x)
    """
    rel = "src/repro/serve/stepper.py"
    assert "SC105" in _lint(tmp_path / "bad", bad, rel)
    assert _lint(tmp_path / "good", good, rel) == []
    # outside serve/ the same code is not an engine step path
    assert _lint(tmp_path / "other", bad, "src/repro/launch/x.py") == []


def test_sc105_allows_only_the_named_wait(tmp_path):
    """A stage's one wait, inside ``with span(WAIT)``, is the engine's
    host boundary; the same call anywhere else, or a ``device_get``
    inside the wait, is still an ad-hoc sync."""
    named = """
        import jax
        import numpy as np
        from repro.serve.spans import WAIT, span
        def step(x):
            with span(WAIT):
                jax.block_until_ready(x)
            return np.asarray(x)
    """
    bare = """
        import jax
        def step(x):
            jax.block_until_ready(x)
            return x
    """
    other_span = """
        import jax
        from repro.serve.spans import UPLOAD, span
        def step(x):
            with span(UPLOAD):
                jax.block_until_ready(x)
            return x
    """
    get_inside = """
        import jax
        from repro.serve.spans import WAIT, span
        def step(x):
            with span(WAIT):
                return jax.device_get(x)
    """
    rel = "src/repro/serve/stepper.py"
    assert _lint(tmp_path / "named", named, rel) == []
    assert "SC105" in _lint(tmp_path / "bare", bare, rel)
    assert "SC105" in _lint(tmp_path / "other", other_span, rel)
    assert "SC105" in _lint(tmp_path / "get", get_inside, rel)


def test_sc201_cache_jit_must_donate(tmp_path):
    bad = """
        import jax
        def fwd(params, caches, tok):
            return tok, caches
        fn = jax.jit(fwd)
    """
    bad_idx = """
        import jax
        def fwd(params, caches, tok):
            return tok, caches
        fn = jax.jit(fwd, donate_argnums=0)
    """
    good = """
        import jax
        def fwd(params, caches, tok):
            return tok, caches
        fn = jax.jit(fwd, donate_argnums=1)
    """
    assert "SC201" in _lint(tmp_path / "bad", bad)
    assert "SC201" in _lint(tmp_path / "bad_idx", bad_idx)
    assert _lint(tmp_path / "good", good) == []


def test_sc202_paging_stays_numpy(tmp_path):
    bad = """
        import jax.numpy as jnp
        def alloc(n):
            return jnp.zeros(n)
    """
    good = """
        import numpy as np
        def alloc(n):
            return np.zeros(n)
    """
    rel = "src/repro/serve/paging.py"
    assert "SC202" in _lint(tmp_path / "bad", bad, rel)
    assert _lint(tmp_path / "good", good, rel) == []


# ---------------------------------------------------------------------------
# jaxpr rule units
# ---------------------------------------------------------------------------

def test_sc301_quant_widening():
    def bad(x_q, w):
        return x_q.astype(jnp.float32) @ w.astype(jnp.float32)

    def good(x_q, w):
        out = jax.lax.dot_general(x_q, w, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        return out.astype(jnp.float32)

    x = jax.ShapeDtypeStruct((8, 16), "int8")
    w = jax.ShapeDtypeStruct((16, 4), "int8")
    bad_f = jaxpr_rules.check_quant_widening(
        jax.jit(bad).trace(x, w).jaxpr, "t", "bad")
    good_f = jaxpr_rules.check_quant_widening(
        jax.jit(good).trace(x, w).jaxpr, "t", "good")
    assert {f.rule for f in bad_f} == {"SC301"}
    assert good_f == []


def test_sc302_dead_donation():
    def dead(x, caches):
        return x + 1.0  # caches unused: donation cannot alias

    def alive(x, caches):
        return x + 1.0, {k: v + 1 for k, v in caches.items()}

    caches = {"k": jnp.ones((8,)), "v": jnp.ones((8,))}
    bad = _CountingJit(dead, donate_argnums=1)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        bad(jnp.ones((4,)), caches)
    f, _ = jaxpr_rules.check_stage(bad, "dead", "unit")
    assert "SC302" in {x.rule for x in f}

    good = _CountingJit(alive, donate_argnums=1)
    good(jnp.ones((4,)), caches)
    f, costs = jaxpr_rules.check_stage(good, "alive", "unit")
    assert "SC302" not in {x.rule for x in f}
    assert costs[0]["aliased_outputs"] == costs[0]["donated_leaves"] == 2


def test_sc303_callback_in_body():
    def bad(x):
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    x = jax.ShapeDtypeStruct((4,), "float32")
    bad_f = jaxpr_rules.check_callbacks(jax.jit(bad).trace(x).jaxpr,
                                        "t", "bad")
    good_f = jaxpr_rules.check_callbacks(
        jax.jit(lambda x: x * 2).trace(x).jaxpr, "t", "good")
    assert {f.rule for f in bad_f} == {"SC303"}
    assert good_f == []


def test_sc304_signature_pins():
    class FakeEngine:
        def __init__(self, stage):
            self._stage = stage

        def stage_programs(self):
            return {"decode_chunk": self._stage}

    churner = _CountingJit(lambda x: x + 1)
    churner(jnp.ones((4,)))
    churner(jnp.ones((8,)))        # second distinct signature
    f = jaxpr_rules.check_pins(FakeEngine(churner),
                               {"decode_chunk": 1}, "unit")
    assert [x.rule for x in f] == ["SC304"]

    stable = _CountingJit(lambda x: x + 1)
    stable(jnp.ones((4,)))
    stable(jnp.ones((4,)))         # same signature twice
    assert jaxpr_rules.check_pins(FakeEngine(stable),
                                  {"decode_chunk": 1}, "unit") == []


# ---------------------------------------------------------------------------
# baseline mechanics
# ---------------------------------------------------------------------------

def test_baseline_suppression_and_staleness():
    f1 = Finding("SC101", "src/a.py", "f", "msg")
    f2 = Finding("SC104", "src/b.py", "g", "msg")
    baseline = {"version": 1, "suppressions": [
        {"key": f1.key, "reason": "known"},
        {"key": "SC999:src/gone.py:h", "reason": "fixed long ago"},
    ]}
    unsup, sup, stale = apply_baseline([f1, f2], baseline)
    assert [f.rule for f in unsup] == ["SC104"]
    assert [f.rule for f in sup] == ["SC101"]
    assert stale == ["SC999:src/gone.py:h"]


def test_committed_baseline_empty_for_serve_and_kernels():
    baseline = load_baseline(REPO / "tools" / "staticcheck_baseline.json")
    for entry in baseline["suppressions"]:
        assert "src/repro/serve" not in entry["key"]
        assert "src/repro/kernels" not in entry["key"]


# ---------------------------------------------------------------------------
# whole-tree runs on HEAD
# ---------------------------------------------------------------------------

def test_ast_layer_clean_on_head():
    findings = run_ast_rules(REPO / "src" / "repro", repo_root=REPO)
    baseline = load_baseline(REPO / "tools" / "staticcheck_baseline.json")
    unsup, _sup, _stale = apply_baseline(findings, baseline)
    assert unsup == [], "\n".join(f.render() for f in unsup)


def test_cli_ast_only_exits_clean(tmp_path):
    report = tmp_path / "report.json"
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "staticcheck.py"),
         "--ast-only", "--report", str(report)],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    data = json.loads(report.read_text())
    assert data["findings"] == []
    assert "SC101" in data["rules"]["ast"]


# ---------------------------------------------------------------------------
# jaxpr layer on a real grid cell + the cycle-model tolerance check
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def nibble_cell():
    cell = runner.GRID_CELLS[1]
    assert cell.name == "nibble-xla"
    return cell, runner.build_cell_engine(cell)


def test_grid_cell_contracts_clean(nibble_cell):
    cell, engine = nibble_cell
    findings = jaxpr_rules.check_pins(engine, cell.expected_pins,
                                      cell.name)
    for name, stage in engine.stage_programs().items():
        f, _ = jaxpr_rules.check_stage(stage, name, cell.name)
        findings += f
    assert findings == [], "\n".join(f.render() for f in findings)


def test_static_flops_match_cycle_model(nibble_cell):
    """The jaxpr walk and the closed-form MAC model (the cycle model's
    geometry) must agree within ``runner.ANALYTIC_RTOL`` (2%) on the
    benched shapes; the cycle bridge must reproduce Table 2's W/4
    ratio."""
    cell, engine = nibble_cell
    for name, stage in engine.stage_programs().items():
        analytic = runner.analytic_stage_macs(name, cell)
        assert analytic is not None
        for sig in stage.signatures:
            cost = walk_jaxpr(stage.jit_fn.trace(
                *stage.abstract_args(sig)).jaxpr)
            rel = (abs(cost.dot_macs - analytic["total_macs"])
                   / analytic["total_macs"])
            assert rel <= runner.ANALYTIC_RTOL, (
                f"{name}: static {cost.dot_macs} vs analytic "
                f"{analytic['total_macs']} ({rel:.1%})")
            # quantized stages carry the nibble 2x-K int-dot load
            assert cost.int_dot_macs > 0
            # Table 2 bridge: nibble streams W/4=2 cycles/operand,
            # shift-add W=8 — a strict 4x cycle win at equal MACs
            from repro.staticcheck.flops import cycle_bridge
            nib = cycle_bridge(cost.dot_macs, "nibble_precompute")
            sa = cycle_bridge(cost.dot_macs, "shift_add")
            assert nib == cost.dot_macs * cycles_per_operand(
                "nibble_precompute", 8)
            assert sa == 4 * nib


def test_stage_roofline_static_front_end(nibble_cell):
    """A stage-cost row converts into roofline terms (compute/memory
    seconds, dominant bound, arithmetic intensity) without any dry-run
    artifact — the capacity model's static front-end."""
    from repro.roofline.analysis import stage_roofline
    cell, engine = nibble_cell
    stage = engine.stage_programs()["decode_chunk"]
    sig = stage.signatures[0]
    cost = walk_jaxpr(stage.jit_fn.trace(*stage.abstract_args(sig)).jaxpr)
    terms = stage_roofline(cost.to_dict())
    assert terms["compute_s"] > 0 and terms["memory_s"] > 0
    assert terms["step_s"] == max(terms["compute_s"], terms["memory_s"])
    low_intensity = terms["arithmetic_intensity"] < terms["ridge_intensity"]
    assert terms["dominant"] == ("memory" if low_intensity else "compute")


def test_static_bytes_bracket_xla(nibble_cell):
    """Static io_bytes (top-level avals) is a floor on XLA's reported
    bytes-accessed for every stage signature."""
    cell, engine = nibble_cell
    for name, stage in engine.stage_programs().items():
        for sig in stage.signatures:
            args = stage.abstract_args(sig)
            cost = walk_jaxpr(stage.jit_fn.trace(*args).jaxpr)
            ca = stage.jit_fn.lower(*args).compile().cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            xla_bytes = float(ca.get("bytes accessed", 0.0) or 0.0)
            if xla_bytes:
                assert cost.io_bytes <= xla_bytes * 1.5, name
            xla_flops = float(ca.get("flops", 0.0) or 0.0)
            if xla_flops:
                assert (cost.scan_once_flops * 0.5 <= xla_flops
                        <= cost.total_flops * 1.5), name
