"""Gate orchestration: AST layer + the quant x backend x mode grid.

Each grid cell builds a tiny (``reduced``) engine, drives a few
requests through it so every stage records its abstract signatures,
then hands the stages to the jaxpr rules.  The cells mirror the
benched serving grid (``benchmarks/serve_bench.py``): dense and
nibble-quantized programs on both matmul backends, plus the spec and
wave modes whose compile-pin contracts differ.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path

import numpy as np

from repro.staticcheck.findings import Finding
from repro.staticcheck.ast_rules import run_ast_rules
from repro.staticcheck import jaxpr_rules
from repro.staticcheck.flops import analytic_macs, cycle_bridge
from repro.roofline.analysis import stage_roofline

# benched serving shape (reduced yi-6b), kept tiny: the contracts are
# shape-independent, so the gate runs in CI seconds, not bench minutes
ARCH = "yi-6b"
BATCH = 4
MAX_LEN = 32
PREFILL_LEN = 8
DECODE_CHUNK = 4
PAGE_SIZE = 4
SPEC_K = 4
WAVE_CHUNK = 4
WAVE_GROUP = 2


@dataclasses.dataclass(frozen=True)
class GridCell:
    name: str
    quant_mode: str
    backend: str
    mode: str                     # "plain" | "spec" | "wave"

    @property
    def expected_pins(self) -> dict:
        if self.mode == "spec":
            return {"prefill": 1, "decode_chunk": 0, "draft": 1,
                    "verify": 1}
        if self.mode == "wave":
            return {"prefill": 0, "decode_chunk": 1, "prefill_chunk": 1}
        return {"prefill": 1, "decode_chunk": 1}


GRID_CELLS = (
    GridCell("dense-xla", "dense", "xla", "plain"),
    GridCell("nibble-xla", "w8a8_nibble", "xla", "plain"),
    GridCell("nibble-pallas", "w8a8_nibble", "pallas", "plain"),
    GridCell("nibble-xla-spec", "w8a8_nibble", "xla", "spec"),
    GridCell("nibble-xla-wave", "w8a8_nibble", "xla", "wave"),
)


def build_cell_engine(cell: GridCell):
    """Build the cell's engine and run a tiny workload so every stage
    records its signatures (3 requests, mixed prompt lengths)."""
    import jax
    from repro.configs import get_config, reduced
    from repro.models import model_init
    from repro.serve.engine import Engine, ServeConfig

    cfg = reduced(get_config(ARCH))
    scfg = ServeConfig(
        batch=BATCH, max_len=MAX_LEN, prefill_len=PREFILL_LEN,
        decode_chunk=DECODE_CHUNK, page_size=PAGE_SIZE,
        quant_mode=cell.quant_mode,
        quant_backend=cell.backend,
        spec_decode=(cell.mode == "spec"), spec_k=SPEC_K,
        prefill_chunk=(WAVE_CHUNK if cell.mode == "wave" else 0),
        admit_group=(WAVE_GROUP if cell.mode == "wave" else 1),
    )
    params = model_init(jax.random.PRNGKey(0), cfg)
    engine = Engine(cfg, params, scfg)
    rng = np.random.default_rng(0)
    for n, length in enumerate((5, 8, 3)):
        engine.submit(rng.integers(1, cfg.vocab_size,
                                   size=length).astype(np.int32),
                      max_new_tokens=4)
    engine.run()
    return engine


# per-stage geometry for the analytic cross-check: tokens processed,
# padded attention context, and LM-head positions per dispatch
def _stage_geometry(stage: str, cell: GridCell) -> dict | None:
    if stage == "prefill":
        return dict(tokens=PREFILL_LEN, kv_len=PREFILL_LEN,
                    logit_positions=1)
    if stage == "prefill_chunk":
        # the wave program projects the LM head over every (G, C)
        # position and gathers per-lane last tokens afterwards
        return dict(tokens=WAVE_GROUP * WAVE_CHUNK, kv_len=MAX_LEN,
                    logit_positions=WAVE_GROUP * WAVE_CHUNK)
    if stage == "decode_chunk":
        return dict(tokens=BATCH * DECODE_CHUNK, kv_len=MAX_LEN,
                    logit_positions=BATCH * DECODE_CHUNK)
    if stage == "draft":
        return dict(tokens=BATCH * SPEC_K, kv_len=MAX_LEN,
                    logit_positions=BATCH * SPEC_K)
    if stage == "verify":
        return dict(tokens=BATCH * (SPEC_K + 1), kv_len=MAX_LEN,
                    logit_positions=BATCH * (SPEC_K + 1))
    return None


def _stage_quantized(stage: str, cell: GridCell) -> bool:
    if cell.quant_mode == "dense":
        return False
    if cell.mode == "spec":
        # spec pins prefill/verify to dense; only the draft runs
        # quantized
        return stage == "draft"
    return True


def analytic_stage_macs(stage: str, cell: GridCell) -> dict | None:
    """Closed-form MACs for one stage dispatch on the gate's shapes."""
    from repro.configs import get_config, reduced
    geo = _stage_geometry(stage, cell)
    if geo is None:
        return None
    cfg = reduced(get_config(ARCH))
    return analytic_macs(cfg, quantized=_stage_quantized(stage, cell),
                         **geo)


# SC306: static jaxpr MACs vs the closed-form analytic model derived
# from ModelConfig geometry.  On the xla cells these two independent
# derivations agree EXACTLY (every projection/attention/head dot is
# accounted); the tolerance absorbs future benign reassociations.
# Pallas cells are exempt: the 128-wide kernel blocks pad the reduced
# model's 64-wide operands, so the grid genuinely executes ~48-78x the
# useful MACs — that padding blow-up is visible in the report table
# instead.
ANALYTIC_RTOL = 0.02


def run_jaxpr_layer(cells=GRID_CELLS):
    """Build + drive every grid cell, run the jaxpr rules, and emit the
    per-stage cost table (static walk + analytic model + cycle
    bridge)."""
    findings: list = []
    stage_table: list = []
    for cell in cells:
        engine = build_cell_engine(cell)
        findings += jaxpr_rules.check_pins(engine, cell.expected_pins,
                                           cell.name)
        for name, stage in sorted(engine.stage_programs().items()):
            # pallas cells: the kernel grid executes padded tiles, so
            # the flop cross-checks (SC305/SC306) are xla-cells-only;
            # the contract rules still run
            f, costs = jaxpr_rules.check_stage(stage, name, cell.name)
            if cell.backend != "xla":
                f = [x for x in f if x.rule != "SC305"]
            findings += f
            analytic = analytic_stage_macs(name, cell)
            for c in costs:
                if analytic is not None:
                    c["analytic_macs"] = analytic["total_macs"]
                    c["analytic_detail"] = analytic
                    rel = (abs(c["dot_macs"] - analytic["total_macs"])
                           / max(analytic["total_macs"], 1))
                    c["analytic_rel_err"] = rel
                    if cell.backend == "xla" and rel > ANALYTIC_RTOL:
                        findings.append(Finding(
                            "SC306", f"jaxpr:{cell.name}", name,
                            f"static dot MACs {c['dot_macs']} vs "
                            f"analytic {analytic['total_macs']} "
                            f"disagree by {rel:.1%} "
                            f"(> {ANALYTIC_RTOL:.0%}): the stage "
                            "geometry or the MAC model drifted"))
                c["nibble_cycles"] = cycle_bridge(
                    c["dot_macs"], "nibble_precompute")
                c["shift_add_cycles"] = cycle_bridge(
                    c["dot_macs"], "shift_add")
                c["roofline"] = stage_roofline(c)
            stage_table += costs
    return findings, stage_table


def run_gate(src_root: str | Path, repo_root: str | Path | None = None,
             ast_only: bool = False, cells=GRID_CELLS):
    """The full gate: AST layer + (optionally) the jaxpr grid.

    Returns ``(findings, report)`` where ``report`` is the
    JSON-serializable summary ``--report`` emits."""
    findings = run_ast_rules(src_root, repo_root)
    stage_table: list = []
    if not ast_only:
        jf, stage_table = run_jaxpr_layer(cells)
        findings += jf
    report = {
        "rules": {
            "ast": ["SC101", "SC102", "SC103", "SC104", "SC105",
                    "SC201", "SC202"],
            "jaxpr": [] if ast_only else
                     ["SC301", "SC302", "SC303", "SC304", "SC305",
                      "SC306"],
        },
        "grid": [] if ast_only else [c.name for c in cells],
        "findings": [f.to_dict() for f in findings],
        "stage_costs": stage_table,
    }
    return findings, report
