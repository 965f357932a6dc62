"""Layer 2: rules over lowered stage programs.

Each rule takes a stage's recorded abstract signature (see
``_CountingJit.signatures``), re-lowers it, and inspects the jaxpr /
StableHLO — no workload re-run, no runtime counters.

* **SC301** — a ``convert_element_type`` that widens an integer
  (quantized) operand to float and feeds a ``dot_general`` through
  layout-only ops: the nibble contract is ONE int8 x int8 dot with
  ``preferred_element_type=int32``; an int->float convert on a dot
  operand means the quantized matmul silently runs in f32.
* **SC302** — donation that failed to alias: every leaf of the donated
  caches argument must appear as a ``tf.aliasing_output`` parameter
  attribute in the lowered module (JAX only *warns* when donation is
  unusable — this turns the warning into a gate failure).  Donation
  warnings captured during lowering/compilation fail the rule too.
* **SC303** — host callbacks / transfers in a compiled body
  (``pure_callback`` & friends, infeed/outfeed): the engine step paths
  must be pure device programs.
* **SC304** — the abstract-signature pin: the number of *distinct
  recorded signatures* (blake2b-hashed) per stage must equal the
  pinned ``compile_counts`` contract for the mode.  This proves the
  refill-without-recompile claim from signatures, independent of the
  runtime counter.
* **SC305** — the static flop model must bracket XLA's own
  ``cost_analysis()`` count (scan-once .. fully-multiplied totals,
  widened by ``FLOPS_RTOL``; ``io_bytes`` must not exceed ``bytes
  accessed``): if the jaxpr walk and the compiler disagree about how
  much work a stage does, the capacity model's front-end is lying.

(**SC306**, the static-vs-analytic MAC cross-check against
``core.cycle_model``'s geometry, lives in ``runner`` — it needs the
grid cell's stage geometry, which the jaxpr alone doesn't carry.)
"""

from __future__ import annotations

import hashlib
import math
import warnings

import jax
from jax.extend import core as jex_core

from repro.staticcheck.findings import Finding
from repro.staticcheck.flops import walk_jaxpr, StageCost

# ops that only rearrange bytes: a convert on the far side of these is
# still "the same operand" for dtype-contract purposes
_LAYOUT_OPS = {
    "reshape", "transpose", "broadcast_in_dim", "squeeze",
    "expand_dims", "slice", "dynamic_slice", "concatenate", "rev",
    "copy", "pad",
}
_INT_DTYPES = ("int8", "int4", "uint8", "uint4")
# XLA's flop count and the static walk are independent models of the
# same program.  XLA's HloCostAnalysis counts a while-loop body ONCE
# (it has no static trip-count model), and CPU fusion duplicates
# producers into multiple consumers (~35% observed on the benched
# grid), so the sound invariant is a bracket: XLA's number must lie
# between the scan-once static total and the fully-multiplied static
# total, each side widened by FLOPS_RTOL.  For loop-free stages the
# bracket collapses to a plain two-sided check.  io_bytes (top-level
# avals) must be a lower bound on the compiler's "bytes accessed" up
# to the same slack.
FLOPS_RTOL = 0.50


def signature_hash(signature) -> str:
    """Deterministic digest of one abstract call signature."""
    treedef, leaf_sigs = signature
    h = hashlib.blake2b(digest_size=12)
    h.update(repr(str(treedef)).encode())
    h.update(repr(leaf_sigs).encode())
    return h.hexdigest()


def _jaxprs_with_producers(jaxpr):
    """Yield (jaxpr, {var: producing eqn}) for the tree of sub-jaxprs."""
    stack = [jaxpr]
    while stack:
        jx = stack.pop()
        producers = {}
        for eqn in jx.eqns:
            for out in eqn.outvars:
                producers[out] = eqn
            for val in eqn.params.values():
                vals = val if isinstance(val, (tuple, list)) else [val]
                for v in vals:
                    if isinstance(v, jex_core.ClosedJaxpr):
                        stack.append(v.jaxpr)
                    elif isinstance(v, jex_core.Jaxpr):
                        stack.append(v)
        yield jx, producers


def _trace_operand(var, producers, depth=24):
    """Walk back through layout-only ops; yield the converts found at
    the frontier."""
    frontier = [(var, depth)]
    while frontier:
        v, d = frontier.pop()
        eqn = producers.get(v)
        if eqn is None or d <= 0:
            continue
        name = eqn.primitive.name
        if name == "convert_element_type":
            yield eqn
        elif name in _LAYOUT_OPS:
            for iv in eqn.invars:
                if hasattr(iv, "aval"):
                    frontier.append((iv, d - 1))


def check_quant_widening(jaxpr, path: str, where: str) -> list:
    """SC301 over one (closed) jaxpr."""
    jx = jaxpr.jaxpr if isinstance(jaxpr, jex_core.ClosedJaxpr) else jaxpr
    findings = []
    for sub, producers in _jaxprs_with_producers(jx):
        for eqn in sub.eqns:
            if eqn.primitive.name != "dot_general":
                continue
            for operand in eqn.invars[:2]:
                if not hasattr(operand, "aval"):
                    continue
                for conv in _trace_operand(operand, producers):
                    src = str(conv.invars[0].aval.dtype)
                    dst = str(conv.outvars[0].aval.dtype)
                    if any(src.startswith(t) for t in _INT_DTYPES) \
                            and "float" in dst:
                        findings.append(Finding(
                            "SC301", path, where,
                            f"quantized operand widened {src}->{dst} "
                            f"feeding dot_general "
                            f"{tuple(operand.aval.shape)}: the "
                            "nibble contract is one int8 dot with "
                            "preferred_element_type=int32"))
    return findings


def check_callbacks(jaxpr, path: str, where: str) -> list:
    """SC303 over one (closed) jaxpr."""
    jx = jaxpr.jaxpr if isinstance(jaxpr, jex_core.ClosedJaxpr) else jaxpr
    findings = []
    for sub, _producers in _jaxprs_with_producers(jx):
        for eqn in sub.eqns:
            name = eqn.primitive.name
            if ("callback" in name or "infeed" in name
                    or "outfeed" in name):
                findings.append(Finding(
                    "SC303", path, where,
                    f"host primitive {name!r} in a compiled stage "
                    "body: engine step programs must be pure device "
                    "code"))
    return findings


def check_stage(stage, stage_name: str, cell: str,
                donate_arg_index: int = 1):
    """Run SC301/SC302/SC303/SC305 over every recorded signature of one
    stage.  Returns ``(findings, costs)`` where ``costs`` is a list of
    per-signature dicts (static + compiler-reported numbers)."""
    path = f"jaxpr:{cell}"
    findings: list = []
    costs: list = []
    for sig in stage.signatures:
        args = stage.abstract_args(sig)
        where = f"{stage_name}"
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            lowered = stage.jit_fn.lower(*args)
            traced = stage.jit_fn.trace(*args)
            compiled = lowered.compile()
        jaxpr = traced.jaxpr

        findings += check_quant_widening(jaxpr, path, where)
        findings += check_callbacks(jaxpr, path, where)

        # SC302: donation must have established aliasing
        donated_leaves = len(jax.tree_util.tree_leaves(
            args[donate_arg_index])) if len(args) > donate_arg_index \
            else 0
        alias_count = lowered.as_text().count("tf.aliasing_output")
        donation_warnings = [str(w.message) for w in caught
                             if "donat" in str(w.message).lower()]
        if donation_warnings:
            findings.append(Finding(
                "SC302", path, where,
                f"donation warning during lowering: "
                f"{donation_warnings[0][:160]}"))
        if alias_count < donated_leaves:
            findings.append(Finding(
                "SC302", path, where,
                f"only {alias_count}/{donated_leaves} donated cache "
                "leaves aliased to outputs in the lowered module: the "
                "unaliased pools are copied every dispatch"))

        # SC305: static flop model vs the compiler's own count
        cost = walk_jaxpr(jaxpr)
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        xla_flops = float(ca.get("flops", 0.0) or 0.0)
        xla_bytes = float(ca.get("bytes accessed", 0.0) or 0.0)
        lo = cost.scan_once_flops * (1 - FLOPS_RTOL)
        hi = cost.total_flops * (1 + FLOPS_RTOL)
        if xla_flops > 0 and not (lo <= xla_flops <= hi):
            findings.append(Finding(
                "SC305", path, where,
                f"XLA cost_analysis flops {xla_flops:.0f} outside the "
                f"static bracket [{lo:.0f}, {hi:.0f}] (scan-once "
                f"{cost.scan_once_flops} .. full {cost.total_flops} "
                f"+/- {FLOPS_RTOL:.0%}): the capacity model's static "
                "front-end is off"))
        if xla_bytes > 0 and cost.io_bytes > xla_bytes * (1 + FLOPS_RTOL):
            findings.append(Finding(
                "SC305", path, where,
                f"static io_bytes {cost.io_bytes} exceeds XLA "
                f"bytes-accessed {xla_bytes:.0f}"))

        costs.append({
            "stage": stage_name,
            "cell": cell,
            "signature": signature_hash(sig),
            **cost.to_dict(),
            "xla_flops": xla_flops,
            "xla_bytes_accessed": xla_bytes,
            "aliased_outputs": alias_count,
            "donated_leaves": donated_leaves,
        })
    return findings, costs


def check_pins(engine, expected: dict, cell: str) -> list:
    """SC304: distinct recorded signatures per stage == the pinned
    compile-count contract, proven by hashing the signatures."""
    findings = []
    path = f"jaxpr:{cell}"
    stages = engine.stage_programs()
    for name, pin in expected.items():
        stage = stages.get(name)
        n_sigs = len(stage.signatures) if stage is not None else 0
        hashes = sorted(signature_hash(s) for s in stage.signatures) \
            if stage is not None else []
        if n_sigs != pin:
            findings.append(Finding(
                "SC304", path, name,
                f"{n_sigs} distinct abstract signatures recorded "
                f"(hashes {hashes[:4]}) but the compile-count pin is "
                f"{pin}: a new signature means a recompile edge"))
    for name, stage in stages.items():
        if name not in expected and len(stage.signatures) > 0:
            findings.append(Finding(
                "SC304", path, name,
                f"stage ran {len(stage.signatures)} signatures but has "
                "no pinned compile count for this mode"))
    return findings
