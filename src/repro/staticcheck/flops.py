"""Static flop/byte extraction from stage jaxprs + the analytic bridge.

``walk_jaxpr`` recursively walks a ``ClosedJaxpr`` (descending into
``pjit`` / ``scan`` / ``while`` / ``cond`` / ``remat`` / ``pallas_call``
sub-jaxprs, multiplying scan bodies by their static ``length``) and
accumulates:

* ``dot_flops`` / ``dot_macs`` — ``2 * batch * M * N * K`` per
  ``dot_general``, split by operand dtype class (``int`` vs ``float``
  dots: the nibble plane-concat contract makes quantized stages carry
  exactly 2x the dense int-MAC count through a *single* int8 dot);
* ``elementwise_flops`` — one flop per output element of arithmetic
  primitives (mirrors XLA's convention closely enough for a static
  cross-check against ``cost_analysis()``);
* ``io_bytes`` — bytes of the top-level jaxpr's input + output avals
  (the dispatch's HBM traffic floor; donated buffers still count once
  on each side, matching how XLA's ``bytes accessed`` treats aliased
  pairs).

``analytic_macs`` computes the same MAC count in closed form from the
``ModelConfig`` + stage geometry — two independent derivations of one
number.  ``cycle_bridge`` converts MACs into multiplier cycles via
``core.cycle_model.cycles_per_operand``, which is what the capacity
model consumes.
"""

from __future__ import annotations

import dataclasses
import math

from jax.extend import core as jex_core
import numpy as np

from repro.core.cycle_model import cycles_per_operand

# primitives counted at one flop per output element
_ELEMENTWISE = {
    "add", "sub", "mul", "div", "rem", "max", "min", "pow",
    "integer_pow", "exp", "log", "log1p", "expm1", "tanh", "logistic",
    "rsqrt", "sqrt", "sign", "abs", "neg", "floor", "ceil", "round",
    "erf", "erf_inv", "cos", "sin", "select_n", "clamp", "nextafter",
    "atan2", "square",
}
# sub-jaxpr-carrying params worth descending into
_SUBJAXPR_PARAMS = ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr",
                    "branches")


@dataclasses.dataclass
class DotRecord:
    lhs_shape: tuple
    rhs_shape: tuple
    lhs_dtype: str
    rhs_dtype: str
    out_dtype: str
    flops: int          # already multiplied by enclosing scan lengths
    macs: int


@dataclasses.dataclass
class StageCost:
    dot_flops: int = 0
    int_dot_macs: int = 0       # integer-operand dots (the quant path)
    float_dot_macs: int = 0     # float-operand dots (attention, dense)
    elementwise_flops: int = 0
    # the same totals with every scan body counted ONCE: XLA's
    # HloCostAnalysis does not multiply while-loop trip counts, so the
    # compiler cross-check brackets its number between `scan_once_*`
    # and the fully-multiplied totals
    scan_once_dot_flops: int = 0
    scan_once_elementwise_flops: int = 0
    io_bytes: int = 0
    has_unbounded_loop: bool = False   # a `while` whose trip count is
    #   not static: its body is counted ONCE (lower bound)
    dots: list = dataclasses.field(default_factory=list)

    @property
    def dot_macs(self) -> int:
        return self.int_dot_macs + self.float_dot_macs

    @property
    def total_flops(self) -> int:
        return self.dot_flops + self.elementwise_flops

    @property
    def scan_once_flops(self) -> int:
        return self.scan_once_dot_flops + self.scan_once_elementwise_flops

    def to_dict(self) -> dict:
        return {
            "dot_flops": self.dot_flops,
            "dot_macs": self.dot_macs,
            "int_dot_macs": self.int_dot_macs,
            "float_dot_macs": self.float_dot_macs,
            "elementwise_flops": self.elementwise_flops,
            "total_flops": self.total_flops,
            "scan_once_flops": self.scan_once_flops,
            "io_bytes": self.io_bytes,
            "has_unbounded_loop": self.has_unbounded_loop,
        }


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(math.prod(shape)) * np.dtype(dtype).itemsize


def _dot_cost(eqn, mult: int) -> DotRecord:
    (lc, rc), (lb, _rb) = eqn.params["dimension_numbers"]
    lhs, rhs = (v.aval for v in eqn.invars[:2])
    contract = math.prod(lhs.shape[i] for i in lc)
    batch = math.prod(lhs.shape[i] for i in lb)
    lhs_free = math.prod(lhs.shape[i] for i in range(len(lhs.shape))
                         if i not in lc and i not in lb)
    r_used = set(rc) | set(_rb)
    rhs_free = math.prod(rhs.shape[i] for i in range(len(rhs.shape))
                         if i not in r_used)
    macs = batch * lhs_free * rhs_free * contract * mult
    out_dtype = str(eqn.outvars[0].aval.dtype)
    return DotRecord(tuple(lhs.shape), tuple(rhs.shape),
                     str(lhs.dtype), str(rhs.dtype), out_dtype,
                     flops=2 * macs, macs=macs)


def _iter_subjaxprs(eqn):
    for key in _SUBJAXPR_PARAMS:
        val = eqn.params.get(key)
        if val is None:
            continue
        vals = val if isinstance(val, (tuple, list)) else [val]
        for v in vals:
            if isinstance(v, jex_core.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jex_core.Jaxpr):
                yield v
    # catch-all for params not in the known list (e.g. custom prims)
    for key, val in eqn.params.items():
        if key in _SUBJAXPR_PARAMS:
            continue
        vals = val if isinstance(val, (tuple, list)) else [val]
        for v in vals:
            if isinstance(v, jex_core.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jex_core.Jaxpr):
                yield v


def _grid_size(eqn) -> int:
    """Static grid product of a pallas_call, 1 if unavailable."""
    gm = eqn.params.get("grid_mapping")
    grid = getattr(gm, "grid", None) if gm is not None else None
    if grid is None:
        grid = eqn.params.get("grid")
    if not grid:
        return 1
    try:
        return int(math.prod(int(g) for g in grid))
    except (TypeError, ValueError):
        return 1


def _walk(jaxpr, cost: StageCost, mult: int, once_mult: int) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            base = _dot_cost(eqn, 1)
            rec = dataclasses.replace(base, flops=base.flops * mult,
                                      macs=base.macs * mult)
            cost.dots.append(rec)
            cost.dot_flops += rec.flops
            cost.scan_once_dot_flops += base.flops * once_mult
            if "int" in rec.lhs_dtype and "int" in rec.rhs_dtype:
                cost.int_dot_macs += rec.macs
            else:
                cost.float_dot_macs += rec.macs
            continue
        if name in _ELEMENTWISE:
            out = eqn.outvars[0].aval
            n = int(math.prod(getattr(out, "shape", ())))
            cost.elementwise_flops += n * mult
            cost.scan_once_elementwise_flops += n * once_mult
            continue
        sub_mult, sub_once = mult, once_mult
        if name == "scan":
            sub_mult = mult * int(eqn.params.get("length", 1))
        elif name == "while":
            cost.has_unbounded_loop = True
        elif name == "pallas_call":
            grid = _grid_size(eqn)
            sub_mult = mult * grid
            sub_once = once_mult * grid
        for sub in _iter_subjaxprs(eqn):
            _walk(sub, cost, sub_mult, sub_once)


def walk_jaxpr(closed) -> StageCost:
    """Accumulate static costs over a ``ClosedJaxpr`` (or ``Jaxpr``)."""
    jaxpr = closed.jaxpr if isinstance(closed, jex_core.ClosedJaxpr) \
        else closed
    cost = StageCost()
    _walk(jaxpr, cost, 1, 1)
    cost.io_bytes = (sum(_aval_bytes(v.aval) for v in jaxpr.invars)
                     + sum(_aval_bytes(v.aval) for v in jaxpr.outvars))
    return cost


# ---------------------------------------------------------------------------
# Analytic closed-form MACs from ModelConfig — the independent derivation
# ---------------------------------------------------------------------------

def _per_token_linear_macs(cfg) -> int:
    """Projection MACs per token for one full forward through the
    repeated attention/MLP stack (dense counting: one MAC per
    multiply-accumulate, quantization factored in by the caller)."""
    d, hd = cfg.d_model, cfg.head_dim
    q = d * cfg.n_heads * hd
    kv = 2 * d * cfg.n_kv_heads * hd
    o = cfg.n_heads * hd * d
    ffn = 3 * d * cfg.d_ff          # SwiGLU/GeGLU: gate + up + down
    return cfg.n_layers * (q + kv + o + ffn)


def _lm_head_macs(cfg, logit_positions: int) -> int:
    return logit_positions * cfg.d_model * cfg.vocab_size


def analytic_macs(cfg, tokens: int, kv_len: int, logit_positions: int,
                  quantized: bool) -> dict:
    """Closed-form per-dispatch MACs for a stage that runs ``tokens``
    tokens, attends over a padded ``kv_len`` context, and projects
    ``logit_positions`` positions through the LM head.

    The nibble plane-concat contract doubles the *integer* contraction
    length of every projection (lo/hi planes along K), so quantized
    stages report 2x linear MACs — that factor is the paper's
    W/4-cycles-per-operand trade made visible in the MAC count."""
    linear = tokens * _per_token_linear_macs(cfg)
    head = _lm_head_macs(cfg, logit_positions)
    attn = (tokens * kv_len * cfg.n_heads * cfg.head_dim * 2
            * cfg.n_layers)
    weight_factor = 2 if quantized else 1
    return {
        "linear_macs": linear * weight_factor,
        "attn_macs": attn,
        "head_macs": head,
        "total_macs": linear * weight_factor + attn + head,
    }


def cycle_bridge(macs: int, arch: str = "nibble_precompute",
                 width: int = 8) -> int:
    """MACs -> multiplier cycles via the paper's Table 2 model: each
    MAC streams one operand through the multiplier at
    ``cycles_per_operand(arch, width)`` cycles."""
    return macs * cycles_per_operand(arch, width)
