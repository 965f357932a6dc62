"""QuantLinear — the projection layer every architecture in the zoo uses.

This is where the paper's technique becomes a first-class framework
feature: one linear layer, five interchangeable execution modes.

===============  ==========================================================
mode             semantics
===============  ==========================================================
``dense``        plain bf16 matmul (the "no paper" baseline)
``qat``          bf16 matmul over fake-quantized operands (training mode;
                 straight-through gradients, serves what it trains)
``w8a8_nibble``  int8 activations × int8 weights via the two-pass nibble
                 decomposition (Algorithm 2 lifted to matmul):
                 ``X·W = 16·(X_hi·W) + X_lo·W``
``w4a8_nibble``  int8 activations × int4 weights: the weight *is* a single
                 nibble plane, stored packed two-per-byte — the paper's
                 storage story (half the weight bytes moved from HBM)
``lut``          the LUT-array formulation: selection (one-hot matmul)
                 from a precomputed scaled-value table instead of
                 arithmetic — the paper's throughput-oriented baseline
===============  ==========================================================

Two execution backends: ``backend="xla"`` (default — lowers to int8
``dot_general`` + shifts; used for the distributed dry-runs) and
``backend="pallas"`` (the hand-tiled kernels in ``repro.kernels``; used
on real chips and validated here under ``interpret=True``).
"""

from __future__ import annotations

import dataclasses
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core import quantize as q
from repro.core.nibble import split_nibbles_signed

QuantMode = Literal["dense", "qat", "w8a8_nibble", "w4a8_nibble", "lut"]

__all__ = ["QuantMode", "linear_init", "linear_apply", "nibble_matmul_xla",
           "lut_matmul_xla"]


def linear_init(key, in_dim: int, out_dim: int,
                dtype=jnp.bfloat16) -> dict:
    """He-style init.  Weights are stored (in_dim, out_dim); quantized
    modes quantize on the fly (weights stay bf16 in the param pytree so
    one checkpoint serves every mode — the serving path folds the
    quantization constant at compile time)."""
    scale = (2.0 / (in_dim + out_dim)) ** 0.5
    w = jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale
    return {"w": w.astype(dtype)}


# ---------------------------------------------------------------------------
# XLA-backend quantized matmuls (the distributable formulations)
# ---------------------------------------------------------------------------

def nibble_matmul_xla(x_q: jax.Array, w_q: jax.Array,
                      *, w_bits: int = 8) -> jax.Array:
    """Single-pass plane-concatenated nibble matmul, int32 accumulation.

    ``x_q``: (..., K) int8.  ``w_q``: (K, N) int8 (w_bits=8) or int4
    values in int8 storage (w_bits=4).  Returns (..., N) int32.

    This is Algorithm 2 with the vector-lane loop replaced by the MXU,
    and the fixed ``<< 4`` alignment folded into the operand layout: the
    high plane is pre-shifted at the operand edge (``hi << 4 == x - lo``
    stays int8-safe) and both planes are concatenated along K, so one
    ``dot_general`` against the twice-stacked weight evaluates both
    "deterministic cycles" in a single MXU pass — the same dataflow the
    Pallas kernels use.
    """
    del w_bits  # int4-in-int8 storage goes through the identical dot
    x_lo, x_hi = split_nibbles_signed(x_q)          # int32 planes, [0,16) / [-8,8)
    x_cat = jnp.concatenate([x_lo, x_hi << 4], axis=-1).astype(jnp.int8)
    w_q = w_q.astype(jnp.int8)
    w_cat = jnp.concatenate([w_q, w_q], axis=0)      # shared tile, reused
    return jax.lax.dot_general(
        x_cat, w_cat, (((x_cat.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


def lut_matmul_xla(x_q: jax.Array, w_q: jax.Array) -> jax.Array:
    """LUT-array formulation: selection instead of multiplication.

    For every activation nibble value k in [0,16) the scaled weight
    ``k·W`` row is conceptually precomputed (the hex string); selection
    of the right row is a one-hot(16) matmul — the TPU-idiomatic
    realisation of the paper's 16:1 slice mux.  Equivalent arithmetic,
    selection-dominated dataflow, exactly the paper's LM design point.
    """
    x_lo, x_hi = split_nibbles_signed(x_q)
    # one-hot over the 16 nibble values: (..., K, 16)
    hot_lo = jax.nn.one_hot(x_lo, 16, dtype=jnp.int8)
    hot_hi = jax.nn.one_hot(x_hi & 0xF, 16, dtype=jnp.int8)
    k_scales = jnp.arange(16, dtype=jnp.int32)
    # signed value of the hi nibble pattern
    k_signed = k_scales - ((k_scales >> 3) << 4)

    # selected scale per (.., K) position — "slice extraction"
    sel_lo = jax.lax.dot_general(hot_lo, k_scales.astype(jnp.int8)[:, None],
                                 (((hot_lo.ndim - 1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.int32)[..., 0]
    sel_hi = jax.lax.dot_general(hot_hi, k_signed.astype(jnp.int8)[:, None],
                                 (((hot_hi.ndim - 1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.int32)[..., 0]
    x_rec = (sel_lo + (sel_hi << 4)).astype(jnp.int8)  # == x_q, via selection
    return jax.lax.dot_general(
        x_rec, w_q.astype(jnp.int8), (((x_rec.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------

def linear_apply(params: dict, x: jax.Array, *,
                 mode: QuantMode = "dense",
                 backend: str = "xla") -> jax.Array:
    """Apply the projection in the selected quantization mode.

    Output dtype follows ``x`` (bf16 in the models); integer modes
    dequantize the int32 accumulator with the folded scales.
    """
    w = params["w"]
    if mode == "dense":
        return jnp.dot(x, w.astype(x.dtype))

    if mode == "qat":
        xq = q.fake_quant(x.astype(jnp.float32), bits=8, axis=-1)
        wq = q.fake_quant(w.astype(jnp.float32), bits=8, axis=0)
        return jnp.dot(xq, wq).astype(x.dtype)

    # integer serving modes -------------------------------------------------
    w_bits = 4 if mode == "w4a8_nibble" else 8
    x_f = x.astype(jnp.float32)
    x_qt = q.quantize(x_f, bits=8, granularity="per_tensor")
    w_qt = q.quantize(w.astype(jnp.float32), bits=w_bits,
                      granularity="per_channel", axis=0)

    if backend == "pallas":
        from repro.kernels import ops  # deferred: kernels import pallas
        # single dispatch path; nibble modes fuse the dequant epilogue
        # in-kernel and emit x.dtype directly (no int32 HBM round-trip)
        return ops.quant_matmul(
            x_qt.values, w_qt.values,
            x_scale=x_qt.scale, w_scale=w_qt.scale.reshape(1, -1),
            w_format="lut" if mode == "lut" else "int8",
            out_dtype=x.dtype)

    if mode == "lut":
        acc = lut_matmul_xla(x_qt.values, w_qt.values)
    else:
        acc = nibble_matmul_xla(x_qt.values, w_qt.values, w_bits=w_bits)

    out = acc.astype(jnp.float32) * x_qt.scale * w_qt.scale.reshape(1, -1)
    # the Pallas kernel rounds its output to x.dtype before it leaves the
    # custom call; without this fence XLA fuses the f32 product into its
    # consumer and skips that rounding on TPU, which parts the two
    # backends' greedy streams
    return jax.lax.optimization_barrier(out.astype(x.dtype))
