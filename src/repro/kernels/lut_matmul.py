"""Pallas TPU kernel: LUT-based array multiplier as selection matmul.

The paper's Fig. 1 design: multiplication by lookup — precomputed scaled
values of the *shared* operand are selected by the other operand's
nibbles, then aligned and summed.  TPUs have no per-lane 16:1 mux in the
MXU datapath, so the TPU-idiomatic realisation of the selection network
is a **one-hot matmul** against the precomputed table (DESIGN.md §2):

* per (bk, bn) weight tile, build the hex-string analogue
  ``table[k*16+v, n] = scale(v) · w[k, n]`` — sixteen scaled copies of
  the broadcast tile, precomputed once per grid step and held in VMEM
  (the paper's ResStrings);
* the activation nibble plane becomes a one-hot matrix
  ``onehot[m, k*16+v] = (x_nibble[m, k] == v)`` — the mux select lines;
* the product is ``onehot @ table`` — deterministic selection +
  accumulation, no arithmetic partial products.

Like the nibble kernel, the two plane selections are fused into **one**
MXU pass: the lo/hi one-hot planes are concatenated along the selection
dimension and the hi table carries the fixed ``<< 4`` alignment folded
in (int16-safe: ``|8·127| << 4 < 2^15``).  The K loop accumulates into a
VMEM scratch block and the int32 output block is written exactly once,
at the last K step.

This preserves the paper's design point exactly: single-pass,
selection-dominated, and more expensive per element than the nibble
kernel (the selection matmul has 16× the contraction width) — which is
precisely the area/power story Fig. 4 tells, translated to FLOPs/bytes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["lut_matmul_pallas"]


def _lut_matmul_kernel(x_ref, w_ref, o_ref, acc_ref):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.int32)                    # (bm, bk)
    w = w_ref[...].astype(jnp.int32)                    # (bk, bn)
    bm, bk = x.shape
    _, bn = w.shape

    # --- precompute: sixteen scaled copies of the shared weight tile ----
    # lo rows use unsigned scales 0..15; hi rows use the signed nibble
    # values (v - 16 for v >= 8) with the fixed << 4 alignment folded
    # into the table (int16 range is sufficient: |8·127·16| < 2^15).
    v = jnp.arange(16, dtype=jnp.int32)
    v_signed = v - ((v >> 3) << 4)
    # (bk, 16, bn) -> (bk*16, bn); "ResString" layout: nibble-major per k
    table_lo = (w[:, None, :] * v[None, :, None]).reshape(bk * 16, bn)
    table_hi = (w[:, None, :] * (v_signed << 4)[None, :, None]) \
        .reshape(bk * 16, bn)

    # --- selection: one-hot of each nibble plane, concatenated ----------
    x_lo = x & 0xF
    x_hi = (x >> 4) & 0xF                               # raw hi pattern
    col = jax.lax.broadcasted_iota(jnp.int32, (bm, bk, 16), 2)

    def onehot(nib):
        return (nib[:, :, None] == col).astype(jnp.int8).reshape(bm, bk * 16)

    hot = jnp.concatenate([onehot(x_lo), onehot(x_hi)], axis=1)
    table = jnp.concatenate([table_lo, table_hi], axis=0).astype(jnp.int16)
    acc_ref[...] += jax.lax.dot_general(                # one selection pass
        hot, table,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k_step == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def lut_matmul_pallas(x_q: jax.Array, w_q: jax.Array, *,
                      bm: int = 128, bn: int = 128, bk: int = 128,
                      interpret: bool = True) -> jax.Array:
    """int8 (M,K) × int8 (K,N) → int32 (M,N) via LUT selection, exact.

    VMEM note: the precomputed table is 2 × (bk·16, bn) int16 — at the
    128/128 defaults that is 16 MiB-scale-safe (2 × 128·16·128·2 B =
    1 MiB) but it *is* the dominant footprint, exactly as the hex strings
    dominate the RTL design's area.
    """
    m, k = x_q.shape
    k2, n = w_q.shape
    assert k == k2
    assert m % bm == 0 and n % bn == 0 and k % bk == 0

    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _lut_matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_q, w_q)
