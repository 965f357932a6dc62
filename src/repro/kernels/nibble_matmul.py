"""Pallas TPU kernel: single-pass plane-fused nibble-decomposed matmul.

The paper's Algorithm 2, lifted from a scalar vector lane to an MXU tile
and then fused so each K grid step costs exactly one MXU pass and the
output block touches HBM exactly once.

Kernel dataflow
===============

**Plane-concatenated single dot.**  The int8 activation tile is split
into the paper's fixed 4-bit decomposition — a low-nibble plane
(unsigned, ``[0,16)``) and a high-nibble plane (signed, ``[-8,8)``).
Instead of issuing one ``dot_general`` per plane and aligning the high
pass with ``<< 4`` afterwards (two MXU passes per K step), the fixed
alignment is folded into the *operand layout*: the high plane is
pre-shifted at the operand edge (``hi << 4 == x - lo``, which stays
int8-safe because ``hi`` is in ``[-8,8)``), and the two planes are
concatenated along the contraction dimension into one ``(bm, 2·bk)``
int8 tile.  The matching ``(2·bk, bn)`` weight tile is the shared weight
block stacked twice — the paper's broadcast-operand reuse made literal:
the same VMEM-resident weight tile serves both nibble planes inside a
single MXU pass.

    [ lo | hi<<4 ] @ [ W ]   ==  lo·W + (hi·W) << 4  ==  x·W
                     [ W ]

This preserves the paper's two-cycle semantics — both nibble planes are
still evaluated as structurally separate halves of the contraction, the
precompute (split + fixed shift) happens once per operand at the edge
rather than per partial product (cf. the sign-magnitude-encoder
argument in PAPERS.md), and the weight operand is loaded once and reused
by both planes — while issuing **one** MXU pass per K step instead of
two.

**VMEM scratch accumulation.**  The K loop accumulates into a
``pltpu.VMEM``-allocated int32 scratch block that lives across the K
grid steps (K is the innermost, "arbitrary"-semantics dimension; M and N
are "parallel" so Mosaic can pipeline).  The HBM output block is written
exactly once, at the last K step — replacing the seed kernel's
``o_ref[...] +=`` read-modify-write of the int32 block on every K step.

**Fused dequantization epilogue.**  When scales are supplied, the
last-K-step flush applies the per-row activation scale ``(bm, 1)`` and
per-channel weight scale ``(1, bn)`` to the int32 accumulator and emits
``out_dtype`` (bf16 by default) directly — the int32 accumulator never
materializes in HBM and output traffic is halved.

The packed-int4 weight variant unpacks two nibbles per byte in-kernel
(shift, mask, sign-extend — the paper's shift-based precompute, no
multiplier), halving HBM→VMEM weight traffic, then runs the identical
plane-concatenated dot.

Tiling: grid ``(M/bm, N/bn, K/bk)``.  ``ops.quant_matmul`` picks the
blocks from each call's shape (``ops.pick_blocks``): ``bm`` a multiple of
32 (int8 native tiling is (32, 128)), ``bn`` and ``bk`` multiples of 128,
as wide as VMEM allows, since on the chip each grid step costs a fixed
overhead.  The concatenated contraction width ``2·bk`` remains a multiple
of 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "fused_nibble_matmul_pallas",
    "nibble_matmul_pallas",
    "nibble_matmul_w4_pallas",
]

_DIM_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _split_planes(x_i32):
    """(lo, hi) planes of an int8 tile held in int32: x == hi*16 + lo."""
    lo = x_i32 & 0xF
    hi = (x_i32 - lo) >> 4  # arithmetic shift — hi is signed
    return lo, hi


def _plane_concat(x_i32):
    """Concatenate the nibble planes along K with the alignment folded in.

    Returns the ``(bm, 2·bk)`` int8 tile ``[lo | hi<<4]``.  The fixed
    ``<< 4`` lives in the operand: ``hi << 4 == x - lo`` is in
    ``[-128, 112]`` so the pre-shifted plane is int8-exact.
    """
    lo = x_i32 & 0xF
    hi_shifted = x_i32 - lo            # == hi << 4, int8-safe
    return jnp.concatenate([lo, hi_shifted], axis=-1).astype(jnp.int8)


def _unpack_w4(wp_ref):
    """Unpack a (bk, bn//2) packed-int4 tile to (bk, bn) int8 in-kernel.

    Exactly the paper's shift-based precompute: shift, mask, sign-extend
    — no multiplier.  Even output columns take the low nibble, odd the
    high nibble.
    """
    wp = wp_ref[...].astype(jnp.int32) & 0xFF
    w_lo = wp & 0xF
    w_lo = w_lo - ((w_lo >> 3) << 4)
    w_hi = (wp >> 4) & 0xF
    w_hi = w_hi - ((w_hi >> 3) << 4)
    bk_, half = wp.shape
    return jnp.stack([w_lo, w_hi], axis=-1).reshape(bk_, 2 * half) \
        .astype(jnp.int8)


def _single_pass_dot(x_i32, w_i8):
    """One MXU pass over the concatenated planes: exact int32 x·W."""
    xcat = _plane_concat(x_i32)                        # (bm, 2·bk)
    wcat = jnp.concatenate([w_i8, w_i8], axis=0)       # (2·bk, bn), shared tile
    return jax.lax.dot_general(
        xcat, wcat,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


def _fused_kernel(x_ref, w_ref, o_ref, acc_ref, *, w_packed: bool):
    """int32 output path: scratch-accumulated, flushed at the last K step."""
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _unpack_w4(w_ref) if w_packed else w_ref[...]
    acc_ref[...] += _single_pass_dot(x_ref[...].astype(jnp.int32), w)

    @pl.when(k_step == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _fused_scaled_kernel(x_ref, xs_ref, w_ref, ws_ref, o_ref, acc_ref, *,
                         w_packed: bool):
    """Scaled output path: dequant epilogue fused into the final flush."""
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _unpack_w4(w_ref) if w_packed else w_ref[...]
    acc_ref[...] += _single_pass_dot(x_ref[...].astype(jnp.int32), w)

    @pl.when(k_step == pl.num_programs(2) - 1)
    def _flush():
        x_scale = xs_ref[...].astype(jnp.float32)      # (bm, 1)
        w_scale = ws_ref[...].astype(jnp.float32)      # (1, bn)
        o_ref[...] = (acc_ref[...].astype(jnp.float32)
                      * x_scale * w_scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("w_packed", "bm", "bn", "bk",
                                             "out_dtype", "interpret"))
def fused_nibble_matmul_pallas(x_q: jax.Array, w: jax.Array,
                               x_scale: jax.Array | None = None,
                               w_scale: jax.Array | None = None, *,
                               w_packed: bool = False,
                               bm: int = 128, bn: int = 128, bk: int = 128,
                               out_dtype=None,
                               interpret: bool = True) -> jax.Array:
    """The single fused entry point behind every nibble design.

    ``x_q``: int8 (M, K).  ``w``: int8 (K, N), or packed int4 (K, N//2)
    when ``w_packed``.  Unscaled → exact int32 (M, N).  With both
    ``x_scale`` (M, 1) and ``w_scale`` (1, N) f32 → the dequant epilogue
    runs in-kernel and emits ``out_dtype`` (default bf16) without an
    int32 HBM round-trip.

    Dimensions must be multiples of the block sizes (``ops.quant_matmul``
    handles padding).  ``interpret=True`` runs the kernel body on CPU for
    validation; pass ``False`` on a real TPU.
    """
    m, k = x_q.shape
    k2, n_stored = w.shape
    n = 2 * n_stored if w_packed else n_stored
    assert k == k2, (x_q.shape, w.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        ((m, n, k), (bm, bn, bk))
    scaled = x_scale is not None or w_scale is not None
    if scaled:
        assert x_scale is not None and w_scale is not None, \
            "pass both scales (use ones for the identity scale)"
        out_dtype = jnp.bfloat16 if out_dtype is None else out_dtype
    else:
        out_dtype = jnp.int32 if out_dtype is None else out_dtype

    grid = (m // bm, n // bn, k // bk)
    w_spec = pl.BlockSpec((bk, bn // 2 if w_packed else bn),
                          lambda i, j, kk: (kk, j))
    x_spec = pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))
    common = dict(
        grid=grid,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_DIM_SEMANTICS),
        interpret=interpret,
    )

    if not scaled:
        kernel = functools.partial(_fused_kernel, w_packed=w_packed)
        return pl.pallas_call(
            kernel,
            in_specs=[x_spec, w_spec],
            **common,
        )(x_q, w)

    kernel = functools.partial(_fused_scaled_kernel, w_packed=w_packed)
    return pl.pallas_call(
        kernel,
        in_specs=[
            x_spec,
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            w_spec,
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        **common,
    )(x_q, x_scale.reshape(m, 1).astype(jnp.float32), w,
      w_scale.reshape(1, n).astype(jnp.float32))


# ---------------------------------------------------------------------------
# Back-compat shims — the seed entry points, now thin wrappers
# ---------------------------------------------------------------------------

def nibble_matmul_pallas(x_q: jax.Array, w_q: jax.Array, *,
                         bm: int = 128, bn: int = 128, bk: int = 128,
                         unroll_passes: bool = True,
                         interpret: bool = True) -> jax.Array:
    """int8 (M,K) × int8 (K,N) → int32 (M,N), exact.

    ``unroll_passes`` is retained for API compatibility; both of the
    seed's execution profiles now lower to the same plane-concatenated
    single-pass kernel (the "sequential vs unrolled" distinction moved
    from two dot issues to two halves of one contraction).
    """
    del unroll_passes
    return fused_nibble_matmul_pallas(x_q, w_q, bm=bm, bn=bn, bk=bk,
                                      interpret=interpret)


def nibble_matmul_w4_pallas(x_q: jax.Array, w_packed: jax.Array, *,
                            bm: int = 128, bn: int = 128, bk: int = 128,
                            interpret: bool = True) -> jax.Array:
    """int8 (M,K) × packed-int4 (K, N//2) → int32 (M,N), exact."""
    return fused_nibble_matmul_pallas(x_q, w_packed, w_packed=True,
                                      bm=bm, bn=bn, bk=bk,
                                      interpret=interpret)
