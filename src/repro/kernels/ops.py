"""Public jit'd entry points for the kernels (padding, backend dispatch).

``interpret`` defaults to auto: interpret-mode on CPU (validation), real
Mosaic lowering on TPU.  All wrappers accept arbitrary (unaligned)
shapes and pad to the block grid internally; results are exact.

Every quantized-matmul execution path now routes through one dispatcher,
:func:`quant_matmul`: weight format (int8 dense / packed int4 / LUT
selection) and the optional fused dequantization epilogue are arguments,
not separate entry points.  The seed entry points (``nibble_matmul``,
``nibble_matmul_w4``, ``lut_matmul``, ``quant_matmul_fused``) remain as
thin shims over it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.lut_matmul import lut_matmul_pallas
from repro.kernels.nibble_matmul import fused_nibble_matmul_pallas
from repro.kernels.quant_matmul_fused import quantize_rows

__all__ = ["quant_matmul", "nibble_matmul", "nibble_matmul_w4", "lut_matmul",
           "quant_matmul_fused", "flash_mha", "paged_flash_decode"]

W_FORMATS = ("int8", "int4_packed", "lut")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x, m0, m1):
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def _flatten_leading(x):
    """Collapse leading dims to a matrix; return (mat, unflatten)."""
    lead = x.shape[:-1]
    mat = x.reshape(-1, x.shape[-1])

    def unflatten(y):
        return y.reshape(*lead, y.shape[-1])

    return mat, unflatten


def _row_scale(s, m):
    """Normalize a scalar / (M,) / (M,1) scale to f32 (M, 1)."""
    s = jnp.asarray(s, jnp.float32).reshape(-1)[:, None]
    return jnp.broadcast_to(s, (m, 1))


def _col_scale(s, n):
    """Normalize a scalar / (N,) / (1,N) scale to f32 (1, N)."""
    s = jnp.asarray(s, jnp.float32).reshape(-1)[None, :]
    return jnp.broadcast_to(s, (1, n))


# ---------------------------------------------------------------------------
# Block choice: each nibble-kernel call's blocks come from its own shape
# ---------------------------------------------------------------------------

# On the chip the kernel's cost is mostly a fixed overhead per grid step
# (about 0.2-0.3 us on a v5e, whatever the block), so a call wants as few
# steps as VMEM allows.  Blocks never pad a dimension further than the
# 128-multiple it is padded to anyway.
_WIDE = (512, 256, 128)
_SUBLANES_I8 = 32            # int8 sublane tiling: the row block's unit
_SMALL_M = 512               # up to here M is one row block
VMEM_LIMIT = 16 * 2**20      # the default scoped VMEM of a Mosaic kernel

_TILE_PLAN: dict = {}


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _widest(dim: int) -> int:
    """The largest of ``_WIDE`` that divides ``dim`` rounded up to 128."""
    padded = _round_up(dim, 128)
    return next(b for b in _WIDE if padded % b == 0)


def vmem_bytes(bm: int, bn: int, bk: int, *, out_bytes: int = 4,
               scaled: bool = True) -> int:
    """VMEM the nibble kernel holds for one set of blocks: double-buffered
    x, w, scale and out blocks, the int32 accumulator, the in-kernel int32
    planes (x, lo, hi<<4 and the dot's result) and the ``(bm, 2·bk)`` /
    ``(2·bk, bn)`` plane concatenations (int32 before the int8 cast).

    An upper bound: the compiler fuses part of this (compiling for a v5e
    needed 0.3-0.8x the estimate at qwen3-4b's projection shapes)."""
    io = bm * bk + bk * bn + bm * bn * out_bytes
    if scaled:     # (bm, 1) pads to 128 lanes, (1, bn) to 8 sublanes
        io += 4 * (bm * 128 + 8 * bn)
    planes = 4 * (3 * bm * bk + bm * bn)
    concat = 4 * bm * 2 * bk + bm * 2 * bk + 2 * bk * bn
    return 2 * io + 4 * bm * bn + planes + concat


def pick_blocks(m: int, k: int, n: int, w_format: str = "int8", *,
                out_bytes: int = 4, scaled: bool = True
                ) -> tuple[int, int, int]:
    """``(bm, bn, bk)`` for one call, from its shape and format alone.

    ``bm``: M rounded up to 32 while M <= 512 (a decode dispatch is one
    row block), else the largest of 512/256/128 dividing M rounded up to
    128.  ``bn``: that rule on N.  ``bk``: that rule on K; a call of at
    most 512 rows instead takes the widest 128-multiple dividing K rounded
    up to 128, the whole K where it fits (it streams its weights, and each
    K step costs as much as a small dot).  Where the VMEM estimate passes
    the scoped limit, ``bk`` steps down first, then ``bn``.  The "lut"
    and "int4_packed" formats keep 128³ blocks (the packed format's
    in-kernel unpack spills its registers at 512-wide blocks).
    """
    if w_format != "int8":
        return 128, 128, 128
    small = m <= _SMALL_M
    bm = _round_up(max(m, 1), _SUBLANES_I8) if small else _widest(m)
    bn = _widest(n)
    kp = _round_up(k, 128)
    bks = [b for b in (range(kp, 0, -128) if small else _WIDE)
           if kp % b == 0]

    def fits(bn_, bk_):
        return vmem_bytes(bm, bn_, bk_, out_bytes=out_bytes,
                          scaled=scaled) <= VMEM_LIMIT

    while True:
        bk = next((b for b in bks if fits(bn, b)), bks[-1])
        if fits(bn, bk) or bn == 128:
            return bm, bn, bk
        bn //= 2


def tile_plan() -> dict:
    """``(M, K, N, w_format) -> (bm, bn, bk, grid_steps)`` for every
    ``quant_matmul`` call traced in this process (a trace-time record:
    it costs nothing when the compiled program runs)."""
    return dict(_TILE_PLAN)


def quant_matmul(x_q: jax.Array, w: jax.Array, *,
                 x_scale: jax.Array | None = None,
                 w_scale: jax.Array | None = None,
                 w_format: str = "int8",
                 bm: int | None = None, bn: int | None = None,
                 bk: int | None = None,
                 out_dtype=None,
                 interpret: bool | None = None) -> jax.Array:
    """The single dispatch path for every quantized matmul.

    ``x_q``: int8 (..., K).  ``w``: int8 (K, N) for ``w_format`` "int8"
    or "lut"; packed int4 (K, N//2) for "int4_packed".

    Unscaled → exact int32 (..., N).  With scales (``x_scale``
    broadcastable to (M, 1), ``w_scale`` to (1, N)) the dequantization
    runs as the kernel's final-K-step epilogue and the result is
    ``out_dtype`` (bf16 by default) — the int32 accumulator never leaves
    VMEM.  The "lut" format is int32-only (its selection kernel models
    the paper's LUT array); scales there are applied as an XLA epilog.

    Block sizes left as ``None`` come from :func:`pick_blocks`; explicit
    ones win.
    """
    if w_format not in W_FORMATS:
        raise ValueError(f"w_format must be one of {W_FORMATS}: {w_format}")
    if interpret is None:
        interpret = not _on_tpu()
    mat, unflatten = _flatten_leading(x_q)
    m, k = mat.shape
    n = 2 * w.shape[1] if w_format == "int4_packed" else w.shape[1]
    scaled = x_scale is not None or w_scale is not None

    if None in (bm, bn, bk):
        res_dtype = out_dtype if out_dtype is not None else \
            (jnp.bfloat16 if scaled else jnp.int32)
        picked = pick_blocks(m, k, n, w_format, scaled=scaled,
                             out_bytes=jnp.dtype(res_dtype).itemsize)
        bm, bn, bk = (b if b is not None else p
                      for b, p in zip((bm, bn, bk), picked))
    _TILE_PLAN[(m, k, n, w_format)] = (
        bm, bn, bk, (_round_up(m, bm) // bm) * (_round_up(n, bn) // bn)
        * (_round_up(k, bk) // bk))

    xp = _pad_to(mat, bm, bk)
    wp = _pad_to(w, bk, bn // 2 if w_format == "int4_packed" else bn)

    if w_format == "lut":
        out = lut_matmul_pallas(xp, wp, bm=bm, bn=bn, bk=bk,
                                interpret=interpret)
        out = out[:m, :n]
        if scaled:
            out = out.astype(jnp.float32)
            if x_scale is not None:
                out = out * _row_scale(x_scale, m)
            if w_scale is not None:
                out = out * _col_scale(w_scale, n)
            out = out.astype(jnp.bfloat16 if out_dtype is None else out_dtype)
        elif out_dtype is not None:
            out = out.astype(out_dtype)
        return unflatten(out)

    if scaled:
        xs = jnp.ones((m, 1), jnp.float32) if x_scale is None \
            else _row_scale(x_scale, m)
        ws = jnp.ones((1, n), jnp.float32) if w_scale is None \
            else _col_scale(w_scale, n)
        xsp = _pad_to(xs, bm, 1)
        wsp = _pad_to(ws, 1, bn)
    else:
        xsp = wsp = None

    out = fused_nibble_matmul_pallas(
        xp, wp, xsp, wsp,
        w_packed=(w_format == "int4_packed"),
        bm=bm, bn=bn, bk=bk, out_dtype=out_dtype, interpret=interpret)
    return unflatten(out[:m, :n])


# ---------------------------------------------------------------------------
# Seed entry points — thin shims over quant_matmul
# ---------------------------------------------------------------------------

def nibble_matmul(x_q: jax.Array, w_q: jax.Array, *,
                  bm: int = 128, bn: int = 128, bk: int = 128,
                  unroll_passes: bool = True,
                  interpret: bool | None = None) -> jax.Array:
    """int8 (..., K) × int8 (K, N) → int32 (..., N) — the paper's kernel.

    ``unroll_passes`` is retained for API compatibility; both profiles
    lower to the plane-concatenated single-pass kernel.
    """
    del unroll_passes
    return quant_matmul(x_q, w_q, w_format="int8", bm=bm, bn=bn, bk=bk,
                        interpret=interpret)


def nibble_matmul_w4(x_q: jax.Array, w_packed: jax.Array, *,
                     bm: int = 128, bn: int = 128, bk: int = 128,
                     interpret: bool | None = None) -> jax.Array:
    """int8 (..., K) × packed-int4 (K, N//2) → int32 (..., N)."""
    return quant_matmul(x_q, w_packed, w_format="int4_packed",
                        bm=bm, bn=bn, bk=bk, interpret=interpret)


def lut_matmul(x_q: jax.Array, w_q: jax.Array, *,
               bm: int = 128, bn: int = 128, bk: int = 128,
               interpret: bool | None = None) -> jax.Array:
    """int8 (..., K) × int8 (K, N) → int32 (..., N) via LUT selection."""
    return quant_matmul(x_q, w_q, w_format="lut", bm=bm, bn=bn, bk=bk,
                        interpret=interpret)


def quant_matmul_fused(x: jax.Array, w_q: jax.Array, w_scale: jax.Array, *,
                       bm: int = 128, bn: int = 128, bk: int = 128,
                       out_dtype=jnp.bfloat16,
                       interpret: bool | None = None) -> jax.Array:
    """float (..., K) × int8 (K, N) + scales → out_dtype (..., N), fused.

    Per-row symmetric int8 activation quantization runs as a cheap XLA
    prolog on the unpadded rows; the matmul and the scale fold run in the
    single-pass kernel with the bf16 epilogue (no int32 HBM round-trip).
    """
    mat, unflatten = _flatten_leading(x)
    x_q, x_scale = quantize_rows(mat)
    out = quant_matmul(x_q, w_q, x_scale=x_scale, w_scale=w_scale,
                       w_format="int8", bm=bm, bn=bn, bk=bk,
                       out_dtype=out_dtype, interpret=interpret)
    return unflatten(out)


# ---------------------------------------------------------------------------
# Flash attention (custom VJP over the Pallas forward/backward kernels)
# ---------------------------------------------------------------------------

def _pad_seq(x, mult):
    p = (-x.shape[1]) % mult
    if p:
        x = jnp.pad(x, ((0, 0), (0, p), (0, 0)))
    return x


def _pad_dim(x, mult):
    p = (-x.shape[2]) % mult
    if p:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, p)))
    return x


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_mha(q, k, v, scale, causal=True, window=0, softcap=0.0,
              group=1, interpret=None):
    """Flash attention over flat head-major layouts.

    q: (B·H, Sq, d); k/v: (B·KVH, Sk, d/dv) with H = KVH·group and the
    q heads ordered (kv_head, group) so head ``bh`` reads kv row
    ``bh // group``.  Differentiable (custom VJP, both passes in Pallas).
    Unaligned Sq/Sk/d are padded to the 128 grid internally.
    """
    o, _ = _flash_fwd_impl(q, k, v, scale, causal, window, softcap, group,
                           interpret)
    return o


def _flash_fwd_impl(q, k, v, scale, causal, window, softcap, group,
                    interpret):
    from repro.kernels.flash_attention import flash_attention_fwd_pallas
    if interpret is None:
        interpret = not _on_tpu()
    sq, sk, dv = q.shape[1], k.shape[1], v.shape[2]
    qp, kp, vp = _pad_seq(q, 128), _pad_seq(k, 128), _pad_seq(v, 128)
    qp, kp = _pad_dim(qp, 128), _pad_dim(kp, 128)
    vp = _pad_dim(vp, 128)
    o, lse = flash_attention_fwd_pallas(
        qp, kp, vp, scale=scale, causal=causal, window=window,
        softcap=softcap, group=group, interpret=interpret)
    return o[:, :sq, :dv], lse[:, :sq]


def _flash_mha_fwd(q, k, v, scale, causal, window, softcap, group,
                   interpret):
    o, lse = _flash_fwd_impl(q, k, v, scale, causal, window, softcap,
                             group, interpret)
    return o, (q, k, v, o, lse)


def _flash_mha_bwd(scale, causal, window, softcap, group, interpret,
                   res, do):
    from repro.kernels.flash_attention import flash_attention_bwd_pallas
    q, k, v, o, lse = res
    if interpret is None:
        interpret = not _on_tpu()
    sq, sk = q.shape[1], k.shape[1]
    d, dv = q.shape[2], v.shape[2]
    qp, kp, vp = _pad_seq(q, 128), _pad_seq(k, 128), _pad_seq(v, 128)
    qp, kp, vp = _pad_dim(qp, 128), _pad_dim(kp, 128), _pad_dim(vp, 128)
    op = _pad_dim(_pad_seq(o, 128), 128)
    dop = _pad_dim(_pad_seq(do, 128), 128)
    lsep = jnp.pad(lse, ((0, 0), (0, (-sq) % 128)),
                   constant_values=0.0)
    dq, dk_h, dv_h = flash_attention_bwd_pallas(
        qp, kp, vp, op, lsep, dop, scale=scale, causal=causal,
        window=window, softcap=softcap, group=group, interpret=interpret)
    dq = dq[:, :sq, :d].astype(q.dtype)
    # fold per-q-head dk/dv back onto the kv heads (sum over the group)
    bh = q.shape[0]
    bkv = k.shape[0]
    dk_h = dk_h[:, :sk, :d].reshape(bkv, group, sk, d).sum(1)
    dv_h = dv_h[:, :sk, :dv].reshape(bkv, group, sk, dv).sum(1)
    return dq, dk_h.astype(k.dtype), dv_h.astype(v.dtype)


flash_mha.defvjp(_flash_mha_fwd, _flash_mha_bwd)


# ---------------------------------------------------------------------------
# Paged decode attention (page-table-indexed KV pool)
# ---------------------------------------------------------------------------

def paged_flash_decode(q, k_pool, v_pool, table, q_pos, *, scale,
                       window=0, softcap=0.0, interpret=None):
    """Single-token decode attention against a paged KV cache.

    ``q``: (B, 1, H, d) with heads ordered (kv_head, group);
    ``k_pool``/``v_pool``: (num_pages, page_size, KVH, d/dv) shared
    pools; ``table``: (B, max_pages) int32 page table; ``q_pos``: (B,)
    per-slot query positions.  Returns (B, 1, H, dv).

    The kernel walks the page table through scalar-prefetched BlockSpec
    index maps — no gathered (B, max_len, ...) copy of the cache is
    materialized, unlike the XLA reference path.  Head dims are padded
    to the 128-lane grid here; page_size/group alignment is the
    caller's concern on real TPUs (interpret mode takes any shape).
    """
    from repro.kernels.flash_attention import paged_decode_attention_pallas
    if interpret is None:
        interpret = not _on_tpu()
    b, s, h, d = q.shape
    if s != 1:
        raise ValueError(f"paged decode takes one query per slot, got "
                         f"S={s}")
    kvh = k_pool.shape[2]
    g = h // kvh
    dv = v_pool.shape[-1]
    qg = q.reshape(b, kvh, g, d)                  # (B, KVH, G, d)

    def pad_last(x, mult=128):
        p = (-x.shape[-1]) % mult
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, p)]) if p else x

    o = paged_decode_attention_pallas(
        pad_last(qg), pad_last(k_pool), pad_last(v_pool), table, q_pos,
        scale=scale, window=window, softcap=softcap, interpret=interpret)
    return o[..., :dv].reshape(b, 1, h, dv)
