"""Attention: GQA/MQA, sliding-window, qk-norm, softcap, MLA, cross-attn.

One implementation serves training (full causal), prefill (causal +
cache write-out) and decode (single query vs cache).  Long sequences use
query-chunked evaluation (lax.scan over query blocks) so activation
memory stays O(S·chunk) instead of O(S²) — required for the 32k cells.

Grouped-query attention is computed in grouped form (queries reshaped to
(KV-heads × group)) so K/V are never materialised at full head count —
this matters for the decode roofline, where KV bytes dominate.
"""

from __future__ import annotations

import functools
import jax
import jax.numpy as jnp

from repro.core.linear import linear_apply, linear_init
from repro.models.layers import apply_rope, rms_norm, rms_norm_init, rope

__all__ = ["attn_init", "attn_apply", "mla_init", "mla_apply",
           "init_kv_cache", "init_mla_cache", "scatter_cache_rows",
           "init_paged_kv_cache", "init_paged_mla_cache",
           "scatter_paged_rows", "scatter_prefill_rows", "gather_pages"]

_NEG_INF = -2.0 ** 30

# named scopes of the KV cache's two costs in a paged decode step, so a
# device trace can put time on each: writing the new rows, and gathering
# the pages back into position order
CACHE_WRITE = "attn.cache_write"
GATHER = "attn.gather"

# When True, the query-chunk loop is a Python loop (static unroll) so the
# compiled HLO contains every chunk — used by the dry-run's cost pass,
# because XLA's cost_analysis counts a while body once regardless of trip
# count.  Production lowering keeps lax.scan (flat compile time).
_UNROLL_CHUNKS = False

import contextlib


@contextlib.contextmanager
def unrolled_chunks():
    global _UNROLL_CHUNKS
    prev, _UNROLL_CHUNKS = _UNROLL_CHUNKS, True
    try:
        yield
    finally:
        _UNROLL_CHUNKS = prev


# ---------------------------------------------------------------------------
# Cache row scatter: scalar (whole-batch) or per-slot write positions
# ---------------------------------------------------------------------------

def scatter_cache_rows(buf, new, index):
    """Write ``new`` (B, S_new, ...) into ``buf`` (B, L, ...) at ``index``.

    ``index`` is either a scalar (every sequence writes at the same
    offset — the classic lockstep decode) or a ``(B,)`` int32 vector of
    per-slot offsets (continuous batching: each slot is an independent
    sequence at its own position).  The single-row vector case is a
    vmapped ``dynamic_update_slice`` over the batch axis, so the
    compiled program is shape-identical for every position assignment.

    Multi-row vector writes (S_new > 1 — the speculative-decode verify
    forward scatters ``k+1`` rows per slot at once) use a positional
    scatter with each row's target clipped to the last slab row:
    ``dynamic_update_slice`` would *shift the whole window down* when
    ``index > L - S_new``, corrupting live rows below the write
    position, whereas clipping collapses only the overflowing rows onto
    row ``L - 1`` — a row no query ever attends before its owner
    rewrites it (the idempotent-write invariant; the engine never lets
    a request's *accepted* stream write past the slab).
    """
    new = new.astype(buf.dtype)
    index = jnp.asarray(index)
    with jax.named_scope(CACHE_WRITE):
        if index.ndim == 0:
            start = (0, index) + (0,) * (buf.ndim - 2)
            return jax.lax.dynamic_update_slice(buf, new, start)
        if new.shape[1] == 1:
            def one(b, n, i):
                return jax.lax.dynamic_update_slice(
                    b, n, (i,) + (0,) * (b.ndim - 1))

            return jax.vmap(one)(buf, new, index)
        b, s = new.shape[:2]
        pos = jnp.clip(index[:, None].astype(jnp.int32)
                       + jnp.arange(s, dtype=jnp.int32)[None, :],
                       0, buf.shape[1] - 1)
        return buf.at[jnp.arange(b)[:, None], pos].set(new)


# ---------------------------------------------------------------------------
# Paged KV cache: shared page pool + per-slot page-table indirection
# ---------------------------------------------------------------------------
#
# Layout: each layer's cache leaf is a shared ``(num_pages, page_size,
# ...)`` pool; a ``(B, max_pages)`` int32 page table (built by
# ``serve.paging``) maps slot positions to pool pages.  Page ids are
# data, not shape — one compilation serves every allocation pattern, so
# slot refill and page recycling never recompile.

def scatter_paged_rows(pool, new, table, index):
    """Write decode rows per slot through the page table.

    ``pool``: (num_pages, page_size, ...); ``new``: (B, S, ...);
    ``table``: (B, max_pages) int32; ``index``: scalar or (B,) start
    position.  Row ``index[b] + j`` of slot ``b`` lands at pool position
    ``(table[b, pos // page_size], pos % page_size)``.  Distinct live
    slots own distinct pages, so the scatter never collides; idle
    slots' table rows all point at the trash page, where their frozen
    idempotent rewrites are harmless.

    The multi-row case (S > 1 — the speculative-decode verify forward
    writes ``k+1`` rows per slot in one dispatch) clips each row's
    logical position to the table's addressable range, so overflowing
    rows collapse onto logical row ``max_len - 1`` — resolved through
    the row's last table entry to either the trash page (unbooked tail)
    or the slot's final row, which no query attends before its owner's
    final write rewrites it (the idempotent-write invariant).
    """
    with jax.named_scope(CACHE_WRITE):
        ps = pool.shape[1]
        b, s = new.shape[:2]
        index = jnp.broadcast_to(
            jnp.asarray(index, jnp.int32).reshape(-1), (b,))
        if s == 1:
            page = jnp.take_along_axis(table, (index // ps)[:, None],
                                       axis=1)[:, 0]
            return pool.at[page, index % ps].set(
                new[:, 0].astype(pool.dtype))
        pos = jnp.clip(
            index[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :],
            0, table.shape[1] * ps - 1)
        page = jnp.take_along_axis(table, pos // ps, axis=1)
        return pool.at[page, pos % ps].set(new.astype(pool.dtype))


def gather_pages(pool, table):
    """Reassemble per-slot contiguous caches from the page pool.

    (num_pages, page_size, ...) gathered through (B, max_pages) →
    (B, max_pages · page_size, ...): the XLA reference decode path —
    after the gather the attention math is bit-identical to the unpaged
    cache (rows beyond a slot's live length hold garbage from the trash
    page or stale pages, exactly where the causal mask already writes
    ``-inf``).
    """
    b, mp = table.shape
    with jax.named_scope(GATHER):
        g = jnp.take(pool, table, axis=0)       # (B, MP, page_size, ...)
        return g.reshape(b, mp * pool.shape[1], *pool.shape[2:])


def scatter_prefill_rows(pool, new, row, start):
    """Write a prefilled KV segment through one slot's page-table row.

    ``pool``: (num_pages, page_size, ...); ``new``: (1, S, ...) rows for
    global positions ``start + [0, S)``; ``row``: (max_pages,) int32.
    Row-granular (unlike the whole-page merge), so a copy-on-write tail
    page keeps its cached rows below ``start`` while the fresh suffix
    rows land beside them.  Positions past the slot's budget clamp to
    the last logical row — those writes carry pad-token garbage and land
    on the row's trailing entry (an unbooked slot points it at the trash
    page; a fully booked slot's final row is rewritten by its final
    decode step before any query can attend to it).
    """
    ps = pool.shape[1]
    s = new.shape[1]
    with jax.named_scope(CACHE_WRITE):
        pos = jnp.clip(jnp.asarray(start, jnp.int32) + jnp.arange(s), 0,
                       row.shape[0] * ps - 1)
        page = jnp.take(row, pos // ps)
        return pool.at[page, pos % ps].set(new[0].astype(pool.dtype))


def _splice_context(ctx, new, context_start):
    """Fixed-length prefix splice: position ``j`` takes the *cached* row
    ``ctx[:, j]`` below ``context_start`` and the freshly computed row
    ``new[:, j - context_start]`` at or above it.  The buffer length
    stays exactly ``new``'s, so the attention reduction downstream is
    shape-identical to an uncached full prefill — with ``context_start
    == 0`` the splice returns ``new``'s values bit-for-bit, which is
    what keeps cache-miss prefills bit-identical to a no-cache engine's.
    """
    s = new.shape[1]
    shape = (1, s) + (1,) * (new.ndim - 2)
    is_ctx = (jnp.arange(s) < context_start).reshape(shape)
    shifted = jnp.roll(new, context_start, axis=1)
    return jnp.where(is_ctx, ctx[:, :s].astype(new.dtype), shifted)


# ---------------------------------------------------------------------------
# Masked online-softmax attention core (query-chunked)
# ---------------------------------------------------------------------------

def _attend_block(q, k, v, q_pos, k_pos, *, scale, causal, window, softcap):
    """q: (B,Sq,KVH,G,D); k/v: (B,Sk,KVH,Dk/Dv); returns (B,Sq,KVH,G,Dv).

    QK^T upcasts in the contraction (``preferred_element_type``) — no
    f32 copies of Q/K are materialized (those copies were ~10 GiB each
    on the deepseek-v3 MLA prefix layers)."""
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    mask = jnp.ones((), jnp.bool_)
    dq = q_pos[:, None, None, :, None]          # (B,1,1,Sq,1)
    dk = k_pos[:, None, None, None, :]          # (B,1,1,1,Sk)
    if causal:
        mask = mask & (dk <= dq)
    if window:
        mask = mask & (dq - dk < window)
    logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out


_PROBS_BUDGET_BYTES = 2 * 2 ** 30  # per-chunk f32 logits budget


def attention_core(q, k, v, q_pos, k_pos, *, scale, causal=True,
                   window=0, softcap=0.0, q_chunk=1024):
    """q: (B,Sq,H,Dk) grouped against k/v: (B,Sk,KVH,·).  f32 math.

    The query-chunk size adapts so one chunk's f32 logits stay under
    ~2 GiB per device: the backward pass re-materializes (B,H,qc,Sk)
    logits + their gradient for the live chunk, and at deepseek scale
    (H=128, S=4096) a 1024-chunk makes that a ~70 GiB transient — the
    dominant training-memory term (EXPERIMENTS.md §Perf iter 7)."""
    b, sq, h, dk = q.shape
    kvh = k.shape[2]
    g = h // kvh
    dv = v.shape[-1]
    sk = k.shape[1]
    per_row = b * h * sk * 4                    # f32 logits bytes per q row
    budget_rows = max(1, _PROBS_BUDGET_BYTES // max(per_row, 1))
    while q_chunk > 128 and q_chunk > budget_rows:
        q_chunk //= 2
    qg = q.reshape(b, sq, kvh, g, dk)

    if _UNROLL_CHUNKS:
        # cost-pass lowering: total attention FLOPs/bytes are invariant
        # to the chunk split (every chunk scores against full K), so use
        # the minimum unroll (2 chunks) to keep compile time flat.
        q_chunk = max(q_chunk, sq // 2)

    if sq <= q_chunk or sq % q_chunk:
        out = _attend_block(qg, k, v, q_pos, k_pos, scale=scale,
                            causal=causal, window=window, softcap=softcap)
        return out.reshape(b, sq, h, dv).astype(v.dtype)

    # query-chunked: scan over Sq blocks, full K/V per block
    nc = sq // q_chunk
    qg_c = qg.reshape(b, nc, q_chunk, kvh, g, dk).transpose(1, 0, 2, 3, 4, 5)
    qp_c = q_pos.reshape(b, nc, q_chunk).transpose(1, 0, 2)

    def body(_, qc):
        q_blk, qp_blk = qc
        o = _attend_block(q_blk, k, v, qp_blk, k_pos, scale=scale,
                          causal=causal, window=window, softcap=softcap)
        return None, o

    if _UNROLL_CHUNKS:
        outs = jnp.stack([body(None, (qg_c[i], qp_c[i]))[1]
                          for i in range(nc)])
    else:
        _, outs = jax.lax.scan(body, None, (qg_c, qp_c))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, h, dv)
    return out.astype(v.dtype)


# ---------------------------------------------------------------------------
# Standard (GQA) attention layer
# ---------------------------------------------------------------------------

def attn_init(key, cfg, *, cross: bool = False) -> dict:
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": linear_init(ks[0], d, h * hd),
        "wk": linear_init(ks[1], d, kvh * hd),
        "wv": linear_init(ks[2], d, kvh * hd),
        "wo": linear_init(ks[3], h * hd, d),
    }
    if cfg.qk_norm:
        p["q_norm"] = rms_norm_init(hd)
        p["k_norm"] = rms_norm_init(hd)
    return p


def init_kv_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    if cfg.kv_cache_dtype == "int8":
        # the paper's low-precision-storage idea on the decode
        # bottleneck: int8 values + per-(token, head) f32 scales halve
        # the KV bytes the decode step streams from HBM.
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:3] + (1,), jnp.float32),
                "v_scale": jnp.zeros(shape[:3] + (1,), jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_kv_cache(cfg, num_pages: int, page_size: int,
                        dtype=jnp.bfloat16) -> dict:
    """Paged dual of :func:`init_kv_cache`: K/V live in a shared
    ``(num_pages, page_size, ...)`` pool addressed through a page table
    instead of a dense per-slot ``max_len`` slab."""
    shape = (num_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
    if cfg.kv_cache_dtype == "int8":
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:3] + (1,), jnp.float32),
                "v_scale": jnp.zeros(shape[:3] + (1,), jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _q8_heads(t):
    """Symmetric int8 per-(token, head): t (B,S,KVH,D) → (q, scale)."""
    amax = jnp.maximum(jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1,
                               keepdims=True), 1e-8)
    scale = amax / 127.0
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale), -128, 127) \
        .astype(jnp.int8)
    return q, scale


def attn_apply(params, cfg, x, *, positions, kind: str = "full",
               cache: dict | None = None, cache_index=None,
               kv_source: jax.Array | None = None, causal: bool = True,
               return_cache: bool = False, page_table=None,
               context_start=None):
    """Returns (out, new_cache).  Modes:

    * train/prefill: ``cache=None`` → K/V from ``x`` (or ``kv_source``
      for cross-attn); prefill callers build the cache via ``positions``.
    * decode: ``cache`` given, ``cache_index`` = write offset (scalar,
      or a ``(B,)`` vector of per-slot offsets for continuous batching);
      the new token's K/V are scattered in and attention runs against
      the cache with per-slot causal masking (``positions`` carries each
      slot's query position).
    * paged decode: additionally ``page_table`` (B, max_pages) int32 —
      ``cache`` leaves are shared (num_pages, page_size, ...) pools; the
      scatter routes through the table and attention either gathers
      pages back into position order (XLA reference path, bit-identical
      to the unpaged cache) or, under ``attn_impl="flash"``, runs the
      Pallas paged-decode kernel that walks the table directly.
    * context prefill (prefix caching): ``cache`` + ``page_table`` +
      ``context_start`` — ``x`` holds a prompt *suffix* whose queries
      sit at global positions ``context_start + [0, S)``; the cached
      prefix rows are gathered from the pools through the table and
      spliced below the fresh K/V at the same fixed buffer length, so
      the attention math (and, with ``context_start == 0``, every bit
      of it) matches an uncached full-prompt prefill.  The computed
      suffix K/V is returned as ``new_cache`` for the caller to scatter
      into its own pages — the shared prefix pages are never written.
    """
    b, s, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    quant = cfg.quant_mode
    qbackend = cfg.quant_backend

    q = linear_apply(params["wq"], x, mode=quant, backend=qbackend).reshape(b, s, h, hd)
    kv_in = x if kv_source is None else kv_source
    sk_new = kv_in.shape[1]
    k = linear_apply(params["wk"], kv_in, mode=quant, backend=qbackend).reshape(b, sk_new, kvh, hd)
    v = linear_apply(params["wv"], kv_in, mode=quant, backend=qbackend).reshape(b, sk_new, kvh, hd)

    if cfg.qk_norm:
        q = rms_norm(params["q_norm"], q, cfg.norm_eps)
        k = rms_norm(params["k_norm"], k, cfg.norm_eps)

    from repro.distributed.sharding import maybe_shard
    q = maybe_shard(q, "heads", kv_heads=kvh)
    k = maybe_shard(k, "heads", kv_heads=kvh)
    v = maybe_shard(v, "heads", kv_heads=kvh)

    use_rope = kv_source is None  # no RoPE on cross-attention
    if use_rope:
        theta = cfg.rope_theta_local if kind == "local" else cfg.rope_theta
        sin_q, cos_q = rope(positions, hd, theta)
        q = apply_rope(q, sin_q, cos_q).astype(x.dtype)
        k_pos_new = positions[:, -sk_new:] if cache is None else positions
        sin_k, cos_k = rope(k_pos_new, hd, theta)
        k = apply_rope(k, sin_k, cos_k).astype(x.dtype)

    scale = cfg.attn_scale or (1.0 / hd ** 0.5)
    window = cfg.sliding_window if kind == "local" else 0
    is_causal_self = causal and kv_source is None

    new_cache = cache
    paged_kernel = False
    if cache is not None and context_start is not None:
        # prefix-cache suffix prefill: splice the gathered cached prefix
        # below the fresh suffix K/V at the fixed buffer length (see
        # _splice_context — bit-identical to a full prefill on a miss).
        # k_pos is the buffer index: cached row j sits at position j,
        # fresh row i at context_start + i, exactly where the splice put
        # them, so plain causal masking covers both.
        if "k_scale" in cache:
            raise NotImplementedError(
                "prefix caching over the int8 KV cache is unsupported: "
                "cached rows would be dequantized while a solo prefill "
                "attends full-precision rows, breaking the bit-match "
                "contract")
        k_full = _splice_context(gather_pages(cache["k"], page_table), k,
                                 context_start)
        v_full = _splice_context(gather_pages(cache["v"], page_table), v,
                                 context_start)
        k_pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        if return_cache:  # suffix rows only; the caller scatters them
            new_cache = {"k": k.astype(jnp.bfloat16),
                         "v": v.astype(jnp.bfloat16)}
    elif cache is not None and page_table is not None:
        # paged decode: scatter through the page table into the shared
        # pool, then either gather pages back into position order (XLA
        # reference — bit-identical to the unpaged cache) or let the Pallas
        # flash-decode kernel walk the table (fast path, no gather copy)
        quant_kv = "k_scale" in cache
        if quant_kv:
            kq, ks = _q8_heads(k)
            vq, vs = _q8_heads(v)
            writes = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
        else:
            writes = {"k": k, "v": v}
        new_cache = {key: scatter_paged_rows(cache[key], val, page_table,
                                             cache_index)
                     for key, val in writes.items()}
        paged_kernel = (cfg.attn_impl == "flash" and not quant_kv
                        and is_causal_self and s == 1
                        and not cfg.attn_core_bypass)
        if paged_kernel:
            k_full = v_full = None
        elif quant_kv:
            k_full = (gather_pages(new_cache["k"], page_table)
                      .astype(jnp.float32)
                      * gather_pages(new_cache["k_scale"], page_table)) \
                .astype(x.dtype)
            v_full = (gather_pages(new_cache["v"], page_table)
                      .astype(jnp.float32)
                      * gather_pages(new_cache["v_scale"], page_table)) \
                .astype(x.dtype)
        else:
            k_full = gather_pages(new_cache["k"], page_table)
            v_full = gather_pages(new_cache["v"], page_table)
        sk_total = page_table.shape[1] * cache["k"].shape[1]
        k_pos = jnp.broadcast_to(jnp.arange(sk_total)[None, :],
                                 (b, sk_total))
    elif cache is not None:
        # decode: scatter the new K/V at cache_index (scalar or per-slot
        # vector), attend to the cache
        quant_kv = "k_scale" in cache
        if quant_kv:
            kq, ks = _q8_heads(k)
            vq, vs = _q8_heads(v)
            new_cache = {
                "k": scatter_cache_rows(cache["k"], kq, cache_index),
                "v": scatter_cache_rows(cache["v"], vq, cache_index),
                "k_scale": scatter_cache_rows(cache["k_scale"], ks,
                                              cache_index),
                "v_scale": scatter_cache_rows(cache["v_scale"], vs,
                                              cache_index),
            }
            k_full = (new_cache["k"].astype(jnp.float32)
                      * new_cache["k_scale"]).astype(x.dtype)
            v_full = (new_cache["v"].astype(jnp.float32)
                      * new_cache["v_scale"]).astype(x.dtype)
            k_cache = new_cache["k"]
        else:
            k_cache = scatter_cache_rows(cache["k"], k, cache_index)
            v_cache = scatter_cache_rows(cache["v"], v, cache_index)
            new_cache = {"k": k_cache, "v": v_cache}
            k_full, v_full = k_cache, v_cache
        k_pos = jnp.broadcast_to(jnp.arange(k_cache.shape[1])[None, :],
                                 (b, k_cache.shape[1]))
    else:
        k_full, v_full = k, v
        k_pos = positions if kv_source is None else jnp.broadcast_to(
            jnp.arange(sk_new)[None, :], (b, sk_new))
        if return_cache:  # prefill: hand the (post-RoPE) K/V to decode
            if cfg.kv_cache_dtype == "int8":
                kq, ks = _q8_heads(k)
                vq, vs = _q8_heads(v)
                new_cache = {"k": kq, "v": vq,
                             "k_scale": ks, "v_scale": vs}
            else:
                new_cache = {"k": k.astype(jnp.bfloat16),
                             "v": v.astype(jnp.bfloat16)}

    if cfg.attn_core_bypass:
        out = jnp.zeros((b, s, h, hd), x.dtype)
    elif paged_kernel:
        from repro.kernels.ops import paged_flash_decode
        out = paged_flash_decode(q, new_cache["k"], new_cache["v"],
                                 page_table, positions[:, -1], scale=scale,
                                 window=window,
                                 softcap=cfg.attn_logit_softcap)
    elif cfg.attn_impl == "flash" and cache is None and is_causal_self:
        out = _flash_self_attention(q, k, v, scale=scale, window=window,
                                    softcap=cfg.attn_logit_softcap)
    else:
        out = attention_core(q, k_full, v_full, positions, k_pos,
                             scale=scale, causal=is_causal_self,
                             window=window,
                             softcap=cfg.attn_logit_softcap)
    out = linear_apply(params["wo"], out.reshape(b, s, h * hd), mode=quant, backend=qbackend)
    return out, new_cache


def _flash_local(q, k, v, *, scale, window, softcap):
    """Device-local flash call: head-major flatten → kernel → restore.

    Heads are ordered (kv_head, group) on the flat axis so the kernel's
    BlockSpec pulls K/V block ``bh // group`` (no materialized repeat)."""
    from repro.kernels.ops import flash_mha
    b, s, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    dv = v.shape[-1]
    qf = q.reshape(b, s, kvh, g, d).transpose(0, 2, 3, 1, 4) \
        .reshape(b * kvh * g, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, s, dv)
    of = flash_mha(qf, kf, vf, scale, True, window, softcap, g, None)
    return of.reshape(b, kvh, g, s, dv).transpose(0, 3, 1, 2, 4) \
        .reshape(b, s, h, dv)


def _flash_self_attention(q, k, v, *, scale, window=0, softcap=0.0):
    """Flash attention, sharded: under a mesh the call runs inside
    shard_map (batch on DP, heads on TP when the KV count divides — the
    same layout the "heads" constraint pins), so the head-major
    flatten/transpose is device-local.  Done naively under GSPMD, those
    reshapes of doubly-sharded axes trigger full q/k/v relayouts —
    measured 114 TB/device of collectives on deepseek-v3 train."""
    from repro.distributed import sharding as shr

    mesh = shr._AMBIENT_MESH
    if mesh is None or shr.TP_AXIS not in mesh.axis_names:
        return _flash_local(q, k, v, scale=scale, window=window,
                            softcap=softcap)

    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    tp = mesh.shape[shr.TP_AXIS]
    dp = tuple(a for a in mesh.axis_names if a != shr.TP_AXIS)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    b, _, h, _ = q.shape
    kvh = k.shape[2]
    b_ax = dp if b % dp_size == 0 else None
    h_ax = shr.TP_AXIS if kvh % tp == 0 else None
    spec = P(b_ax, None, h_ax, None)

    fn = functools.partial(_flash_local, scale=scale, window=window,
                           softcap=softcap)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


# ---------------------------------------------------------------------------
# Multi-head Latent Attention (deepseek-v3)
# ---------------------------------------------------------------------------

def mla_init(key, cfg) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    r_q, r_kv = cfg.q_lora_rank, cfg.kv_lora_rank
    d_nope, d_rope, d_v = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_a": linear_init(ks[0], d, r_q),
        "q_a_norm": rms_norm_init(r_q),
        "wq_b": linear_init(ks[1], r_q, h * (d_nope + d_rope)),
        "wkv_a": linear_init(ks[2], d, r_kv + d_rope),
        "kv_a_norm": rms_norm_init(r_kv),
        "wkv_b": linear_init(ks[3], r_kv, h * (d_nope + d_v)),
        "wo": linear_init(ks[4], h * d_v, d),
    }


def init_mla_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    """MLA caches the *compressed* latent + shared rope key: per-token
    bytes = kv_lora_rank + qk_rope_dim — the paper-adjacent footprint win."""
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
    }


def init_paged_mla_cache(cfg, num_pages: int, page_size: int,
                         dtype=jnp.bfloat16) -> dict:
    """Paged dual of :func:`init_mla_cache`: compressed latents + shared
    rope key in page pools."""
    return {
        "c_kv": jnp.zeros((num_pages, page_size, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((num_pages, page_size, cfg.qk_rope_dim), dtype),
    }


def mla_apply(params, cfg, x, *, positions, cache=None, cache_index=None,
              return_cache: bool = False, page_table=None,
              context_start=None):
    b, s, d = x.shape
    h = cfg.n_heads
    d_nope, d_rope, d_v = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    quant = cfg.quant_mode
    qbackend = cfg.quant_backend

    # --- queries (low-rank) ------------------------------------------------
    q_a = rms_norm(params["q_a_norm"],
                   linear_apply(params["wq_a"], x, mode=quant, backend=qbackend), cfg.norm_eps)
    q = linear_apply(params["wq_b"], q_a, mode=quant, backend=qbackend) \
        .reshape(b, s, h, d_nope + d_rope)
    q_nope, q_rope = q[..., :d_nope], q[..., d_nope:]
    sin, cos = rope(positions, d_rope, cfg.rope_theta)
    q_rope = apply_rope(q_rope, sin, cos).astype(x.dtype)

    # --- compressed KV -------------------------------------------------------
    kv_a = linear_apply(params["wkv_a"], x, mode=quant, backend=qbackend)
    c_kv = rms_norm(params["kv_a_norm"], kv_a[..., :cfg.kv_lora_rank],
                    cfg.norm_eps)
    k_rope_new = kv_a[..., cfg.kv_lora_rank:].reshape(b, s, 1, d_rope)
    k_pos_new = positions
    sin_k, cos_k = rope(k_pos_new, d_rope, cfg.rope_theta)
    k_rope_new = apply_rope(k_rope_new, sin_k, cos_k).astype(x.dtype) \
        .reshape(b, s, d_rope)

    new_cache = cache
    if cache is not None and context_start is not None:
        # prefix-cache suffix prefill: splice cached latents + rope keys
        # below the fresh rows at the fixed buffer length, then let the
        # shared decompression matmul expand the spliced latents exactly
        # as a full prefill would (cached latents are the bf16 rows a
        # solo prefill computes, so the splice is bit-transparent)
        c_kv_f = _splice_context(gather_pages(cache["c_kv"], page_table),
                                 c_kv, context_start)
        k_rope_f = _splice_context(gather_pages(cache["k_rope"],
                                                page_table),
                                   k_rope_new, context_start)
        sk = s
        k_pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        if return_cache:  # suffix rows only; the caller scatters them
            new_cache = {"c_kv": c_kv.astype(jnp.bfloat16),
                         "k_rope": k_rope_new.astype(jnp.bfloat16)}
    elif cache is not None and page_table is not None:
        # paged decode: scatter the latent row through the page table,
        # gather pages back for the shared decompression matmul (the
        # latent is re-expanded per step anyway, so the XLA gather is
        # the natural reference path for MLA)
        new_cache = {
            "c_kv": scatter_paged_rows(cache["c_kv"], c_kv, page_table,
                                       cache_index),
            "k_rope": scatter_paged_rows(cache["k_rope"], k_rope_new,
                                         page_table, cache_index),
        }
        c_kv_f = gather_pages(new_cache["c_kv"], page_table)
        k_rope_f = gather_pages(new_cache["k_rope"], page_table)
        sk = c_kv_f.shape[1]
        k_pos = jnp.broadcast_to(jnp.arange(sk)[None, :], (b, sk))
    elif cache is not None:
        c_kv_f = scatter_cache_rows(cache["c_kv"], c_kv, cache_index)
        k_rope_f = scatter_cache_rows(cache["k_rope"], k_rope_new,
                                      cache_index)
        new_cache = {"c_kv": c_kv_f, "k_rope": k_rope_f}
        sk = c_kv_f.shape[1]
        k_pos = jnp.broadcast_to(jnp.arange(sk)[None, :], (b, sk))
    else:
        c_kv_f, k_rope_f = c_kv, k_rope_new
        k_pos = positions
        sk = s
        if return_cache:  # prefill: cache the compressed latents
            new_cache = {"c_kv": c_kv.astype(jnp.bfloat16),
                         "k_rope": k_rope_new.astype(jnp.bfloat16)}

    # --- decompress K/V (from latent) ---------------------------------------
    kv = linear_apply(params["wkv_b"], c_kv_f, mode=quant, backend=qbackend) \
        .reshape(b, sk, h, d_nope + d_v)
    k_nope, v = kv[..., :d_nope], kv[..., d_nope:]
    k_rope_b = jnp.broadcast_to(k_rope_f[:, :, None, :], (b, sk, h, d_rope))

    q_full = jnp.concatenate([q_nope.astype(jnp.float32),
                              q_rope.astype(jnp.float32)], axis=-1)
    k_full = jnp.concatenate([k_nope.astype(jnp.float32),
                              k_rope_b.astype(jnp.float32)], axis=-1)

    from repro.distributed.sharding import maybe_shard
    q_full = maybe_shard(q_full, "heads")
    k_full = maybe_shard(k_full, "heads")
    v = maybe_shard(v, "heads")

    scale = 1.0 / (d_nope + d_rope) ** 0.5
    if cfg.attn_core_bypass:
        out = jnp.zeros((b, s, h, d_v), x.dtype)
    elif cfg.attn_impl == "flash" and cache is None:
        out = _flash_self_attention(q_full.astype(x.dtype),
                                    k_full.astype(x.dtype), v, scale=scale)
    else:
        out = attention_core(q_full.astype(x.dtype), k_full.astype(x.dtype),
                             v, positions, k_pos, scale=scale, causal=True)
    out = linear_apply(params["wo"], out.reshape(b, s, h * d_v), mode=quant, backend=qbackend)
    return out, new_cache
