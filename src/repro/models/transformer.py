"""Model assembly: heterogeneous layer stacks, scan-over-blocks, caches.

The layer stack is ``prefix + block×n + suffix`` (configs/base.py); the
repeated blocks run under ``jax.lax.scan`` with parameters stacked on a
leading block axis — compile time stays flat in depth (one HLO body per
distinct block), which is what makes the 61-layer deepseek dry-run
tractable.  Heterogeneous layers *within* a block (gemma3's 5 local + 1
global, jamba's mamba/attn + mlp/moe interleave) are unrolled inside the
scan body.

Three entry points per model, matching the dry-run shapes:
* ``forward``      — full-sequence logits (training);
* ``prefill``      — full-sequence pass that also returns decode caches;
* ``decode_step``  — one token against the caches.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LayerSpec, ModelConfig
from repro.models.attention import (
    attn_apply,
    attn_init,
    init_kv_cache,
    init_mla_cache,
    init_paged_kv_cache,
    init_paged_mla_cache,
    mla_apply,
    mla_init,
)
from repro.models.layers import (
    embed_apply,
    embed_init,
    embed_logits,
    mlp_apply,
    mlp_init,
    rms_norm,
    rms_norm_init,
)
from repro.models.mamba import (
    init_mamba_cache,
    mamba_apply,
    mamba_init,
    mamba_step,
)
from repro.models.moe import moe_apply, moe_init

__all__ = ["model_init", "forward", "prefill", "decode_step", "init_caches",
           "init_paged_caches", "merge_slot_paged_caches",
           "scatter_prefill_paged_caches",
           "copy_paged_cache_page", "extract_cache_pages",
           "insert_cache_pages", "encode", "unrolled_blocks"]

# When True, the block stack is a Python loop instead of lax.scan, so the
# compiled HLO contains every layer body.  Used by the dry-run cost pass:
# XLA cost_analysis excludes while-loop bodies (measured: gemma-7b flops
# identical at 1, 2 and 3 scanned blocks), so scanned programs are costed
# by lowering 1- and 2-block *unrolled* variants and extrapolating.
_UNROLL_BLOCKS = False

import contextlib


@contextlib.contextmanager
def unrolled_blocks():
    global _UNROLL_BLOCKS
    prev, _UNROLL_BLOCKS = _UNROLL_BLOCKS, True
    try:
        yield
    finally:
        _UNROLL_BLOCKS = prev


# ---------------------------------------------------------------------------
# Single layer
# ---------------------------------------------------------------------------

def _layer_init(key, cfg: ModelConfig, spec: LayerSpec, *,
                cross: bool = False) -> dict:
    ks = jax.random.split(key, 4)
    p: dict[str, Any] = {"mixer_norm": rms_norm_init(cfg.d_model)}
    if spec.mixer == "attn":
        if spec.attn_kind == "mla":
            p["attn"] = mla_init(ks[0], cfg)
        else:
            p["attn"] = attn_init(ks[0], cfg)
    elif spec.mixer == "mamba":
        p["mamba"] = mamba_init(ks[0], cfg)
    if cross:
        p["cross_norm"] = rms_norm_init(cfg.d_model)
        p["cross"] = attn_init(ks[2], cfg)
    if spec.ffn != "none":
        p["ffn_norm"] = rms_norm_init(cfg.d_model)
        if spec.ffn == "moe":
            p["moe"] = moe_init(ks[1], cfg)
        else:
            p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff)
    return p


def _layer_apply(params, cfg: ModelConfig, spec: LayerSpec, x, *,
                 positions, cache=None, cache_index=None, enc_out=None,
                 causal=True, mode="train", page_table=None,
                 context_start=None):
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: dict = {}
    cache = cache or {}

    if spec.mixer == "attn":
        h = rms_norm(params["mixer_norm"], x, cfg.norm_eps)
        if spec.attn_kind == "mla":
            out, c = mla_apply(params["attn"], cfg, h, positions=positions,
                               cache=cache.get("attn"),
                               cache_index=cache_index,
                               return_cache=(mode == "prefill"),
                               page_table=page_table,
                               context_start=context_start)
        else:
            out, c = attn_apply(params["attn"], cfg, h, positions=positions,
                                kind=spec.attn_kind,
                                cache=cache.get("attn"),
                                cache_index=cache_index, causal=causal,
                                return_cache=(mode == "prefill"),
                                page_table=page_table,
                                context_start=context_start)
        if c is not None:
            new_cache["attn"] = c
        x = x + out
    elif spec.mixer == "mamba":
        h = rms_norm(params["mixer_norm"], x, cfg.norm_eps)
        if mode == "decode":
            out, c = mamba_step(params["mamba"], cfg, h, cache["mamba"])
            new_cache["mamba"] = c
        else:
            out, c = mamba_apply(params["mamba"], cfg, h,
                                 return_cache=(mode == "prefill"))
            if c is not None:
                new_cache["mamba"] = c
        x = x + out

    if "cross" in params and enc_out is not None:
        h = rms_norm(params["cross_norm"], x, cfg.norm_eps)
        out, _ = attn_apply(params["cross"], cfg, h, positions=positions,
                            kv_source=enc_out, causal=False)
        x = x + out

    if spec.ffn != "none":
        h = rms_norm(params["ffn_norm"], x, cfg.norm_eps)
        if spec.ffn == "moe":
            out, aux = moe_apply(params["moe"], cfg, h)
        else:
            out = mlp_apply(params["mlp"], h, act=cfg.act,
                            quant_mode=cfg.quant_mode,
                            quant_backend=cfg.quant_backend)
        x = x + out
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Stack = prefix + scan(blocks) + suffix
# ---------------------------------------------------------------------------

def _stack_init(key, cfg: ModelConfig, *, cross: bool = False) -> dict:
    n_blk = cfg.n_blocks
    keys = jax.random.split(key, 3)

    prefix = [
        _layer_init(k, cfg, spec, cross=cross)
        for k, spec in zip(jax.random.split(keys[0],
                                            max(1, len(cfg.prefix_pattern))),
                           cfg.prefix_pattern)
    ]
    suffix = [
        _layer_init(k, cfg, spec, cross=cross)
        for k, spec in zip(jax.random.split(keys[2],
                                            max(1, len(cfg.suffix_pattern))),
                           cfg.suffix_pattern)
    ]

    # blocks: per pattern position, vmapped init over the block axis
    blk_keys = jax.random.split(keys[1], n_blk * len(cfg.block_pattern)) \
        .reshape(n_blk, len(cfg.block_pattern), 2)
    blocks = {}
    for j, spec in enumerate(cfg.block_pattern):
        init_j = functools.partial(_layer_init, cfg=cfg, spec=spec,
                                   cross=cross)
        blocks[str(j)] = jax.vmap(lambda k: init_j(k))(blk_keys[:, j])
    return {"prefix": prefix, "blocks": blocks, "suffix": suffix}


def _stack_apply(params, cfg: ModelConfig, x, *, positions, caches=None,
                 cache_index=None, enc_out=None, causal=True, mode="train",
                 page_table=None, context_start=None):
    """Returns (x, new_caches, total_aux)."""
    total_aux = jnp.zeros((), jnp.float32)
    want_cache = mode in ("prefill", "decode")
    new_caches: dict = {"prefix": [], "blocks": None, "suffix": []}
    caches = caches or {"prefix": [None] * len(cfg.prefix_pattern),
                        "blocks": None,
                        "suffix": [None] * len(cfg.suffix_pattern)}

    from repro.distributed.sharding import maybe_shard

    def run_layer(p, spec, x, cache, table=page_table):
        x = maybe_shard(x, "activation")   # pin (dp, ∅, ∅) between layers
        return _layer_apply(p, cfg, spec, x, positions=positions,
                            cache=cache, cache_index=cache_index,
                            enc_out=enc_out, causal=causal, mode=mode,
                            page_table=table,
                            context_start=context_start)

    # prefix/suffix layers run OUTSIDE the scanned-and-checkpointed
    # blocks; without their own remat, all their attention internals
    # (f32 probability chunks: ~34 GiB per chunk on deepseek's MLA
    # prefix) are saved for backward.
    fixed_layer = run_layer
    if cfg.remat and mode == "train":
        fixed_layer = jax.checkpoint(run_layer, prevent_cse=False,
                                     static_argnums=(1,))

    for p, spec, c in zip(params["prefix"], cfg.prefix_pattern,
                          caches["prefix"]):
        x, nc, aux = fixed_layer(p, spec, x, c)
        total_aux += aux
        new_caches["prefix"].append(nc)

    # --- scanned blocks -----------------------------------------------------
    # A given block cache (decode, wave, draft, verify, context prefill)
    # rides the scan as a carry that each layer updates in place.  As
    # scan xs/ys it would be sliced out per layer and restacked into a
    # second pool-sized buffer, copying the whole stacked pool every
    # step.  Paged pools are viewed as one pool of n_blocks × num_pages
    # pages, and a layer addresses its own through the page table
    # shifted by layer × num_pages, so its rows are written and gathered
    # straight in the stack.  Other leaves (unpaged caches, mamba state)
    # read and write their layer's entry by dynamic index.  A fresh
    # prefill (no cache given) emits its new per-layer caches as ys.
    if cfg.n_blocks:
        blk_caches = caches["blocks"]
        paged = page_table is not None and blk_caches is not None
        n_pages = _pool_pages(blk_caches) if paged else 0
        if paged:
            blk_caches = _map_cache_leaves(
                lambda a: a.reshape(-1, *a.shape[2:]), None, blk_caches,
                paged)

        def block_body(carry, xs):
            h, aux_acc, cache = carry
            blk_params, layer = xs
            table = page_table + layer * n_pages if paged else page_table
            emitted = {}
            for j, spec in enumerate(cfg.block_pattern):
                key = str(j)
                c = None if cache is None else _map_cache_leaves(
                    None, lambda a: jax.lax.dynamic_index_in_dim(
                        a, layer, keepdims=False), cache[key], paged)
                h, nc, aux = run_layer(blk_params[key], spec, h, c, table)
                aux_acc += aux
                if mode == "decode":
                    cache = {**cache, key: _map_cache_leaves(
                        lambda a, n: n,
                        lambda a, n: jax.lax.dynamic_update_index_in_dim(
                            a, n, layer, 0), cache[key], paged, nc)}
                else:
                    emitted[key] = nc
            return (h, aux_acc, cache), (emitted if mode == "prefill" else 0)

        body = block_body
        if cfg.remat and mode == "train":
            body = jax.checkpoint(block_body, prevent_cse=False)

        xs = (params["blocks"], jnp.arange(cfg.n_blocks, dtype=jnp.int32))
        carry = (x, total_aux, blk_caches)
        if _UNROLL_BLOCKS:
            emitted = []
            for i in range(cfg.n_blocks):
                carry, y = body(carry,
                                jax.tree_util.tree_map(lambda a: a[i], xs))
                emitted.append(y)
            ys = jax.tree_util.tree_map(lambda *ys: jnp.stack(ys), *emitted)
        else:
            carry, ys = jax.lax.scan(body, carry, xs)
        x, total_aux, blk_caches = carry
        if mode == "decode":
            new_caches["blocks"] = _map_cache_leaves(
                lambda a: a.reshape(cfg.n_blocks, n_pages, *a.shape[1:]),
                None, blk_caches, paged)
        elif want_cache:
            new_caches["blocks"] = ys

    for p, spec, c in zip(params["suffix"], cfg.suffix_pattern,
                          caches["suffix"]):
        x, nc, aux = fixed_layer(p, spec, x, c)
        total_aux += aux
        new_caches["suffix"].append(nc)

    return x, (new_caches if want_cache else None), total_aux


# ---------------------------------------------------------------------------
# Whole models
# ---------------------------------------------------------------------------

def model_init(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 5)
    params = {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model),
        "stack": _stack_init(ks[1], cfg, cross=cfg.is_encdec),
        "final_norm": rms_norm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        from repro.core.linear import linear_init
        params["lm_head"] = linear_init(ks[2], cfg.d_model, cfg.vocab_size)
    if cfg.is_encdec:
        enc_cfg = cfg.replace(n_layers=cfg.n_enc_layers,
                              block_pattern=(LayerSpec(),),
                              prefix_pattern=(), suffix_pattern=())
        params["encoder"] = {
            "stack": _stack_init(ks[3], enc_cfg),
            "norm": rms_norm_init(cfg.d_model),
        }
    return params


def _logits(params, cfg, x):
    from repro.distributed.sharding import maybe_shard
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        out = embed_logits(params["embed"], x)
    else:
        from repro.core.linear import linear_apply
        out = linear_apply(params["lm_head"], x, mode="dense") \
            .astype(jnp.float32)
    # vocab-sharded logits: keeps the softmax/CE temporaries distributed
    return maybe_shard(out, "logits")


def encode(params, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    """Encoder pass over stub-frontend frame embeddings (B, S_enc, D)."""
    enc_cfg = cfg.replace(n_layers=cfg.n_enc_layers,
                          block_pattern=(LayerSpec(),),
                          prefix_pattern=(), suffix_pattern=())
    b, s, _ = frames.shape
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    x, _, _ = _stack_apply(params["encoder"]["stack"], enc_cfg, frames,
                           positions=pos, causal=False, mode="train")
    return rms_norm(params["encoder"]["norm"], x, cfg.norm_eps)


def _embed_inputs(params, cfg, tokens, extra_embeds):
    x = embed_apply(params["embed"], tokens,
                    scale_by_sqrt_dim=cfg.emb_scale_by_sqrt_dim)
    if extra_embeds is not None:   # VLM stub frontend: prepend patch embeds
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    return x


def forward(params, cfg: ModelConfig, tokens, *, extra_embeds=None,
            frames=None):
    """Training logits.  tokens: (B, S) int32.

    * VLM: ``extra_embeds`` (B, P, D) prepended (logits cover P+S).
    * enc-dec: ``frames`` (B, S_enc, D) run through the encoder first.
    """
    enc_out = encode(params, cfg, frames) if frames is not None else None
    x = _embed_inputs(params, cfg, tokens, extra_embeds)
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    x, _, aux = _stack_apply(params["stack"], cfg, x, positions=pos,
                             enc_out=enc_out, mode="train")
    return _logits(params, cfg, x), aux


def _cache_tree(cfg: ModelConfig, layer_cache) -> dict:
    """The cache tree of the whole stack from ``layer_cache(spec)``, one
    layer's zero caches.  Each scanned-block leaf is one allocation of
    zeros in the stacked shape, so building the pool never holds a leaf
    twice."""
    def stacked(spec: LayerSpec):
        one = jax.eval_shape(lambda: layer_cache(spec))
        return jax.tree_util.tree_map(
            lambda a: jnp.zeros((cfg.n_blocks, *a.shape), a.dtype), one)

    return {
        "prefix": [layer_cache(s) for s in cfg.prefix_pattern],
        "blocks": {str(j): stacked(s)
                   for j, s in enumerate(cfg.block_pattern)}
        if cfg.n_blocks else None,
        "suffix": [layer_cache(s) for s in cfg.suffix_pattern],
    }


def init_caches(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Unpaged decode caches: one ``(batch, max_len, ...)`` row per
    sequence — the layout a prefill emits and a plain ``decode_step``
    (no page table) reads.  A serving engine stores KV in page pools
    instead (:func:`init_paged_caches`).  Mamba recurrent state has no
    sequence axis and is per-sequence in both."""
    def layer_cache(spec: LayerSpec):
        if spec.mixer == "attn":
            if spec.attn_kind == "mla":
                return {"attn": init_mla_cache(cfg, batch, max_len)}
            return {"attn": init_kv_cache(cfg, batch, max_len)}
        if spec.mixer == "mamba":
            return {"mamba": init_mamba_cache(cfg, batch)}
        return {}

    return _cache_tree(cfg, layer_cache)


def init_paged_caches(cfg: ModelConfig, batch: int, num_pages: int,
                      page_size: int) -> dict:
    """Paged dual of :func:`init_caches`: every attention/MLA leaf is a
    shared ``(num_pages, page_size, ...)`` pool (one pool per layer; the
    scanned blocks stack pools on their leading block axis exactly like
    the unpaged caches).  Capacity scales with *live* tokens:
    ``num_pages`` is a workload knob, not ``batch × max_len /
    page_size``."""
    def layer_cache(spec: LayerSpec):
        if spec.mixer == "attn":
            if spec.attn_kind == "mla":
                return {"attn": init_paged_mla_cache(cfg, num_pages,
                                                     page_size)}
            return {"attn": init_paged_kv_cache(cfg, num_pages, page_size)}
        if spec.mixer == "mamba":
            return {"mamba": init_mamba_cache(cfg, batch)}
        return {}

    return _cache_tree(cfg, layer_cache)


def prefill(params, cfg: ModelConfig, tokens, *, extra_embeds=None,
            frames=None, max_len: int | None = None, logits_index=None,
            ctx_caches=None, ctx_table=None, ctx_start=None):
    """Run the prompt, return (next-token logits, caches, enc_out).

    ``logits_index`` selects which position's logits to return (default:
    the last).  It may be a traced scalar or ``(B,)`` vector, which is
    what lets a serving engine prefill prompts *padded* to a fixed slot
    budget — the real prompt length is data, not shape, so one
    compilation serves every request.  (Cache rows written by the pad
    tokens are harmless: decode overwrites row ``p`` before any query
    can attend to it.)

    Context prefill (prefix caching): with ``ctx_caches`` (paged cache
    pools), ``ctx_table`` (the slot's (1, max_pages) page-table row) and
    ``ctx_start`` (traced scalar), ``tokens`` holds only the *uncached
    suffix* of a prompt whose first ``ctx_start`` rows already sit in
    shared pool pages.  Queries run at global positions ``ctx_start +
    [0, S)`` and every attention layer splices the gathered cached rows
    below the fresh ones (see ``attn_apply``); the returned caches hold
    the suffix rows only.  ``ctx_start`` is data, not shape — one
    compilation serves hit and miss alike, and a miss (``ctx_start ==
    0``) is bit-identical to a plain full-prompt prefill.
    """
    enc_out = encode(params, cfg, frames) if frames is not None else None
    x = _embed_inputs(params, cfg, tokens, extra_embeds)
    b, s, _ = x.shape
    pos = jnp.arange(s)[None, :]
    if ctx_start is not None:
        pos = pos + jnp.asarray(ctx_start, jnp.int32)
    pos = jnp.broadcast_to(pos, (b, s))
    x, caches, _ = _stack_apply(params["stack"], cfg, x, positions=pos,
                                enc_out=enc_out, mode="prefill",
                                caches=ctx_caches, page_table=ctx_table,
                                context_start=ctx_start)
    if logits_index is None:
        x_last = x[:, -1:]
    else:
        idx = jnp.broadcast_to(jnp.asarray(logits_index, jnp.int32)
                               .reshape(-1), (b,))
        x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    logits = _logits(params, cfg, x_last)
    if max_len is not None and max_len > s:
        caches = _grow_caches(cfg, caches, s, max_len)
    return logits, caches, enc_out


# Cache leaves with a sequence axis (always axis 1 after any block-stack
# leading axis is accounted for) — padded out to the decode budget.
_SEQ_CACHE_KEYS = {"k", "v", "c_kv", "k_rope", "k_scale", "v_scale"}


def _is_block_leaf(path) -> bool:
    """True when a cache-tree path points inside the scanned-block
    subtree, whose leaves carry a leading ``n_blocks`` axis.  The cache
    tree is ``{"prefix": [...], "blocks": {...}, "suffix": [...]}``, so
    the top-level dict key decides the layout — structurally, never by
    comparing coincidental sizes (``batch == prompt_len`` makes axis 1
    of a block-stacked leaf look like a sequence axis)."""
    head = path[0]
    return isinstance(head, jax.tree_util.DictKey) and head.key == "blocks"


def _leaf_key(path):
    return path[-1].key if hasattr(path[-1], "key") else None


def _map_cache_leaves(on_pool, on_other, tree, paged, *rest):
    """Map a layer-stacked cache subtree leaf by leaf: a page pool (a
    sequence leaf, when ``paged``) takes ``on_pool``, any other leaf
    ``on_other``, and ``None`` keeps the leaf as it is.  ``rest`` are
    trees of the same structure whose leaves are passed alongside."""
    def f(path, a, *r):
        pool = paged and _leaf_key(path) in _SEQ_CACHE_KEYS
        fn = on_pool if pool else on_other
        return a if fn is None else fn(a, *r)

    return jax.tree_util.tree_map_with_path(f, tree, *rest)


def _pool_pages(tree) -> int:
    """Pages per layer in a stacked paged cache subtree (every pool is
    built with the same ``num_pages``); 0 when it holds no pool."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return next((a.shape[1] for path, a in leaves
                 if _leaf_key(path) in _SEQ_CACHE_KEYS), 0)


def _grow_caches(cfg, caches, cur_len, max_len):
    """Pad prefill KV caches out to the decode budget (key-aware: SSM
    conv/state caches have no sequence axis and are left alone)."""
    def pad_leaf(path, a):
        key = _leaf_key(path)
        if key not in _SEQ_CACHE_KEYS:
            return a
        # seq axis: 1 for per-layer caches, 2 under the block-stack axis
        axis = 2 if _is_block_leaf(path) else 1
        pad_width = [(0, 0)] * a.ndim
        pad_width[axis] = (0, max_len - cur_len)
        return jnp.pad(a, pad_width)

    return jax.tree_util.tree_map_with_path(pad_leaf, caches)


def merge_slot_paged_caches(big, one, slot, pages):
    """Copy a prefilled single-sequence cache into the shared page
    pools.  ``one``'s sequence leaves (length ``S``, a multiple of the
    pool page size) are reshaped into ``S / page_size`` whole pages and
    scattered to the page ids in ``pages`` (a ``(max_pages,)`` traced
    vector — entries past the request's live pages point at the trash
    page, so pad-token pages land somewhere harmless and one
    compilation serves every prompt length).  Non-sequence leaves
    (mamba conv/ssm state) scatter at batch slot ``slot``; the batch
    axis is found structurally: axis 0 for prefix/suffix leaves, axis 1
    under the block-stack leading axis."""
    pages = jnp.asarray(pages, jnp.int32)

    def put(path, b_leaf, s_leaf):
        key = _leaf_key(path)
        blk = _is_block_leaf(path)
        if key not in _SEQ_CACHE_KEYS:
            b_ax = 1 if blk else 0
            start = [0] * b_leaf.ndim
            start[b_ax] = slot
            return jax.lax.dynamic_update_slice(
                b_leaf, s_leaf.astype(b_leaf.dtype), tuple(start))
        ps = b_leaf.shape[2] if blk else b_leaf.shape[1]
        s = s_leaf.shape[2] if blk else s_leaf.shape[1]
        if s % ps:
            raise ValueError(f"prefill cache length {s} is not a whole "
                             f"number of pages (page_size {ps})")
        n_p = s // ps
        if blk:
            nb = b_leaf.shape[0]
            rows = s_leaf.reshape(nb, n_p, ps, *s_leaf.shape[3:])
            return b_leaf.at[:, pages[:n_p]].set(rows.astype(b_leaf.dtype))
        rows = s_leaf.reshape(n_p, ps, *s_leaf.shape[2:])
        return b_leaf.at[pages[:n_p]].set(rows.astype(b_leaf.dtype))

    return jax.tree_util.tree_map_with_path(put, big, one)


def scatter_prefill_paged_caches(big, one, slot, row, start):
    """Row-granular dual of :func:`merge_slot_paged_caches` for prefix
    caching: write a context-prefilled suffix cache (rows for global
    positions ``start + [0, S)``) through one slot's page-table ``row``
    into the shared pools.  Unlike the whole-page merge, writes are per
    row, so the shared prefix pages *below* ``start`` — and the cached
    rows a copy-on-write tail page carries below ``start`` — are never
    touched.  Non-sequence leaves (none on the archs prefix caching
    admits, but kept for shape parity) scatter at batch slot ``slot``
    exactly as in the merge."""
    from repro.models.attention import scatter_prefill_rows
    row = jnp.asarray(row, jnp.int32)

    def put(path, b_leaf, s_leaf):
        key = _leaf_key(path)
        blk = _is_block_leaf(path)
        if key not in _SEQ_CACHE_KEYS:
            b_ax = 1 if blk else 0
            start_idx = [0] * b_leaf.ndim
            start_idx[b_ax] = slot
            return jax.lax.dynamic_update_slice(
                b_leaf, s_leaf.astype(b_leaf.dtype), tuple(start_idx))
        if blk:
            return jax.vmap(
                lambda pool, new: scatter_prefill_rows(pool, new, row,
                                                       start)
            )(b_leaf, s_leaf)
        return scatter_prefill_rows(b_leaf, s_leaf, row, start)

    return jax.tree_util.tree_map_with_path(put, big, one)


def copy_paged_cache_page(caches, src, dst):
    """Copy pool page ``src`` onto ``dst`` in every sequence-cache pool
    (the copy-on-write primitive: duplicate a shared tail page into a
    slot's private page before the slot's first write can land on
    shared storage).  ``src``/``dst`` are traced scalars, so the copy
    lives inside the compiled prefill program; the no-COW default is
    ``src == dst == 0`` — rewriting the trash page with itself, a
    bit-exact no-op — which keeps the program count at one."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)

    def cp(path, leaf):
        key = _leaf_key(path)
        if key not in _SEQ_CACHE_KEYS:
            return leaf
        if _is_block_leaf(path):
            return leaf.at[:, dst].set(leaf[:, src])
        return leaf.at[dst].set(leaf[src])

    return jax.tree_util.tree_map_with_path(cp, caches)


def extract_cache_pages(caches, pages, pad_to: int | None = None) -> list[dict]:
    """Copy pool pages ``pages`` out of every sequence-cache pool into
    host memory: returns one payload per page, a ``{flat_leaf_index:
    np.ndarray}`` dict covering exactly the sequence leaves (page axis
    removed — a payload entry is ``(page_size, ...)``, or ``(n_blocks,
    page_size, ...)`` under the block stack).  This is the preemption
    swap-out / prefix-demotion primitive: together with
    :func:`insert_cache_pages` it round-trips a page's rows through a
    host cold tier bit-exactly (device→host→device is a copy, never a
    recompute).  Keying payloads by flattened leaf index keeps them
    structure-free; the restoring engine re-derives block-ness from its
    own cache tree, which is by construction the same tree.

    ``pad_to`` fixes the gather width by padding the page-id vector
    with the trash page (id 0): these are eager dispatches, and XLA
    compiles one kernel per shape — a serving engine pads every call
    to one width so the whole swap tier costs exactly one compilation
    (pre-paid at reset), not one per distinct page count mid-run.  The
    padded rows are dropped before returning."""
    pages = list(pages)
    padded = pages + [0] * (max(0, (pad_to or 0) - len(pages)))
    idx = jnp.asarray(np.asarray(padded, np.int32))
    leaves = jax.tree_util.tree_flatten_with_path(caches)[0]
    cols: dict[int, tuple[np.ndarray, bool]] = {}
    for i, (path, leaf) in enumerate(leaves):
        key = _leaf_key(path)
        if key not in _SEQ_CACHE_KEYS:
            continue
        blk = _is_block_leaf(path)
        gathered = leaf[:, idx] if blk else leaf[idx]
        cols[i] = (np.asarray(jax.device_get(gathered)), blk)
    return [{i: (a[:, j] if blk else a[j]) for i, (a, blk) in cols.items()}
            for j in range(len(pages))]


def insert_cache_pages(caches, pages, payloads, pad_to: int | None = None):
    """Write host page payloads (from :func:`extract_cache_pages`) back
    into pool pages ``pages`` of every sequence-cache leaf — the swap-in
    / prefix-promotion dual.  Runs eagerly outside the compiled stages:
    swaps are rare scheduler events, and page ids are host integers
    here, not traced values.

    ``pad_to`` pins the scatter width like the extract side: padded
    entries write zero rows onto the trash page (id 0), which no query
    ever attends — the same idempotent-write invariant that lets idle
    slots decode into it."""
    pages = list(pages)
    if len(pages) != len(payloads):
        raise ValueError(f"{len(pages)} pages but {len(payloads)} "
                         f"payloads")
    pad = max(0, (pad_to or 0) - len(pages))
    idx = jnp.asarray(np.asarray(pages + [0] * pad, np.int32))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(caches)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        key = _leaf_key(path)
        if key in _SEQ_CACHE_KEYS and i in payloads[0]:
            blk = _is_block_leaf(path)
            rows = np.stack([p[i] for p in payloads],
                            axis=1 if blk else 0)
            if pad:
                shp = list(rows.shape)
                shp[1 if blk else 0] = pad
                rows = np.concatenate(
                    [rows, np.zeros(shp, rows.dtype)], axis=1 if blk else 0)
            if blk:
                leaf = leaf.at[:, idx].set(rows.astype(leaf.dtype))
            else:
                leaf = leaf.at[idx].set(rows.astype(leaf.dtype))
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def decode_step(params, cfg: ModelConfig, token, caches, index, *,
                enc_out=None, page_table=None):
    """One decode step.  token: (B, S) int32 (classically S == 1).

    ``index`` is the cache write position of ``token[:, 0]`` — a scalar
    (every sequence at the same position, the lockstep special case) or
    a ``(B,)`` int32 vector of *per-slot* positions (continuous
    batching: each batch slot is an independent sequence).  Positions
    are data, not shape: both forms compile once and serve every
    position assignment.  Attention caches scatter per slot; mamba
    layers carry per-sequence recurrent state and never index by
    position, so their semantics are unchanged.

    With ``S > 1`` the step evaluates ``S`` consecutive tokens per slot
    in one forward — row ``j`` writes cache position ``index + j`` and
    attends everything at or below it (per-position causal masking) —
    which is the speculative-decode *verify* shape: all ``k`` draft
    positions plus the bonus position get their next-token logits in a
    single batched dense dispatch.  S > 1 requires attention-only
    stacks (a mamba mixer would need ``S`` recurrent sub-steps; the
    serve engine rejects spec decode on mamba models up front).

    With ``page_table`` (a ``(B, max_pages)`` int32 table), ``caches``
    are shared page pools: the scatter routes through the table
    (``page = table[slot, pos // page_size]``) and attention gathers
    pages back into position order — page ids are data, not shape, so
    the same compilation serves every allocation pattern.
    """
    x = embed_apply(params["embed"], token,
                    scale_by_sqrt_dim=cfg.emb_scale_by_sqrt_dim)
    b, s = x.shape[0], token.shape[1]
    index = jnp.asarray(index, jnp.int32)
    pos = (jnp.broadcast_to(index.reshape(-1, 1), (b, 1))
           + jnp.arange(s, dtype=jnp.int32)[None, :])
    x, new_caches, _ = _stack_apply(params["stack"], cfg, x, positions=pos,
                                    caches=caches, cache_index=index,
                                    enc_out=enc_out, mode="decode",
                                    page_table=page_table)
    return _logits(params, cfg, x), new_caches
