"""Partition rules: DP/FSDP × TP × EP (+ SP for caches) for every arch.

One rule table covers the whole zoo because parameters are named
consistently (models/*).  Conventions on the production mesh
(("pod",) "data", "model"):

* **FSDP axes** = ("pod", "data") when multi-pod else ("data",) — weight
  shards gather on use (GSPMD), gradients reduce-scatter back.
* **TP axis** = "model" — megatron-style column/row parallel pairs; MoE
  experts (EP) and the vocab dimension also live on "model".
* **Sequence/cache sharding**: decode caches put batch on the DP axes
  and KV-heads on "model" when divisible, else the sequence axis goes to
  "model"; the batch-1 ``long_500k`` cells shard sequence over the DP
  axes instead (there is no batch to split).

Everything returns ``PartitionSpec`` trees matching the exact pytrees
the models produce, including the scan-stacked block dimension.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig

__all__ = ["param_specs", "opt_state_specs", "batch_specs", "cache_specs",
           "page_table_spec", "fsdp_axes", "TP_AXIS", "maybe_shard"]

TP_AXIS = "model"


# Ambient mesh for in-model sharding constraints.  Set explicitly by the
# launch layer (dryrun/trainer) — deterministic, no reliance on jax's
# evolving context-mesh APIs; unit tests leave it unset and every
# constraint is a no-op.
import contextlib

_AMBIENT_MESH = None


@contextlib.contextmanager
def ambient_mesh(mesh):
    global _AMBIENT_MESH
    prev, _AMBIENT_MESH = _AMBIENT_MESH, mesh
    try:
        yield mesh
    finally:
        _AMBIENT_MESH = prev


def maybe_shard(x, kind: str, kv_heads: int | None = None):
    """Apply a sharding constraint when an ambient mesh is installed;
    no-op otherwise (unit tests, single-device runs).

    kinds: "activation" (B,S,D)→(dp,∅,∅); "logits" (B,S,V)→(dp,∅,tp) —
    the vocab-sharded softmax constraint that keeps the CE loss from
    materializing replicated (B,S,V) temporaries.
    """
    mesh = _AMBIENT_MESH
    if mesh is None or TP_AXIS not in mesh.axis_names:
        return x
    dp = tuple(a for a in mesh.axis_names if a != TP_AXIS)
    if kind == "activation":
        spec = P(dp, *([None] * (x.ndim - 1)))
    elif kind == "logits":
        spec = P(dp, *([None] * (x.ndim - 2)), TP_AXIS)
    elif kind == "heads":
        # (B, S, H, D): heads on TP when the *KV* head count divides the
        # axis (q and k must agree or the grouped einsum reshards),
        # otherwise explicitly replicated.  Without this, GSPMD resolves
        # indivisible head counts by sharding the head_dim *contraction*
        # of QK^T and all-reducing the probs — measured 1.9 TB/device on
        # llama4 prefill_32k (EXPERIMENTS.md §Perf).
        decider = kv_heads if kv_heads is not None else x.shape[2]
        head_ax = TP_AXIS if decider % mesh.shape[TP_AXIS] == 0 else None
        spec = P(dp, None, head_ax, None)
    else:
        raise KeyError(kind)
    spec = _fit_spec(spec, x.shape, mesh)
    from jax.sharding import NamedSharding
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def fsdp_axes(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------

# (parent-key, leaf-key) → spec builder.  COL = (fsdp, tp); ROW = (tp, fsdp).
_COL_PARENTS = {"wq", "wk", "wv", "gate", "up", "wq_b", "wkv_b", "in_proj",
                "lm_head"}
_ROW_PARENTS = {"wo", "down", "out_proj"}
_PLAIN_PARENTS = {"wq_a", "wkv_a"}       # low-rank downs: FSDP only


def _param_rule(path: tuple[str, ...], leaf, fsdp: tuple) -> P:
    keys = [k for k in path]
    leafk = keys[-1]
    parent = keys[-2] if len(keys) >= 2 else ""
    stacked = "blocks" in keys          # scan-stacked: leading block axis

    def wrap(*spec):
        return P(*( (None,) + spec if stacked else spec ))

    if leafk == "emb":
        # (V, D): vocab on TP, D replicated.  Sharding D on the FSDP/data
        # axes looks attractive but makes the embedding-grad contraction
        # doubly-data-sharded (batch on data × D on data) — XLA resolves
        # that by all-gathering the *global batch* of f32 logits, which
        # is catastrophic (measured: 64 GiB/device temps on gemma3).
        return wrap(TP_AXIS, None)
    if leafk == "w":
        if parent in _COL_PARENTS:
            return wrap(fsdp, TP_AXIS)
        if parent in _ROW_PARENTS:
            return wrap(TP_AXIS, fsdp)
        if parent in _PLAIN_PARENTS:
            return wrap(fsdp, None)
        return wrap(fsdp, None)          # unknown linear: FSDP the in-dim
    if leafk in ("w_gate", "w_up"):
        # (E, D, F): EP on the expert axis × FSDP on the inner dim for
        # *storage* (97% of deepseek's params are experts — EP-only
        # storage is 81 GB/device).  The shard_map MoE path requests
        # P(TP, ∅, ∅); pjit inserts the per-layer FSDP gather at the
        # shard_map boundary (one layer's experts live at a time).
        return wrap(TP_AXIS, fsdp, None)
    if leafk == "w_down":                # (E, F, D)
        return wrap(TP_AXIS, None, fsdp)
    if leafk == "router":                # small, replicate
        return wrap(None, None)
    if leafk == "conv_w":                # (W, C): channels on TP
        return wrap(None, TP_AXIS)
    if leafk == "conv_b":
        return wrap(TP_AXIS)
    # norms, A_log, D, dt_bias, scalars: replicated
    return wrap(*([None] * (leaf.ndim - (1 if stacked else 0))))


def _tree_map_with_str_path(fn, tree):
    def keyify(entry) -> str:
        if hasattr(entry, "key"):
            return str(entry.key)
        if hasattr(entry, "idx"):
            return str(entry.idx)
        return str(entry)

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: fn(tuple(keyify(p) for p in path), leaf), tree)


def _fit_spec(spec: P, shape, mesh) -> P:
    """Drop sharded axes that do not divide the dimension they shard
    (odd vocab sizes, batch=1 long-context cells, tiny head counts).
    GSPMD requires exact divisibility; replication is always valid."""
    out = []
    for i, dim in enumerate(shape):
        axes = spec[i] if i < len(spec) else None
        if axes is None:
            out.append(None)
            continue
        ax_tuple = axes if isinstance(axes, tuple) else (axes,)
        size = 1
        for a in ax_tuple:
            size *= mesh.shape[a]
        out.append(axes if dim % size == 0 else None)
    return P(*out)


def param_specs(params: Any, mesh) -> Any:
    fsdp = fsdp_axes(mesh)
    return _tree_map_with_str_path(
        lambda path, leaf: _fit_spec(_param_rule(path, leaf, fsdp),
                                     leaf.shape, mesh), params)


def opt_state_specs(opt_state: Any, params_spec: Any) -> Any:
    """Moments mirror parameter sharding (ZeRO falls out of the FSDP axis
    already in the param specs); int8-moment scales are replicated."""
    def moment_spec(pspec, leaf_or_subtree):
        if isinstance(leaf_or_subtree, dict) and "q" in leaf_or_subtree:
            return {"q": pspec, "scale": P()}
        return pspec

    mu = jax.tree_util.tree_map(
        moment_spec, params_spec, opt_state["mu"],
        is_leaf=lambda x: isinstance(x, dict) and "q" in x)
    nu = jax.tree_util.tree_map(
        moment_spec, params_spec, opt_state["nu"],
        is_leaf=lambda x: isinstance(x, dict) and "q" in x)
    return {"step": P(), "mu": mu, "nu": nu}


# ---------------------------------------------------------------------------
# Batch / cache rules
# ---------------------------------------------------------------------------

def batch_specs(cfg: ModelConfig, mesh) -> dict:
    dp = fsdp_axes(mesh)                 # batch over pod+data
    spec = {"tokens": P(dp, None), "labels": P(dp, None)}
    if cfg.family == "vlm":
        spec["patch_embeds"] = P(dp, None, None)
    if cfg.is_encdec:
        spec["frames"] = P(dp, None, None)
    return spec


def _kv_spec(cfg: ModelConfig, mesh, batch: int, stacked: bool,
             seq_to_dp: bool) -> P:
    """(B, S, KVH, D) spec."""
    tp_size = mesh.shape[TP_AXIS]
    dp = fsdp_axes(mesh)
    if seq_to_dp:                        # batch=1 long-context cells
        head_ax = TP_AXIS if cfg.n_kv_heads % tp_size == 0 else None
        spec = (None, dp, head_ax, None)
    elif cfg.n_kv_heads % tp_size == 0:
        spec = (dp, None, TP_AXIS, None)
    else:                                # few KV heads: sequence on TP
        spec = (dp, TP_AXIS, None, None)
    return P(*((None,) + spec if stacked else spec))


def _mla_spec(mesh, stacked: bool, seq_to_dp: bool) -> P:
    dp = fsdp_axes(mesh)
    spec = (None, dp, None) if seq_to_dp else (dp, None, None)
    return P(*((None,) + spec if stacked else spec))


def _paged_kv_spec(cfg: ModelConfig, mesh, stacked: bool) -> P:
    """(num_pages, page_size, KVH, D-or-1) pool spec.

    KV heads go on the TP axis when they divide it — the attention
    math is head-parallel, so each shard holds whole heads and the
    gather/scatter through the page table stays local.  When heads do
    not divide (GQA models reduced to 1 KV head), fall back to the
    in-page sequence axis; the page-id axis itself is NEVER sharded —
    page ids are data, and splitting the pool by page id would turn
    every host-side allocation decision into a placement decision."""
    tp_size = mesh.shape[TP_AXIS]
    if cfg.n_kv_heads % tp_size == 0:
        spec = (None, None, TP_AXIS, None)
    else:
        spec = (None, TP_AXIS, None, None)
    return P(*((None,) + spec if stacked else spec))


def _paged_mla_spec(stacked: bool) -> P:
    """(num_pages, page_size, rank) latent pool: no head axis exists, so
    the in-page sequence axis is the only shardable one."""
    spec = (None, TP_AXIS, None)
    return P(*((None,) + spec if stacked else spec))


def page_table_spec(mesh) -> P:
    """The (batch, max_pages) page table stays host-authored and fully
    replicated: every shard walks the same logical table (the pool's
    sharded axis is heads/rows *within* a page, never the page id)."""
    del mesh
    return P(None, None)


def _mamba_cache_spec(mesh, leafk: str, stacked: bool) -> P:
    dp = fsdp_axes(mesh)
    if leafk == "conv":                  # (B, W-1, C)
        spec = (dp, None, TP_AXIS)
    else:                                # ssm state (B, H, N, P)
        spec = (dp, TP_AXIS, None, None)
    return P(*((None,) + spec if stacked else spec))


def cache_specs(cfg: ModelConfig, caches: Any, mesh, *, batch: int,
                paged: bool) -> Any:
    """Partition specs for a cache tree.  ``paged`` says which layout the
    caller built: a serving engine's page pools, or the unpaged
    per-sequence caches a plain ``decode_step`` reads."""
    seq_to_dp = batch == 1

    def rule(path, leaf):
        keys = [k for k in path]
        stacked = "blocks" in keys
        leafk = keys[-1]
        if leafk in ("k", "v", "k_scale", "v_scale"):
            # paged pools drop the batch axis — (num_pages, page_size,
            # KVH, D) with int8 scale pools at D=1 — and get their own
            # head-or-sequence rule; mamba state stays per-slot (B, ...)
            # next to the pools, so only the KV/MLA leaves switch
            spec = (_paged_kv_spec(cfg, mesh, stacked) if paged
                    else _kv_spec(cfg, mesh, batch, stacked, seq_to_dp))
        elif leafk in ("c_kv", "k_rope"):
            spec = (_paged_mla_spec(stacked) if paged
                    else _mla_spec(mesh, stacked, seq_to_dp))
        elif leafk in ("conv", "ssm"):
            spec = _mamba_cache_spec(mesh, leafk, stacked)
        else:
            spec = P(*([None] * leaf.ndim))
        return _fit_spec(spec, leaf.shape, mesh)

    return _tree_map_with_str_path(rule, caches)
