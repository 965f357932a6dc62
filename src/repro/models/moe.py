"""Mixture-of-Experts block: top-k routing, sort-based dispatch, EP-ready.

Two execution paths, same mathematics:

* **local** (no mesh): fixed-shape sort/scatter dispatch on the whole
  token set — used by unit tests and single-device runs.
* **sharded** (ambient mesh with a "model" axis and E % tp == 0): a
  ``shard_map`` over the mesh.  Tokens stay sharded on the data axes and
  *replicated* across "model"; each model shard owns E/tp experts,
  locally dispatches only the (token, k) assignments routed to its
  experts, and the combine is a single ``psum`` over "model".  This
  keeps every buffer local-token-sized — the naive global formulation
  makes XLA all-gather the full 1M-token batch for the argsort (measured
  726 GB/device temps on deepseek-v3 before this path existed).

Dispatch details (both paths): flatten (token, k) assignments, sort by
expert id (stable), position-in-segment via cumsum offsets, capacity
``C = ceil(k·T/E · capacity_factor)`` with overflow dropped, scatter-add
into the (E, C, D) buffer (add, not set: dropped entries contribute
zeros at slot (0,0) and must not overwrite a real resident).

Expert weights are stacked (E, ·, ·) arrays → EP is one PartitionSpec.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import mlp_apply, mlp_init

__all__ = ["moe_init", "moe_apply"]


def moe_init(key, cfg) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    ks = jax.random.split(key, 5)
    scale = (2.0 / (d + f)) ** 0.5

    def w(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * scale) \
            .astype(jnp.bfloat16)

    p = {
        "router": (jax.random.normal(ks[0], (d, e), jnp.float32) * 0.02)
        .astype(jnp.float32),
        "w_gate": w(ks[1], (e, d, f)),
        "w_up": w(ks[2], (e, d, f)),
        "w_down": w(ks[3], (e, f, d)),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], d,
                               cfg.d_ff_expert * cfg.n_shared_experts)
    return p


def _capacity(cfg, n_tokens: int, n_experts: int) -> int:
    c = int(cfg.top_k * n_tokens / n_experts * cfg.capacity_factor)
    return max(8, c)


def _route(params, cfg, xt):
    """Shared routing math.  xt: (T, D) → (top_w, top_e, aux_loss).

    The router dot upcasts in the MXU (``preferred_element_type``)
    instead of materializing an f32 copy of xt — that copy was being
    saved as a shard_map residual across every scanned layer (measured:
    a 101 GiB/device f32[58,B,S,D] stack on deepseek-v3 train)."""
    e, k = cfg.n_experts, cfg.top_k
    t = xt.shape[0]
    logits = jnp.dot(xt, params["router"].astype(xt.dtype),
                     preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    me = probs.mean(0)
    ce = jnp.zeros((e,)).at[top_e.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce)
    return top_w, top_e, aux


def _dispatch_compute_combine(cfg, xt, top_w, top_e, w_gate, w_up, w_down,
                              *, e_lo: int, e_count: int, cap: int):
    """Sort-dispatch the assignments in [e_lo, e_lo+e_count) onto the
    local expert stack, run the FFN, combine back to (T, D) (zeros for
    tokens routed elsewhere)."""
    t, d = xt.shape
    k = cfg.top_k

    flat_e = top_e.reshape(-1)
    flat_w = top_w.reshape(-1).astype(jnp.float32)
    flat_tok = jnp.arange(t * k, dtype=jnp.int32) // k

    mine = (flat_e >= e_lo) & (flat_e < e_lo + e_count)
    loc_e = jnp.where(mine, flat_e - e_lo, e_count)      # e_count = overflow

    order = jnp.argsort(loc_e, stable=True)
    e_sorted = loc_e[order]
    tok_sorted = flat_tok[order]
    w_sorted = flat_w[order]

    counts = jnp.zeros((e_count + 1,), jnp.int32).at[e_sorted].add(1)
    seg_start = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                 jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(t * k, dtype=jnp.int32) - seg_start[e_sorted]
    keep = (pos_in_e < cap) & (e_sorted < e_count)

    slot_e = jnp.where(keep, e_sorted, 0)
    slot_c = jnp.where(keep, pos_in_e, 0)
    w_eff = jnp.where(keep, w_sorted, 0.0)

    contrib_in = jnp.where(keep[:, None], xt[tok_sorted], 0).astype(xt.dtype)
    expert_in = jnp.zeros((e_count, cap, d), xt.dtype) \
        .at[slot_e, slot_c].add(contrib_in)

    def ffn(h):
        g = jnp.einsum("ecd,edf->ecf", h, w_gate.astype(h.dtype))
        u = jnp.einsum("ecd,edf->ecf", h, w_up.astype(h.dtype))
        g = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype)
        return jnp.einsum("ecf,efd->ecd", g * u, w_down.astype(h.dtype))

    expert_out = ffn(expert_in)

    gathered = expert_out[slot_e, slot_c]
    contrib = gathered.astype(jnp.float32) * w_eff[:, None]
    out = jnp.zeros((t, d), jnp.float32).at[tok_sorted].add(contrib)
    return out.astype(xt.dtype)


# ---------------------------------------------------------------------------
# Execution paths
# ---------------------------------------------------------------------------

def _moe_local(params, cfg, x):
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    top_w, top_e, aux = _route(params, cfg, xt)
    cap = _capacity(cfg, b * s, cfg.n_experts)
    out = _dispatch_compute_combine(
        cfg, xt, top_w, top_e, params["w_gate"], params["w_up"],
        params["w_down"], e_lo=0, e_count=cfg.n_experts, cap=cap)
    return out.reshape(b, s, d), aux


def _moe_sharded(params, cfg, x, mesh):
    """shard_map EP: tokens on data axes, experts on the model axis."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    from repro.distributed.sharding import TP_AXIS

    tp = mesh.shape[TP_AXIS]
    e_per = cfg.n_experts // tp
    dp = tuple(a for a in mesh.axis_names if a != TP_AXIS)
    b = x.shape[0]
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    x_spec = P(dp, None, None) if b % dp_size == 0 else P(None, None, None)

    def local_fn(x_loc, router, w_gate, w_up, w_down):
        bl, sl, d = x_loc.shape
        xt = x_loc.reshape(bl * sl, d)
        top_w, top_e, aux = _route({"router": router}, cfg, xt)
        # capacity is per *local* token count: same expected load per
        # expert as the global formulation, locally bounded buffers.
        cap = _capacity(cfg, bl * sl, cfg.n_experts)
        m_idx = jax.lax.axis_index(TP_AXIS)
        e_lo = m_idx * e_per
        out = _dispatch_compute_combine(
            cfg, xt, top_w, top_e, w_gate, w_up, w_down,
            e_lo=e_lo, e_count=e_per, cap=cap)
        out = jax.lax.psum(out, TP_AXIS)     # combine across expert shards
        return out.reshape(bl, sl, d), aux

    # remat inside the shard_map: its residuals are otherwise saved by
    # the *forward* layer scan (the outer jax.checkpoint does not make
    # shard_map internals primal-only), stacking per-layer buffers.
    local_fn = jax.checkpoint(local_fn, prevent_cse=False)

    out, aux = shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_spec, P(None, None), P(TP_AXIS, None, None),
                  P(TP_AXIS, None, None), P(TP_AXIS, None, None)),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"],
      params["w_down"])
    return out, aux


def moe_apply(params, cfg, x, *, rng=None):
    """x: (B, S, D) → (out (B,S,D), aux_loss scalar)."""
    from repro.distributed import sharding as shr

    mesh = shr._AMBIENT_MESH
    if (mesh is not None and shr.TP_AXIS in mesh.axis_names
            and cfg.n_experts % mesh.shape[shr.TP_AXIS] == 0):
        out, aux = _moe_sharded(params, cfg, x, mesh)
    else:
        out, aux = _moe_local(params, cfg, x)

    if cfg.n_shared_experts:
        b, s, d = x.shape
        shared = mlp_apply(params["shared"], x.reshape(b * s, d),
                           act=cfg.act, quant_mode=cfg.quant_mode,
                           quant_backend=cfg.quant_backend)
        out = out + shared.reshape(b, s, d)
    return out, aux
