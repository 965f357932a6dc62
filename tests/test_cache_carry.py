"""The block caches ride the layer scan as a carry updated in place.

Every stage program that decodes (``decode_chunk``, draft, verify, the
wave prefill) hands the stacked KV pool or mamba state to
``decode_step``, and a plain ``decode_step`` over one sequence's
unpaged cache (the reference the serving tests hold the engine to) does
the same with that cache; the scanned blocks write each layer's rows
straight into the stack.  These tests pin that the carry changes no
bit: served tokens and the caches left behind equal, bit for bit, those
of a ``decode_step`` that scans the block caches as ``xs`` and restacks
what each layer returns as ``ys`` (the scan before the carry), both
scanned and unrolled (``unrolled_blocks()``, the costing path).  A scanned and an
unrolled program are not compared with each other bit for bit: the CPU
compiler rounds a bf16 intermediate in one and not in the other.

The pool build is pinned too: one zero allocation per stacked leaf.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import model_init
from repro.models import transformer as tr
from repro.models.attention import (init_kv_cache, init_mla_cache,
                                    init_paged_kv_cache,
                                    init_paged_mla_cache)
from repro.models.layers import embed_apply
from repro.models.mamba import init_mamba_cache
from repro.serve import Engine, ServeConfig
from repro.serve import engine as engine_mod

# name -> (arch, block repeats, model overrides, serve overrides); three
# or more blocks, so a layer's pages sit at a non-zero offset of the
# stack.  ``None`` decodes one sequence at the model level over its
# unpaged cache instead of serving it.
CASES = {
    "paged_bf16": ("qwen3-4b", 3, {}, {}),
    "paged_int8": ("qwen3-4b", 3, {"kv_cache_dtype": "int8"}, {}),
    "dense_slab": ("qwen3-4b", 3, {}, None),
    "mla": ("deepseek-v3-671b", 2, {}, {}),
    "mamba_hybrid": ("jamba-v0.1-52b", 2, {}, {}),
    "spec_draft_verify": ("yi-6b", 3, {}, {"spec_decode": True,
                                           "spec_k": 2}),
}


def _model(arch, blocks, over):
    cfg = reduced(get_config(arch))
    fixed = len(cfg.prefix_pattern) + len(cfg.suffix_pattern)
    cfg = cfg.replace(n_layers=fixed + blocks * len(cfg.block_pattern),
                      **over)
    return cfg, model_init(jax.random.PRNGKey(1), cfg)


def _serve(cfg, params, serve_over):
    kw = dict(batch=2, max_len=32, prefill_len=8, decode_chunk=3,
              page_size=4)
    kw.update(serve_over)
    engine = Engine(cfg, params, ServeConfig(**kw))
    rng = np.random.default_rng(0)
    ids = [engine.submit(jnp.asarray(rng.integers(0, cfg.vocab_size, n),
                                     jnp.int32), 6)
           for n in (5, 7, 4)]
    done = engine.run()
    tokens = [list(done[i].tokens) for i in ids]
    caches = [np.asarray(a) for a in jax.tree_util.tree_leaves(
        engine._caches)]
    return tokens, caches


def _decode_unpaged(cfg, params, step):
    """Greedy decode of one sequence through ``step`` (a ``decode_step``)
    over the unpaged cache its prefill grows to 32 rows."""
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 5)), jnp.int32)
    logits, caches, _ = tr.prefill(params, cfg, prompt, max_len=32)
    tokens = []
    for i in range(6):
        tok = jnp.argmax(logits[:, -1].astype(jnp.float32), -1)
        tokens.append(int(tok[0]))
        logits, caches = step(params, cfg, tok[:, None].astype(jnp.int32),
                              caches, 5 + i)
    return [tokens], [np.asarray(a)
                      for a in jax.tree_util.tree_leaves(caches)]


def _xs_ys_decode_step(params, cfg, token, caches, index, *,
                       enc_out=None, page_table=None):
    """``decode_step`` with the block caches scanned as ``xs`` and
    restacked as ``ys``: each layer takes its own slice of the stacked
    caches and returns its own new cache.  Unrolled under
    ``unrolled_blocks()``, as the scan is."""
    x = embed_apply(params["embed"], token,
                    scale_by_sqrt_dim=cfg.emb_scale_by_sqrt_dim)
    b, s = x.shape[0], token.shape[1]
    index = jnp.asarray(index, jnp.int32)
    pos = (jnp.broadcast_to(index.reshape(-1, 1), (b, 1))
           + jnp.arange(s, dtype=jnp.int32)[None, :])
    stack = params["stack"]

    def layer(p, spec, x, c):
        return tr._layer_apply(p, cfg, spec, x, positions=pos, cache=c,
                               cache_index=index, enc_out=enc_out,
                               mode="decode", page_table=page_table)[:2]

    def body(x, xs):
        blk_params, blk_caches = xs
        out = {}
        for j, spec in enumerate(cfg.block_pattern):
            x, out[str(j)] = layer(blk_params[str(j)], spec, x,
                                   blk_caches[str(j)])
        return x, out

    new = {"prefix": [], "blocks": None, "suffix": []}
    for p, spec, c in zip(stack["prefix"], cfg.prefix_pattern,
                          caches["prefix"]):
        x, nc = layer(p, spec, x, c)
        new["prefix"].append(nc)
    xs = (stack["blocks"], caches["blocks"])
    if tr._UNROLL_BLOCKS:
        ys = []
        for i in range(cfg.n_blocks):
            x, y = body(x, jax.tree_util.tree_map(lambda a: a[i], xs))
            ys.append(y)
        new["blocks"] = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)
    else:
        x, new["blocks"] = jax.lax.scan(body, x, xs)
    for p, spec, c in zip(stack["suffix"], cfg.suffix_pattern,
                          caches["suffix"]):
        x, nc = layer(p, spec, x, c)
        new["suffix"].append(nc)
    return tr._logits(params, cfg, x), new


def _assert_same(got, want):
    assert got[0] == want[0]
    assert len(got[1]) == len(want[1])
    for a, b in zip(got[1], want[1]):
        assert a.shape == b.shape and a.dtype == b.dtype
        # bit for bit: compare the raw bytes, so -0.0 / NaN patterns count
        assert a.tobytes() == b.tobytes()


@pytest.mark.parametrize("unrolled", [False, True],
                         ids=["scanned", "unrolled"])
@pytest.mark.parametrize("case", list(CASES))
def test_carried_cache_bitmatches_xs_ys(case, unrolled, monkeypatch):
    arch, blocks, over, serve_over = CASES[case]
    cfg, params = _model(arch, blocks, over)
    monkeypatch.setattr(tr, "_UNROLL_BLOCKS", unrolled)
    if serve_over is None:
        carried = _decode_unpaged(cfg, params, tr.decode_step)
        want = _decode_unpaged(cfg, params, _xs_ys_decode_step)
    else:
        carried = _serve(cfg, params, serve_over)
        monkeypatch.setattr(engine_mod, "decode_step", _xs_ys_decode_step)
        want = _serve(cfg, params, serve_over)
    assert all(len(t) == 6 for t in carried[0])
    _assert_same(carried, want)


# ---------------------------------------------------------------------------
# Pool build: zeros of the stacked shape, one allocation per leaf
# ---------------------------------------------------------------------------

def _layer_caches(cfg, spec, paged, batch, max_len, num_pages, page_size):
    """One layer's caches from the per-layer constructors."""
    if spec.mixer == "mamba":
        return {"mamba": init_mamba_cache(cfg, batch)}
    if spec.mixer != "attn":
        return {}
    if spec.attn_kind == "mla":
        return {"attn": init_paged_mla_cache(cfg, num_pages, page_size)
                if paged else init_mla_cache(cfg, batch, max_len)}
    return {"attn": init_paged_kv_cache(cfg, num_pages, page_size)
            if paged else init_kv_cache(cfg, batch, max_len)}


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
@pytest.mark.parametrize("arch,over", [
    ("qwen3-4b", {}), ("qwen3-4b", {"kv_cache_dtype": "int8"}),
    ("deepseek-v3-671b", {}), ("jamba-v0.1-52b", {})],
    ids=["gqa", "gqa_int8", "mla", "mamba_hybrid"])
def test_stacked_caches_are_one_zero_allocation_per_leaf(arch, over, paged):
    cfg = reduced(get_config(arch)).replace(**over)
    cfg = cfg.replace(n_layers=len(cfg.prefix_pattern)
                      + len(cfg.suffix_pattern)
                      + 3 * len(cfg.block_pattern))
    batch, max_len, num_pages, page_size = 2, 16, 9, 4
    if paged:
        build = lambda: tr.init_paged_caches(cfg, batch, num_pages,
                                             page_size)
    else:
        build = lambda: tr.init_caches(cfg, batch, max_len)
    caches = build()
    one = lambda spec: _layer_caches(cfg, spec, paged, batch, max_len,
                                     num_pages, page_size)
    want = {"prefix": [one(s) for s in cfg.prefix_pattern],
            "blocks": {str(j): jax.tree_util.tree_map(
                lambda a: np.broadcast_to(a, (cfg.n_blocks, *a.shape)),
                one(s)) for j, s in enumerate(cfg.block_pattern)},
            "suffix": [one(s) for s in cfg.suffix_pattern]}
    got_leaves, got_def = jax.tree_util.tree_flatten(caches)
    want_leaves, want_def = jax.tree_util.tree_flatten(want)
    assert got_def == want_def
    for g, w in zip(got_leaves, want_leaves):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert not np.asarray(g).any()
    # each leaf is one broadcast of a scalar zero to its final shape:
    # no per-layer cache is built and then stacked or copied
    jaxpr = jax.make_jaxpr(build)().jaxpr
    assert len(jaxpr.eqns) == len(got_leaves)
    for eqn in jaxpr.eqns:
        assert eqn.primitive.name == "broadcast_in_dim"
        assert eqn.invars[0].aval.shape == ()
