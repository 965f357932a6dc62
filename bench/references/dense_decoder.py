"""Plain float32 reference of a dense decoder-only transformer of the
Llama / Qwen3 kind, and the seeded weights that the benchmark serves.

It follows the published description of both models: pre-norm RMSNorm
blocks, grouped-query attention with rotary position embeddings
(rotate-half convention, ``theta ** (-2i / head_dim)``), Qwen3's
per-head RMSNorm on queries and keys before the rotation (``qk_norm``),
a SwiGLU MLP, a final RMSNorm, and a head that is the embedding
transposed where ``tie_word_embeddings``.  Everything is float32 at
``precision="highest"``, with no kernel, cache or batching: one causal
pass over one sequence.  It imports nothing of the program.

The weights are made here, from the seed, in the layout and the type
that the program serves (a nested dict of bf16 projections stacked over
the layers, with float32 norm scales).  Two conventions of that layout
are read here as they are: projections are stored ``(in, out)``, and a
norm scale ``s`` stands for the weight ``1 + s``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["init_weights", "token_gaps"]

# per-leaf std of the random projections: 1 / sqrt(fan-in), so that every
# layer's output is of the order of its input; the embedding's 0.02 keeps
# the tied head's logits near unit spread
_EMBED_STD = 0.02
_NORM_STD = 0.1


def _dims(model: dict) -> tuple:
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    kvh = model["num_key_value_heads"]
    hd = model.get("head_dim") or d // h
    return (model["num_hidden_layers"], d, h, kvh, hd,
            model["intermediate_size"], model["vocab_size"])


def _shapes(model: dict, qk_norm: bool) -> dict:
    """The weight tree as ``{path: (shape, kind)}`` leaves."""
    n, d, h, kvh, hd, f, v = _dims(model)
    attn = {"wq": {"w": ((n, d, h * hd), "proj")},
            "wk": {"w": ((n, d, kvh * hd), "proj")},
            "wv": {"w": ((n, d, kvh * hd), "proj")},
            "wo": {"w": ((n, h * hd, d), "proj")}}
    if qk_norm:
        attn["q_norm"] = {"scale": ((n, hd), "norm")}
        attn["k_norm"] = {"scale": ((n, hd), "norm")}
    block = {"mixer_norm": {"scale": ((n, d), "norm")},
             "attn": attn,
             "ffn_norm": {"scale": ((n, d), "norm")},
             "mlp": {"gate": {"w": ((n, d, f), "proj")},
                     "up": {"w": ((n, d, f), "proj")},
                     "down": {"w": ((n, f, d), "proj")}}}
    tree = {"embed": {"emb": ((v, d), "embed")},
            "stack": {"prefix": [], "blocks": {"0": block}, "suffix": []},
            "final_norm": {"scale": ((d,), "norm")}}
    if not model["tie_word_embeddings"]:
        tree["lm_head"] = {"w": ((d, v), "proj")}
    return tree


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _init(key, model_items: tuple, qk_norm: bool):
    tree = _shapes(dict(model_items), qk_norm)
    leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=_is_leaf)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (shape, kind) in zip(keys, leaves):
        if kind == "norm":
            out.append(_NORM_STD * jax.random.normal(k, shape, jnp.float32))
            continue
        std = _EMBED_STD if kind == "embed" else shape[-2] ** -0.5
        out.append(jax.random.normal(k, shape, jnp.bfloat16)
                   * jnp.asarray(std, jnp.bfloat16))
    return jax.tree_util.tree_unflatten(treedef, out)


def seed_key(seed: int):
    """A PRNG key that tells apart every seed up to 64 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def init_weights(model: dict, qk_norm: bool, seed: int):
    """The served weights, drawn on the default device from ``seed`` in
    one jitted call."""
    return _init(seed_key(seed), tuple(sorted(model.items())), qk_norm)


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)


def _rope(x, pos, theta):
    """x: (S, heads, hd); rotate-half."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd // 2, dtype=jnp.float32)
                      / (hd // 2))
    ang = pos[:, None].astype(jnp.float32) * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, w, pos, model, qk_norm):
    _, d, h, kvh, hd, _, _ = _dims(model)
    eps = model["rms_norm_eps"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    s = x.shape[0]
    a = w["attn"]
    hx = _rms(x, w["mixer_norm"]["scale"], eps)
    q = (hx @ f32(a["wq"]["w"])).reshape(s, h, hd)
    k = (hx @ f32(a["wk"]["w"])).reshape(s, kvh, hd)
    v = (hx @ f32(a["wv"]["w"])).reshape(s, kvh, hd)
    if qk_norm:
        q = _rms(q, a["q_norm"]["scale"], eps)
        k = _rms(k, a["k_norm"]["scale"], eps)
    q = _rope(q, pos, model["rope_theta"])
    k = _rope(k, pos, model["rope_theta"])
    # query head i reads key/value head i // (h // kvh)
    k = jnp.repeat(k, h // kvh, axis=1)
    v = jnp.repeat(v, h // kvh, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = pos[None, :, None] >= pos[None, None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + att.reshape(s, h * hd) @ f32(a["wo"]["w"])
    m = w["mlp"]
    hx = _rms(x, w["ffn_norm"]["scale"], eps)
    g = jax.nn.silu(hx @ f32(m["gate"]["w"])) * (hx @ f32(m["up"]["w"]))
    return x + g @ f32(m["down"]["w"])


@functools.partial(jax.jit, static_argnums=(3, 4))
def _gaps(weights, tokens, wanted, model_items: tuple, qk_norm: bool):
    model = dict(model_items)
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(tokens.shape[0])
        x = weights["embed"]["emb"][tokens].astype(jnp.float32)

        def body(x, w):
            return _layer(x, w, pos, model, qk_norm), None

        x, _ = jax.lax.scan(body, x, weights["stack"]["blocks"]["0"])
        x = _rms(x, weights["final_norm"]["scale"], model["rms_norm_eps"])
        head = (weights["embed"]["emb"].T if model["tie_word_embeddings"]
                else weights["lm_head"]["w"])
        logits = x @ head.astype(jnp.float32)
    chosen = jnp.take_along_axis(logits, wanted[:, None], axis=1)[:, 0]
    return (logits.max(axis=1) - chosen) / logits.std(axis=1)


def token_gaps(weights, model: dict, qk_norm: bool, tokens, wanted):
    """For each position ``p`` of ``tokens`` (one sequence, int32), the
    gap by which the reference's logit of ``wanted[p]`` lies below its
    best logit there, in standard deviations of that position's logits:
    0 where ``wanted[p]`` is the reference's first choice."""
    return _gaps(weights, jnp.asarray(tokens, jnp.int32),
                 jnp.asarray(wanted, jnp.int32),
                 tuple(sorted(model.items())), qk_norm)
