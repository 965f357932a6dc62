"""Serve engine: generation plumbing, determinism, continuous batching
(per-slot positions, slot refill without recompile), quant-mode parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import model_init
from repro.serve import Engine, ServeConfig


def _setup(quant="dense"):
    cfg = reduced(get_config("yi-6b")).replace(quant_mode=quant)
    params = model_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _solo_greedy(params, cfg, prompt, n_new, max_len):
    """Reference: one sequence decoded alone at scalar positions over its
    own unpaged cache.  Both model calls are jitted, as the engine's
    stages are: an op-by-op run rounds the quantized modes' activation
    scales differently from a compiled program."""
    from repro.models import decode_step, prefill
    run_prefill = jax.jit(lambda p, t: prefill(p, cfg, t, max_len=max_len))
    run_decode = jax.jit(lambda p, t, c, i: decode_step(p, cfg, t, c, i))
    logits, caches, _ = run_prefill(params, prompt[None])
    tok = jnp.argmax(logits[:, -1].astype(jnp.float32), -1)[:, None] \
        .astype(jnp.int32)
    out = [int(tok[0, 0])]
    for i in range(n_new - 1):
        lg, caches = run_decode(params, tok, caches,
                                jnp.int32(prompt.shape[0] + i))
        tok = jnp.argmax(lg[:, -1].astype(jnp.float32), -1)[:, None] \
            .astype(jnp.int32)
        out.append(int(tok[0, 0]))
    return out


def test_generate_shapes_and_determinism():
    cfg, params = _setup()
    engine = Engine(cfg, params, ServeConfig(batch=2, max_len=32))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 cfg.vocab_size)
    out1 = engine.generate(prompts, n_new=8)
    out2 = engine.generate(prompts, n_new=8)
    assert out1.shape == (2, 16)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    # prompt is preserved
    np.testing.assert_array_equal(np.asarray(out1[:, :8]),
                                  np.asarray(prompts))


def test_greedy_matches_manual_argmax_rollout():
    cfg, params = _setup()
    from repro.models import decode_step, prefill
    prompts = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0,
                                 cfg.vocab_size)
    engine = Engine(cfg, params, ServeConfig(batch=1, max_len=24,
                                             page_size=8))
    out = engine.generate(prompts, n_new=4)

    logits, caches, _ = prefill(params, cfg, prompts, max_len=24)
    tok = jnp.argmax(logits[:, -1].astype(jnp.float32), -1)[:, None] \
        .astype(jnp.int32)
    manual = [int(tok[0, 0])]
    for i in range(3):
        lg, caches = decode_step(params, cfg, tok, caches, 8 + i)
        tok = jnp.argmax(lg[:, -1].astype(jnp.float32), -1)[:, None] \
            .astype(jnp.int32)
        manual.append(int(tok[0, 0]))
    assert out[0, 8:].tolist() == manual


def test_temperature_sampling_varies():
    cfg, params = _setup()
    scfg = ServeConfig(batch=1, max_len=16, temperature=5.0)
    engine = Engine(cfg, params, scfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0,
                                 cfg.vocab_size)
    outs = [engine.generate(prompts, 2, rng=jax.random.PRNGKey(s))[0, 6:]
            for s in range(8)]
    # high temperature: the decode chunk's draw is not a deterministic
    # argmax
    assert len({int(o[1]) for o in outs}) > 1
    # the FIRST post-prefill token must go through the same path — the
    # seed engine hardcoded argmax for it regardless of temperature
    assert len({int(o[0]) for o in outs}) > 1


def test_int8_kv_cache_decode_accuracy():
    """int8 KV cache (paper-aligned low-precision storage): teacher-forced
    decode must track the bf16-cache path closely."""
    import numpy as np

    from repro.models import decode_step, forward, prefill
    cfg, params = _setup()
    cfg8 = cfg.replace(kv_cache_dtype="int8")
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 8), 0,
                                cfg.vocab_size)
    full, _ = forward(params, cfg, tokens)
    logits, caches, _ = prefill(params, cfg8, tokens[:, :4], max_len=8)
    got = [np.asarray(logits[:, -1].astype(jnp.float32))]
    for t in range(4, 8):
        lg, caches = decode_step(params, cfg8, tokens[:, t:t + 1], caches, t)
        got.append(np.asarray(lg[:, -1].astype(jnp.float32)))
    want = np.asarray(full[:, 3:, :].astype(jnp.float32))
    got = np.stack(got, 1)[:, :want.shape[1]]
    rel = np.abs(got - want).max() / max(np.abs(want).max(), 1e-6)
    assert rel < 0.05, rel


def test_int8_kv_cache_halves_bytes():
    from repro.models import init_caches
    cfg, _ = _setup()
    big = init_caches(cfg.replace(kv_cache_dtype="bf16"), 2, 512)
    small = init_caches(cfg.replace(kv_cache_dtype="int8"), 2, 512)

    def nbytes(t):
        return sum(v.size * v.dtype.itemsize
                   for v in jax.tree_util.tree_leaves(t))

    # 2× minus the per-(token,head) f32 scales: at the reduced head_dim
    # of 16 the scale overhead is 4/16 = 25% → 1.6×; at production head
    # dims (128) it is 4/128 → 1.94×.
    ratio = nbytes(big) / nbytes(small)
    assert ratio > 1.55, ratio
    assert abs(ratio - 2 / (1 + 4 / cfg.head_dim)) < 1e-6  # exact accounting


# ---------------------------------------------------------------------------
# Continuous batching: per-slot positions, refill, compile stability
# ---------------------------------------------------------------------------

def test_decode_step_vector_positions_match_scalar():
    """A (B,) position vector with all-equal entries must bit-match the
    scalar path (same math, vmapped scatter)."""
    from repro.models import decode_step, prefill
    cfg, params = _setup()
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 cfg.vocab_size)
    _, caches, _ = prefill(params, cfg, prompts, max_len=16)
    tok = jnp.zeros((2, 1), jnp.int32)
    l_s, c_s = decode_step(params, cfg, tok, caches, 8)
    l_v, c_v = decode_step(params, cfg, tok, caches,
                           jnp.full((2,), 8, jnp.int32))
    np.testing.assert_array_equal(np.asarray(l_s), np.asarray(l_v))
    for a, b in zip(jax.tree_util.tree_leaves(c_s),
                    jax.tree_util.tree_leaves(c_v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("quant,backend", [
    ("dense", "xla"), ("dense", "pallas"),
    ("w8a8_nibble", "xla"), ("w8a8_nibble", "pallas"),
])
def test_staggered_batch_matches_solo(quant, backend):
    """The per-slot-position tentpole: a staggered batch (every slot a
    different prompt length, prefilled padded to the slot budget) must
    BIT-match each sequence decoded alone at scalar positions."""
    cfg, params = _setup(quant)
    cfg = cfg.replace(quant_backend=backend)
    max_len, n_new = 16, 4
    rng = np.random.default_rng(0)
    prompts = [jnp.asarray(rng.integers(0, cfg.vocab_size, p), jnp.int32)
               for p in (3, 5, 7)]

    engine = Engine(cfg, params, ServeConfig(batch=3, max_len=max_len,
                                             prefill_len=8, decode_chunk=3))
    ids = [engine.submit(p, n_new) for p in prompts]
    done = engine.run()
    for rid, prompt in zip(ids, prompts):
        want = _solo_greedy(params, cfg, prompt, n_new, max_len)
        assert done[rid].tokens == want, (quant, backend, done[rid].tokens,
                                          want)


def test_slot_refill_without_recompile():
    """More requests than slots, mixed prompt lengths and budgets: every
    refill must reuse the two compiled programs (prefill, decode chunk).
    Bit-exactness of the refilled slots is covered against solo decoding
    too — a refilled slot starts mid-stream next to older sequences."""
    cfg, params = _setup()
    engine = Engine(cfg, params, ServeConfig(batch=2, max_len=24,
                                             prefill_len=8, decode_chunk=4,
                                             page_size=8))
    rng = np.random.default_rng(1)
    spec = [(4, 6), (8, 3), (5, 7), (6, 1), (3, 5)]
    prompts = [jnp.asarray(rng.integers(0, cfg.vocab_size, p), jnp.int32)
               for p, _ in spec]
    ids = [engine.submit(p, n) for p, (_, n) in zip(prompts, spec)]
    done = engine.run()
    assert engine.compile_counts == {"prefill": 1, "decode_chunk": 1}
    for rid, prompt, (_, n) in zip(ids, prompts, spec):
        assert len(done[rid].tokens) == n
        assert done[rid].tokens == _solo_greedy(params, cfg, prompt, n, 24)


def test_eos_stops_slot_early():
    cfg, params = _setup()
    # pick an eos id the greedy path actually emits: probe a solo run
    probe = _solo_greedy(params, cfg,
                         jnp.asarray([1, 2, 3, 4], jnp.int32), 8, 16)
    eos = probe[3]   # stop where the solo run emits this token
    engine = Engine(cfg, params, ServeConfig(batch=2, max_len=16,
                                             prefill_len=4, eos_id=eos,
                                             decode_chunk=4))
    rid = engine.submit(jnp.asarray([1, 2, 3, 4], jnp.int32), 8)
    done = engine.run()
    toks = done[rid].tokens
    assert toks == probe[:probe.index(eos) + 1]   # truncated at first eos
    assert toks[-1] == eos


def test_generate_validates_batch():
    cfg, params = _setup()
    engine = Engine(cfg, params, ServeConfig(batch=2, max_len=16))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (3, 4), 0,
                                 cfg.vocab_size)
    with pytest.raises(ValueError, match="batch"):
        engine.generate(prompts, 2)
    with pytest.raises(ValueError, match="max_len"):
        engine.generate(prompts[:2], 20)
