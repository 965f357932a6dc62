"""The comparison that decides ``correct``, on the CPU dry path: a sound
run passes, and a run whose timed path is broken underneath fails it,
once for each fault a one-chip serving cell can have."""

import jax.numpy as jnp
import pytest

from bench.tests import dry


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = dry.copy(tmp_path_factory.mktemp("faults"))
    dry.add_tiny(root)
    return root


def test_sound_run_is_correct(root):
    r = dry.run(root)
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] == 8
    assert list(r)[-1] == "checks"
    c = r["checks"][dry.READING]
    assert c["value"] <= c["limit"]
    assert set(r["readings"]) == {"gap_max_std", "gap_mean_std",
                                  "off_argmax_share",
                                  "gap_mean_std_by_request"}


def _token_altered(monkeypatch):
    from repro.serve import engine
    real = engine._slot_sampler

    def bad(scfg):
        sample = real(scfg)
        return lambda logits, keys: (sample(logits, keys) + 1) % \
            logits.shape[-1]
    monkeypatch.setattr(engine, "_slot_sampler", bad)


def _state_unchanged(monkeypatch):
    # decode writes no K/V: the step hands its cache back as it was
    from repro.models import attention
    monkeypatch.setattr(attention, "scatter_paged_rows",
                        lambda pool, new, table, index: pool)


def _half_batch(monkeypatch):
    # half of the slots is not computed: the first half takes the
    # second half's logits
    from repro.serve import engine
    real = engine.decode_step

    def half(params, cfg, token, caches, index, **kw):
        logits, caches = real(params, cfg, token, caches, index, **kw)
        b = logits.shape[0]
        rest = logits[b // 2:]
        return jnp.concatenate([rest, rest])[:b], caches
    monkeypatch.setattr(engine, "decode_step", half)


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch])
def test_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    r = dry.run(root)
    assert r["correct"] is False
    c = r["checks"][dry.READING]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("prompt,served,length", [(300, 100, 512),
                                                  (2040, 60, 2304),
                                                  (2048, 512, 2304)])
def test_reference_sequence_ends_at_the_last_compared_token(prompt, served,
                                                            length):
    import numpy as np
    from bench import harness
    toks = list(range(1000, 1000 + served))
    rec = {"prompt": np.arange(1, prompt + 1, dtype=np.int32),
           "tokens": toks}
    seq, at = harness.forced(rec, 2560)
    k = min(served, harness.CHECK_FIRST)
    assert len(seq) == length
    assert at == slice(prompt - 1, prompt - 1 + k)
    assert list(seq[prompt:prompt + k - 1]) == toks[:k - 1]
    assert not seq[prompt + k - 1:].any()
