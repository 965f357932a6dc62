"""The names the benchmark matches in a device trace: each stage program
compiles as ``jit_<function>`` from the engine's one table
(``STAGE_FUNCTIONS``), whatever its ``_build_*`` closure is called, and
every operation's metadata carries the stage's named scope (and, in the
decode chunk, the scopes of its costs)."""

import re

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import model_init
from repro.serve import Engine, ServeConfig
from repro.serve.engine import STAGE_FUNCTIONS, _CountingJit

# the program names the benchmark's readers match today
PINNED = {"prefill": "jit_prefill_into_slot", "decode_chunk": "jit_chunk"}
# scopes inside the decode chunk, one per cost of a paged w8a8 step
DECODE_SCOPES = ("attn.gather", "attn.cache_write", "quant.act",
                 "quant.weight", "nibble.matmul")

MODES = {
    "plain": {},
    "wave": {"admit_group": 2},
    "spec": {"spec_decode": True, "spec_k": 2},
}


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("qwen3-4b")).replace(quant_mode="w8a8_nibble")
    return cfg, model_init(jax.random.PRNGKey(0), cfg)


def _lowered(model, mode):
    """Each built stage of a served engine in ``mode``, lowered from the
    signature it recorded: ``{stage: lowered}``."""
    cfg, params = model
    engine = Engine(cfg, params, ServeConfig(
        batch=2, max_len=32, prefill_len=8, page_size=8,
        decode_chunk=2, **MODES[mode]))
    rng = np.random.default_rng(0)
    for n in (3, 5):
        engine.submit(rng.integers(0, cfg.vocab_size, n), 4)
    engine.run()
    out = {}
    for stage, fn in engine.stage_programs().items():
        (sig,) = fn.signatures
        out[stage] = fn.jit_fn.lower(*_CountingJit.abstract_args(sig))
    return out


def _module_name(lowered) -> str:
    return lowered.as_text().split("module @", 1)[1].split(" ", 1)[0]


@pytest.mark.parametrize("mode", sorted(MODES))
def test_stage_programs_are_named_from_the_table(model, mode):
    lowered = _lowered(model, mode)
    want = {"plain": {"prefill", "decode_chunk"},
            "wave": {"prefill_chunk", "decode_chunk"},
            "spec": {"prefill", "draft", "verify"}}[mode]
    assert set(lowered) == want
    for stage, low in lowered.items():
        fn = STAGE_FUNCTIONS[stage]
        name = _module_name(low)
        assert name == f"jit_{fn}"
        if stage in PINNED:
            assert name == PINNED[stage]
        # the stage's scope is in its operations' metadata
        assert f'op_name="jit({fn})/{stage}/' in _op_names(low)


def _op_names(lowered) -> str:
    """The compiled program's text, whose operations carry their full
    scope path as ``op_name`` metadata."""
    return lowered.compile().as_text()


def test_decode_chunk_scopes_name_its_costs(model):
    text = _op_names(_lowered(model, "plain")["decode_chunk"])
    for scope in DECODE_SCOPES:
        assert re.search(rf'op_name="jit\(chunk\)/decode_chunk/[^"]*'
                         rf'{re.escape(scope)}/', text), scope
