"""Compile the serving kernels for a TPU v5e chip that is described, not
attached: the TPU compiler runs here and refuses what the chip would
refuse (misaligned blocks, Mosaic gaps) without a device.

Nothing touches the TPU library at import: the topology is described in
a module-scoped fixture, which skips where it cannot be.  The compile
cache is off around these compiles — an entry written for a described
chip cannot be read back without one.

The main path (``ops.quant_matmul`` with the fused dequant epilogue,
which ``core.linear`` uses for w8a8 and w4a8) compiles at qwen3-4b's
seven projection shapes with the blocks it picks for each.  Kernels the
compiler refuses today are strict xfails that name the refusal, so a fix
shows up as an XPASS.
"""

import re

import jax
import jax.numpy as jnp
import pytest

# qwen3-4b projections (d_model 2560, 32 q heads / 8 kv heads x 128,
# d_ff 9728): (K, N) of the int8 weight
_PROJ = {"q": (2560, 4096), "k": (2560, 1024), "v": (2560, 1024),
         "o": (4096, 2560), "gate": (2560, 9728), "up": (2560, 9728),
         "down": (9728, 2560)}

_BLOCK_RULE = r"divisible by 8 and 128"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shape_on_chip(topo):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


class KernelRefused(Exception):
    """The TPU compiler refused the kernel with the expected message."""


def _expect_refusal(pattern, fn, *args):
    """Compile; re-raise the compiler's error as ``KernelRefused`` only
    when it is the known refusal, so any other failure stays a failure."""
    try:
        _compile(fn, *args)
    except Exception as e:  # noqa: BLE001 — the message decides
        if re.search(pattern, str(e)):
            raise KernelRefused(str(e)[:300]) from e
        raise


@pytest.mark.parametrize("proj", list(_PROJ))
@pytest.mark.parametrize("m", [8, 16, 2048])
def test_quant_matmul_compiles_at_qwen3_4b_shapes(shape_on_chip, m, proj):
    """Decode (M=8, 16) and prefill (M=2048) int8 matmuls with the
    in-kernel dequant epilogue lower to a Mosaic kernel and compile, with
    the blocks ``ops.pick_blocks`` chooses for the shape."""
    from repro.kernels import ops
    k, n = _PROJ[proj]

    def fn(x, w, xs, ws):
        return ops.quant_matmul(x, w, x_scale=xs, w_scale=ws,
                                out_dtype=jnp.bfloat16, interpret=False)

    compiled = _compile(fn, shape_on_chip((m, k), jnp.int8),
                        shape_on_chip((k, n), jnp.int8),
                        shape_on_chip((), jnp.float32),
                        shape_on_chip((1, n), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()
    assert ops.tile_plan()[(m, k, n, "int8")][:3] == \
        ops.pick_blocks(m, k, n, out_bytes=2)


@pytest.mark.xfail(strict=True, raises=KernelRefused,
                   reason="packed-int4 weight block (bk, bn//2) = (128, 64) "
                          "is not (8, 128)-aligned")
def test_int4_packed_matmul_compiles(shape_on_chip):
    from repro.kernels import ops
    _expect_refusal(
        _BLOCK_RULE,
        lambda x, w: ops.quant_matmul(x, w, w_format="int4_packed",
                                      interpret=False),
        shape_on_chip((8, 2560), jnp.int8),
        shape_on_chip((2560, 2048), jnp.int8))


@pytest.mark.xfail(strict=True, raises=KernelRefused,
                   reason="Mosaic: 'Mix int/int - NYI' for the int8 x "
                          "int16 selection matmul")
def test_lut_matmul_compiles(shape_on_chip):
    from repro.kernels.lut_matmul import lut_matmul_pallas
    _expect_refusal(
        r"Mix int/int - NYI",
        lambda x, w: lut_matmul_pallas(x, w, interpret=False),
        shape_on_chip((128, 256), jnp.int8),
        shape_on_chip((256, 128), jnp.int8))


@pytest.mark.xfail(strict=True, raises=KernelRefused,
                   reason="KV page block (1, page_size, 1, d) is not "
                          "(8, 128)-aligned")
def test_paged_decode_attention_compiles(shape_on_chip):
    from repro.kernels.flash_attention import paged_decode_attention_pallas
    _expect_refusal(
        _BLOCK_RULE,
        lambda q, k, v, t, p: paged_decode_attention_pallas(
            q, k, v, t, p, scale=128 ** -0.5, interpret=False),
        shape_on_chip((8, 8, 4, 128), jnp.bfloat16),
        shape_on_chip((64, 16, 8, 128), jnp.bfloat16),
        shape_on_chip((64, 16, 8, 128), jnp.bfloat16),
        shape_on_chip((8, 8), jnp.int32),
        shape_on_chip((8,), jnp.int32))


@pytest.mark.xfail(strict=True, raises=KernelRefused,
                   reason="flash forward lse out block (1, bq) is not "
                          "(8, 128)-aligned")
def test_flash_attention_fwd_compiles(shape_on_chip):
    from repro.kernels.flash_attention import flash_attention_fwd_pallas
    qkv = [shape_on_chip((4, 256, 128), jnp.bfloat16)] * 3
    _expect_refusal(
        _BLOCK_RULE,
        lambda q, k, v: flash_attention_fwd_pallas(
            q, k, v, scale=128 ** -0.5, interpret=False),
        *qkv)


@pytest.mark.xfail(strict=True, raises=KernelRefused,
                   reason="flash backward lse in block (1, bq) is not "
                          "(8, 128)-aligned")
def test_flash_attention_bwd_compiles(shape_on_chip):
    from repro.kernels.flash_attention import flash_attention_bwd_pallas
    t = shape_on_chip((4, 256, 128), jnp.bfloat16)
    _expect_refusal(
        _BLOCK_RULE,
        lambda q, k, v, o, lse, do: flash_attention_bwd_pallas(
            q, k, v, o, lse, do, scale=128 ** -0.5, interpret=False),
        t, t, t, t, shape_on_chip((4, 256), jnp.float32), t)


@pytest.mark.xfail(strict=True, raises=KernelRefused,
                   reason="Mosaic kernels cannot be automatically "
                          "partitioned: tp > 1 with quant_backend='pallas' "
                          "needs a shard_map around ops.quant_matmul")
def test_quant_matmul_compiles_tensor_parallel(topo):
    """A column-parallel qwen3-4b q projection over a (1, 4) mesh."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.kernels import ops
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))
    k, n = _PROJ["q"]

    def on(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    _expect_refusal(
        r"cannot be automatically partitioned",
        lambda x, w, xs, ws: ops.quant_matmul(
            x, w, x_scale=xs, w_scale=ws, out_dtype=jnp.bfloat16,
            interpret=False),
        on((8, k), jnp.int8, P()), on((k, n), jnp.int8, P(None, "model")),
        on((), jnp.float32, P()), on((1, n), jnp.float32, P(None, "model")))


# ---------------------------------------------------------------------------
# Stage programs at qwen3-4b's widths with the benchmark cell's pool
# ---------------------------------------------------------------------------

# the chat cell's serving settings: 16 slots over 1,537 pages of 16 rows
_CELL = dict(batch=16, max_len=2560, prefill_len=2048, decode_chunk=8,
             cache_mode="paged", page_size=16, num_pages=1537,
             quant_mode="w8a8_nibble", quant_backend="pallas")


@pytest.fixture(scope="module")
def cell_programs(topo):
    """``decode_chunk`` and ``prefill`` of an engine at qwen3-4b's widths
    with 4 and 8 layers, compiled for one described v5e chip:
    ``{(stage, layers): (temp bytes, pool bytes, pool shapes, HLO)}``.
    The engine is built on abstract params and caches, so nothing the
    size of a layer is allocated here."""
    from jax.sharding import SingleDeviceSharding

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import model_init
    from repro.serve import Engine, ServeConfig
    from repro.serve import engine as engine_mod

    one_chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    b, c = _CELL["batch"], _CELL["decode_chunk"]
    max_pages = _CELL["max_len"] // _CELL["page_size"]
    out = {}
    init = engine_mod.init_caches
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_on_tpu", lambda: True)   # lower Pallas to Mosaic
        mp.setattr(engine_mod, "init_caches",
                   lambda *a: jax.eval_shape(lambda: init(*a)))
        for layers in (4, 8):
            cfg = get_config("qwen3-4b").replace(n_layers=layers)
            params = jax.eval_shape(
                lambda: model_init(jax.random.PRNGKey(0), cfg))
            engine = Engine(cfg, params, ServeConfig(**_CELL))
            caches = engine._caches
            pool = sum(a.size * a.dtype.itemsize
                       for a in jax.tree_util.tree_leaves(caches))
            k = caches["blocks"]["0"]["attn"]["k"].shape
            shapes = (k, (k[0] * k[1],) + k[2:])  # stacked, and as one pool
            head = (on_chip(params), on_chip(caches))
            args = {
                "decode_chunk": head + (
                    arg((b, 1), jnp.int32), arg((b,), jnp.int32),
                    arg((b,), jnp.bool_), arg((b,), jnp.int32),
                    arg((b, max_pages), jnp.int32), arg((c, b), jnp.int32),
                    arg((c, b), jnp.bool_), arg((b, 2), jnp.uint32),
                    arg((b,), jnp.int32)),
                "prefill": head + (
                    arg((1, _CELL["prefill_len"]), jnp.int32),
                    arg((), jnp.int32), arg((), jnp.int32),
                    arg((max_pages,), jnp.int32), arg((2,), jnp.uint32)),
            }
            programs = engine.stage_programs()
            for stage, a in args.items():
                compiled = programs[stage].jit_fn.lower(*a).compile()
                temp = compiled.memory_analysis().temp_size_in_bytes
                out[stage, layers] = (temp, pool, shapes,
                                      compiled.as_text())
    return out


def _pool_shaped_copies(hlo, shapes):
    """Instructions of the compiled program that copy, transpose or
    broadcast into a buffer of one of ``shapes``."""
    dims = "|".join(",".join(map(str, s)) for s in shapes)
    result = re.compile(r"= \w+\[(%s)\]\{[^}]*\} (copy|transpose|broadcast)"
                        r"(-start)?\(" % dims)
    return [line.strip()[:200] for line in hlo.splitlines()
            if result.search(line)]


def test_decode_chunk_updates_the_pool_in_place(cell_programs):
    """The decode chunk holds no copy of the stacked KV pool: its temp
    does not grow with depth, and from 8 layers on it is below half the
    pool.  At 4 layers it is still below one pool (a pool-sized buffer
    would pass that): most of it is one layer's gathered context, 2 x
    84 MB, the same at any depth."""
    (t4, p4, s4, h4), (t8, p8, s8, h8) = (cell_programs["decode_chunk", n]
                                          for n in (4, 8))
    assert not _pool_shaped_copies(h4, s4)
    assert not _pool_shaped_copies(h8, s8)
    assert t8 <= t4, (t4, t8)
    assert t4 < p4, (t4, p4)
    assert t8 < p8 / 2, (t8, p8)


def test_prefill_holds_no_pool_copy(cell_programs):
    """The prefill program merges a prompt's pages into the pool in
    place.  Its temp carries the prompt's attention activations and the
    fresh per-layer cache it emits, so it grows with depth, but by less
    than half of what the pool grows by."""
    (t4, p4, s4, h4), (t8, p8, s8, h8) = (cell_programs["prefill", n]
                                          for n in (4, 8))
    assert not _pool_shaped_copies(h4, s4)
    assert not _pool_shaped_copies(h8, s8)
    assert t8 - t4 < (p8 - p4) / 2, (t4, t8, p4, p8)
