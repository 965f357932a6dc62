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
