"""Single-pass plane-fused kernel validation.

Bit-exactness of the plane-concatenated single-dot kernel vs kernels/ref
across signed edge cases (-128, the ±8 nibble boundaries) and unaligned
shapes exercising ``ops._pad_to`` on all three dims, plus the fused
dequant epilogue (bf16, no int32 round-trip) and the single
``quant_matmul`` dispatch path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantize as q
from repro.core.nibble import pack_int4
from repro.kernels import ops, ref

RNG = np.random.default_rng(20260730)


def _rand_i8(*shape):
    return jnp.asarray(RNG.integers(-128, 128, shape, dtype=np.int64),
                       jnp.int8)


# --- signed edge cases: extremes and the nibble-boundary values ----------
# ±8 is where the signed high-nibble plane flips sign; 15→16 is where the
# low plane wraps; -128 is the asymmetric int8 extreme whose hi<<4 plane
# saturates the int8 range of the pre-shifted operand.
EDGE_VALUES = [-128, -127, -17, -16, -9, -8, -7, -1, 0, 1, 7, 8, 9, 15,
               16, 17, 126, 127]


def test_edge_value_grid_exact():
    """Every (x, w) pair of edge values through a whole-block matmul."""
    vals = np.array(EDGE_VALUES, np.int64)
    # x rows cycle through edge values; w cols likewise → all pairs occur
    x = jnp.asarray(np.tile(vals, (32, 8))[:, :128], jnp.int8)
    w = jnp.asarray(np.tile(vals[:, None], (8, 32))[:128, :], jnp.int8)
    got = ops.nibble_matmul(x, w, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.nibble_matmul_ref(x, w)))


@pytest.mark.parametrize("xv", [-128, -8, -1, 8, 127])
@pytest.mark.parametrize("wv", [-128, -8, 8, 127])
def test_constant_extremes_exact(xv, wv):
    x = jnp.full((32, 256), xv, jnp.int8)
    w = jnp.full((256, 32), wv, jnp.int8)
    got = ops.nibble_matmul(x, w, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got), np.full((32, 32), xv * wv * 256, np.int64))


# --- unaligned shapes: _pad_to must fire on each dim separately ----------
UNALIGNED = [
    (129, 128, 128),   # pad M only
    (128, 129, 128),   # pad N only
    (128, 128, 129),   # pad K only
    (130, 129, 131),   # pad all three
    (1, 1, 1),         # degenerate
    (127, 255, 383),   # just below block multiples
]


@pytest.mark.parametrize("m,n,k", UNALIGNED)
def test_unaligned_shapes_exact(m, n, k):
    x, w = _rand_i8(m, k), _rand_i8(k, n)
    got = ops.quant_matmul(x, w, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.nibble_matmul_ref(x, w)))


@pytest.mark.parametrize("m,n,k", [(129, 130, 257), (64, 64, 64)])
def test_w4_packed_unaligned_exact(m, n, k):
    x = _rand_i8(m, k)
    w4 = jnp.asarray(RNG.integers(-8, 8, (k, n), dtype=np.int64), jnp.int8)
    wp = pack_int4(w4)
    got = ops.quant_matmul(x, wp, w_format="int4_packed", interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.nibble_matmul_w4_ref(x, wp)))


def test_multiblock_k_accumulation_exact():
    """K spanning several blocks exercises the VMEM-scratch accumulation
    and the single final flush."""
    x, w = _rand_i8(128, 640), _rand_i8(640, 128)
    got = ops.quant_matmul(x, w, bk=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.nibble_matmul_ref(x, w)))


# --- the fused dequant epilogue ------------------------------------------

@pytest.mark.parametrize("m,n,k", [(128, 128, 256), (32, 48, 100),
                                   (130, 129, 131)])
def test_scaled_epilogue_matches_xla_dequant(m, n, k):
    """bf16-epilogue output must equal the int32 kernel + XLA dequant,
    i.e. fusing the scale fold must not change the arithmetic."""
    x, w = _rand_i8(m, k), _rand_i8(k, n)
    xs = jnp.asarray(RNG.uniform(0.01, 0.1, (m, 1)), jnp.float32)
    ws = jnp.asarray(RNG.uniform(0.01, 0.1, (1, n)), jnp.float32)
    fused = ops.quant_matmul(x, w, x_scale=xs, w_scale=ws,
                             out_dtype=jnp.float32, interpret=True)
    acc = ops.quant_matmul(x, w, interpret=True)
    want = acc.astype(jnp.float32) * xs * ws
    np.testing.assert_allclose(np.asarray(fused), np.asarray(want),
                               rtol=1e-6)


def test_scaled_epilogue_emits_requested_dtype():
    x, w = _rand_i8(64, 64), _rand_i8(64, 64)
    xs = jnp.ones((64, 1), jnp.float32)
    ws = jnp.ones((1, 64), jnp.float32)
    out = ops.quant_matmul(x, w, x_scale=xs, w_scale=ws, interpret=True)
    assert out.dtype == jnp.bfloat16          # default fused out dtype
    out32 = ops.quant_matmul(x, w, interpret=True)
    assert out32.dtype == jnp.int32           # unscaled stays exact int32


@pytest.mark.parametrize("m,n,k", [(64, 96, 200), (130, 64, 96)])
def test_quant_matmul_fused_vs_oracle(m, n, k):
    x = jnp.asarray(RNG.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(k, n)), jnp.float32)
    wq = q.quantize(w, bits=8, granularity="per_channel", axis=0)
    got = ops.quant_matmul_fused(x, wq.values, wq.scale,
                                 interpret=True).astype(jnp.float32)
    want = ref.quant_dequant_matmul_ref(x, wq.values, wq.scale.reshape(1, -1))
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel < 0.02, rel


def test_quant_matmul_fused_batched_leading_dims():
    x = jnp.asarray(RNG.normal(size=(2, 3, 96)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(96, 40)), jnp.float32)
    wq = q.quantize(w, bits=8, granularity="per_channel", axis=0)
    out = ops.quant_matmul_fused(x, wq.values, wq.scale, interpret=True)
    assert out.shape == (2, 3, 40)
    flat = ops.quant_matmul_fused(x.reshape(6, 96), wq.values, wq.scale,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(out).reshape(6, 40),
                                  np.asarray(flat))


# --- dispatch-path coherence ---------------------------------------------

def test_unscaled_out_dtype_honored():
    """out_dtype without scales must cast (both fused and lut formats)."""
    x, w = _rand_i8(33, 40), _rand_i8(40, 32)
    want = np.asarray(ref.nibble_matmul_ref(x, w), np.float32)
    o = ops.quant_matmul(x, w, out_dtype=jnp.float32, interpret=True)
    assert o.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(o), want)
    ol = ops.quant_matmul(x, w, w_format="lut", out_dtype=jnp.float32,
                          interpret=True)
    assert ol.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(ol), want)


@pytest.mark.parametrize("w_format", ["int8", "lut"])
def test_scalar_scales_accepted(w_format):
    """Per-tensor (scalar) scales are part of the documented contract."""
    x, w = _rand_i8(33, 40), _rand_i8(40, 32)
    xs = jnp.asarray(RNG.uniform(0.01, 0.1, (33, 1)), jnp.float32)
    got = ops.quant_matmul(x, w, x_scale=xs, w_scale=jnp.float32(0.05),
                           w_format=w_format, out_dtype=jnp.float32,
                           interpret=True)
    want = ops.quant_matmul(x, w, interpret=True).astype(jnp.float32) \
        * xs * 0.05
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_w_format_validation():
    x, w = _rand_i8(32, 32), _rand_i8(32, 32)
    with pytest.raises(ValueError):
        ops.quant_matmul(x, w, w_format="int2")


def test_lut_format_through_dispatch():
    x, w = _rand_i8(64, 96), _rand_i8(96, 64)
    got = ops.quant_matmul(x, w, w_format="lut", interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.lut_matmul_ref(x, w)))


def test_linear_apply_pallas_matches_xla():
    """The rewired layer path: fused pallas backend vs XLA backend."""
    from repro.core.linear import linear_apply, linear_init
    params = linear_init(jax.random.PRNGKey(0), 96, 64)
    x = jnp.asarray(RNG.normal(size=(4, 96)), jnp.bfloat16)
    for mode in ("w8a8_nibble", "w4a8_nibble", "lut"):
        a = linear_apply(params, x, mode=mode, backend="pallas")
        b = linear_apply(params, x, mode=mode, backend="xla")
        assert a.dtype == x.dtype
        an = np.asarray(a, np.float32)
        bn = np.asarray(b, np.float32)
        rel = np.linalg.norm(an - bn) / (np.linalg.norm(bn) + 1e-9)
        assert rel < 0.05, (mode, rel)


# --- block choice from each call's shape ---------------------------------

def _proj_shapes(d, q_out, kv_out, ff, tp=1):
    """(K, N) of the seven projections of one layer; a tp shard cuts N of
    the column-parallel ones (q, k, v, gate, up) and K of o and down."""
    return {"q": (d, q_out // tp), "k": (d, kv_out // tp),
            "v": (d, kv_out // tp), "o": (q_out // tp, d),
            "gate": (d, ff // tp), "up": (d, ff // tp), "down": (ff // tp, d)}


# qwen3-4b: d 2560, 32 x 128 q, 8 x 128 kv, ff 9728; yi-6b: d 4096,
# 32 x 128 q, 4 x 128 kv, ff 11008
LAYERS = {"qwen3-4b": _proj_shapes(2560, 4096, 1024, 9728),
          "qwen3-4b-tp4": _proj_shapes(2560, 4096, 1024, 9728, tp=4),
          "yi-6b": _proj_shapes(4096, 4096, 512, 11008),
          "yi-6b-tp4": _proj_shapes(4096, 4096, 512, 11008, tp=4)}


def _up(x, mult):
    return -(-x // mult) * mult


@pytest.mark.parametrize("m", [1, 16, 300, 2048])
@pytest.mark.parametrize("layer", sorted(LAYERS))
def test_block_rule_at_projection_shapes(layer, m):
    """Blocks divide the padded dims, pad no dim further than 128³ blocks
    would, keep the (32, 128) int8 tiling and fit the scoped VMEM."""
    for proj, (k, n) in LAYERS[layer].items():
        bm, bn, bk = ops.pick_blocks(m, k, n, out_bytes=2)
        where = (layer, proj, m, (bm, bn, bk))
        assert bm % 32 == 0 and bn % 128 == 0 and bk % 128 == 0, where
        assert _up(n, 128) % bn == 0 and _up(k, 128) % bk == 0, where
        assert _up(m, bm) <= _up(m, 128), where
        if m > 512:
            assert _up(m, 128) % bm == 0, where
        assert ops.vmem_bytes(bm, bn, bk, out_bytes=2) <= ops.VMEM_LIMIT, \
            where


@pytest.mark.parametrize("m,most", [(16, 400), (2048, 1600)])
def test_tile_plan_grid_steps_per_layer(m, most):
    """The traced calls of one qwen3-4b layer, as core.linear makes them
    (per-tensor x scale, per-channel w scale, bf16 out), are recorded in
    ``ops.tile_plan()`` with at most ``most`` grid steps in all (128³
    blocks ran 6,160 in decode and 98,560 in a 2,048-row prefill)."""
    shapes = LAYERS["qwen3-4b"]
    for k, n in set(shapes.values()):
        jax.eval_shape(
            lambda x, w, ws: ops.quant_matmul(
                x, w, x_scale=jnp.float32(0.1), w_scale=ws,
                out_dtype=jnp.bfloat16, interpret=True),
            jax.ShapeDtypeStruct((m, k), jnp.int8),
            jax.ShapeDtypeStruct((k, n), jnp.int8),
            jax.ShapeDtypeStruct((1, n), jnp.float32))
    plan = ops.tile_plan()
    steps = sum(plan[(m, k, n, "int8")][3] for k, n in shapes.values())
    assert steps <= most, (steps, {p: plan[(m, k, n, "int8")]
                                   for p, (k, n) in shapes.items()})


def test_tile_plan_keeps_explicit_and_lut_blocks():
    x, w = _rand_i8(40, 256), _rand_i8(256, 256)
    ops.quant_matmul(x, w, bm=128, bn=128, bk=128, interpret=True)
    assert ops.tile_plan()[(40, 256, 256, "int8")] == (128, 128, 128, 4)
    ops.quant_matmul(x, w, bk=128, interpret=True)
    assert ops.tile_plan()[(40, 256, 256, "int8")] == (64, 256, 128, 2)
    ops.quant_matmul(x, w, w_format="lut", interpret=True)
    assert ops.tile_plan()[(40, 256, 256, "lut")] == (128, 128, 128, 4)


# N of 512 and 256 pick bn = N; at these M, bk is the whole K
@pytest.mark.parametrize("scaled", [False, True], ids=["int32", "bf16"])
@pytest.mark.parametrize("k,n", [(1024, 512), (768, 256)])
@pytest.mark.parametrize("m", [1, 16, 300])
def test_chosen_blocks_bit_identical_to_128(m, k, n, scaled):
    x, w = _rand_i8(m, k), _rand_i8(k, n)
    kw = {}
    if scaled:
        kw = dict(x_scale=jnp.float32(0.0123),
                  w_scale=jnp.asarray(RNG.uniform(0.01, 0.1, (1, n)),
                                      jnp.float32))
    small = ops.quant_matmul(x, w, bm=128, bn=128, bk=128, interpret=True,
                             **kw)
    chosen = ops.quant_matmul(x, w, interpret=True, **kw)
    assert ops.tile_plan()[(m, k, n, "int8")][:3] == (_up(m, 32), n, k)
    assert chosen.dtype == small.dtype
    np.testing.assert_array_equal(np.asarray(chosen, np.float32),
                                  np.asarray(small, np.float32))
