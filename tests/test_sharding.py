"""Sharding-rule unit tests + an end-to-end mini dry-run in a subprocess
(subprocess so XLA_FLAGS device-count fakery never leaks into this
process — smoke tests must see 1 device)."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed.sharding import _fit_spec, _param_rule

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeMesh:
    axis_names = ("data", "model")
    shape = {"data": 4, "model": 2}


class FakeLeaf:
    def __init__(self, *shape):
        self.shape = shape
        self.ndim = len(shape)


def test_param_rules_col_row():
    fsdp = ("data",)
    assert _param_rule(("wq", "w"), FakeLeaf(64, 32), fsdp) \
        == P(("data",), "model")
    assert _param_rule(("wo", "w"), FakeLeaf(32, 64), fsdp) \
        == P("model", ("data",))
    assert _param_rule(("blocks", "0", "mlp", "gate", "w"),
                       FakeLeaf(4, 64, 128), fsdp) \
        == P(None, ("data",), "model")     # stacked: leading block axis


def test_param_rules_embed_and_experts():
    fsdp = ("data",)
    assert _param_rule(("embed", "emb"), FakeLeaf(1000, 64), fsdp) \
        == P("model", None)
    assert _param_rule(("moe", "w_gate"), FakeLeaf(8, 64, 128), fsdp) \
        == P("model", ("data",), None)
    assert _param_rule(("moe", "w_down"), FakeLeaf(8, 128, 64), fsdp) \
        == P("model", None, ("data",))
    assert _param_rule(("norm", "scale"), FakeLeaf(64), fsdp) == P(None)


def test_fit_spec_drops_nondivisible():
    mesh = FakeMesh()
    # 50280 % 2 == 0 → keeps; 50281 % 2 → drops
    assert _fit_spec(P("model", None), (50280, 64), mesh) \
        == P("model", None)
    assert _fit_spec(P("model", None), (50281, 64), mesh) == P(None, None)
    # batch=1 over data axis is dropped
    assert _fit_spec(P(("data",), None, "model", None),
                     (1, 128, 2, 16), mesh) == P(None, None, "model", None)


def test_fit_spec_tuple_axes():
    mesh = FakeMesh()
    # ("data","model") product = 8; 64 % 8 == 0 keeps, 12 % 8 drops
    assert _fit_spec(P(("data", "model"),), (64,), mesh) \
        == P(("data", "model"))
    assert _fit_spec(P(("data", "model"),), (12,), mesh) == P(None)


@pytest.mark.slow
def test_mini_dryrun_subprocess():
    """Lower+compile one reduced arch on a fake 8-device (4,2) mesh, with
    the real sharding rules, in a clean subprocess."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json, jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config, reduced
        from repro.distributed.sharding import (ambient_mesh, batch_specs,
            opt_state_specs, param_specs)
        from repro.launch.mesh import make_local_mesh
        from repro.models import model_init
        from repro.optim import AdamWConfig, adamw_init
        from repro.train import TrainConfig, make_train_step

        mesh = make_local_mesh(dp=4, tp=2)
        cfg = reduced(get_config("qwen3-4b")).replace(
            d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, vocab_size=256)
        tcfg = TrainConfig()
        step = make_train_step(cfg, tcfg)
        params = jax.eval_shape(lambda: model_init(jax.random.PRNGKey(0), cfg))
        opt = jax.eval_shape(lambda: adamw_init(params, tcfg.optimizer))
        batch = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
        specs = (param_specs(params, mesh),
                 opt_state_specs(adamw_init(params, tcfg.optimizer) if 0 else opt,
                                 param_specs(params, mesh)),
                 batch_specs(cfg, mesh))
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        with mesh, ambient_mesh(mesh):
            compiled = jax.jit(step, in_shardings=shardings) \\
                .lower(params, opt, batch).compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, list):      # older jax: one dict per computation
            cost = cost[0] if cost else {}
        print(json.dumps({"ok": True, "flops": cost.get("flops", 0.0)}))
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["ok"]


def test_paged_kv_pool_shards_kv_heads_when_divisible():
    """(num_pages, page_size, KVH, D) pools put KV heads on the model
    axis when they divide it; the page-id axis is never sharded."""
    from types import SimpleNamespace

    from repro.distributed.sharding import cache_specs
    cfg = SimpleNamespace(cache_mode="paged", n_kv_heads=2)
    caches = {"k": FakeLeaf(10, 4, 2, 16), "v": FakeLeaf(10, 4, 2, 16)}
    specs = cache_specs(cfg, caches, FakeMesh(), batch=2)
    assert specs["k"] == P(None, None, "model", None)
    assert specs["v"] == P(None, None, "model", None)


def test_paged_kv_pool_falls_back_to_page_sequence_axis():
    """KV heads that don't divide the model axis (GQA reduced to odd
    head counts) shard the in-page sequence axis instead — and when
    page_size doesn't divide either, the pool stays replicated rather
    than letting GSPMD reject the program."""
    from types import SimpleNamespace

    from repro.distributed.sharding import cache_specs
    cfg = SimpleNamespace(cache_mode="paged", n_kv_heads=3)
    specs = cache_specs(cfg, {"k": FakeLeaf(10, 4, 3, 16)}, FakeMesh(),
                        batch=2)
    assert specs["k"] == P(None, "model", None, None)
    specs = cache_specs(cfg, {"k": FakeLeaf(10, 5, 3, 16)}, FakeMesh(),
                        batch=2)
    assert specs["k"] == P(None, None, None, None)


def test_paged_scale_pools_follow_the_kv_rule():
    """int8 quant scale pools (..., 1) shard exactly like their KV
    pools — a shard must hold the scales for the rows it owns."""
    from types import SimpleNamespace

    from repro.distributed.sharding import cache_specs
    cfg = SimpleNamespace(cache_mode="paged", n_kv_heads=2)
    specs = cache_specs(cfg, {"k_scale": FakeLeaf(10, 4, 2, 1)},
                        FakeMesh(), batch=2)
    assert specs["k_scale"] == P(None, None, "model", None)


def test_paged_mla_and_stacked_and_mamba_rules():
    """MLA latent pools (num_pages, page_size, rank) have no head axis
    — the in-page sequence axis shards; a stacked (blocks-leading)
    pool gets a leading None; mamba state keeps its per-slot dense
    rule even in paged mode."""
    from types import SimpleNamespace

    from repro.distributed.sharding import cache_specs
    cfg = SimpleNamespace(cache_mode="paged", n_kv_heads=2)
    specs = cache_specs(
        cfg, {"c_kv": FakeLeaf(10, 4, 8),
              "blocks": {"k": FakeLeaf(3, 10, 4, 2, 16)},
              "conv": FakeLeaf(4, 3, 8)},
        FakeMesh(), batch=2)
    assert specs["c_kv"] == P(None, "model", None)
    assert specs["blocks"]["k"] == P(None, None, None, "model", None)
    assert specs["conv"] == P(("data",), None, "model")


def test_page_table_spec_is_replicated():
    from repro.distributed.sharding import page_table_spec
    assert page_table_spec(FakeMesh()) == P(None, None)


def test_make_local_mesh_sizing_and_validation():
    """tp/dp sizing on the single local device: tp=1 works (and the
    no-argument call keeps the (n, 1) shape), anything needing more
    devices than exist raises with the sizes in the message."""
    from repro.launch.mesh import make_local_mesh
    n = len(jax.devices())
    mesh = make_local_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.shape == {"data": n, "model": 1}
    mesh = make_local_mesh(dp=1, tp=1)
    assert mesh.shape == {"data": 1, "model": 1}
    with pytest.raises(ValueError, match="tp must be >= 1"):
        make_local_mesh(tp=0)
    with pytest.raises(ValueError, match="dp must be >= 0"):
        make_local_mesh(dp=-1)
    with pytest.raises(ValueError, match=f"does not divide the {n}"):
        make_local_mesh(tp=2 * n)
    with pytest.raises(ValueError, match="needs"):
        make_local_mesh(dp=n, tp=2)


def test_maybe_shard_noop_without_mesh():
    """No ambient mesh → constraints are identity (unit-test safety)."""
    import jax.numpy as jnp

    from repro.distributed.sharding import maybe_shard
    x = jnp.ones((4, 8, 16))
    y = maybe_shard(x, "activation")
    assert y is x
