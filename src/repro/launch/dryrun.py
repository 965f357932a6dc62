import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: 512 placeholder host devices stand in for 2 pods × 256 chips,
``jax.jit(step).lower(...).compile()`` must succeed for every cell, and
the compiled artifact yields the memory/cost/collective numbers the
roofline analysis consumes.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun \
        [--arch gemma3-1b ...] [--shape train_4k ...] \
        [--multipod | --singlepod | --both] [--out results.json]
"""

import argparse       # noqa: E402
import json           # noqa: E402
import re             # noqa: E402
import time           # noqa: E402
import traceback      # noqa: E402

import jax            # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCH_NAMES, get_config  # noqa: E402
from repro.distributed.sharding import (  # noqa: E402
    batch_specs,
    cache_specs,
    opt_state_specs,
    param_specs,
)
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.shapes import (  # noqa: E402
    SHAPES,
    decode_input_structs,
    prefill_input_structs,
    skip_reason,
    train_input_structs,
)
from repro.models import decode_step, init_caches, model_init, prefill  # noqa: E402
from repro.optim import AdamWConfig, adamw_init  # noqa: E402
from repro.train import TrainConfig, make_train_step  # noqa: E402

# Post-partitioning HLO collective lines look like
#   %all-reduce.3 = f32[1024,128]{1,0} all-reduce(%x), replica_groups=...
#   %ag = (bf16[...], bf16[...]) all-gather-start(...), ...
# The output shape(s) sit between '=' and the op name; we sum their bytes.
_COLLECTIVE_LINE_RE = re.compile(
    r"=\s*(?P<shapes>\(?[^=]*?)\s*"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)"
    r"(?P<variant>-start|-done)?(?:\.\d+)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8,
                "s16": 2, "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}


def collective_bytes_from_hlo(hlo_text: str) -> dict:
    """Per-kind byte totals of every collective in the compiled HLO.

    Async pairs are counted once (the ``-done`` is skipped; ``-start``
    carries the shapes).  Bytes are the op's *output* bytes on this
    device's program — the per-device wire volume proxy the roofline
    collective term divides by link bandwidth."""
    totals: dict[str, float] = {}
    count = 0
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_LINE_RE.search(line)
        if not m or m.group("variant") == "-done":
            continue
        kind = m.group("op")
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(m.group("shapes")):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        if nbytes:
            totals[kind] = totals.get(kind, 0) + nbytes
            count += 1
    totals["n_ops"] = count
    return totals


def _tp_compatible(cfg, mesh):
    """Adjust configs whose head counts don't divide the TP axis — the
    sharding rules already fall back to sequence sharding for caches;
    parameters shard on d_model/d_ff which are 128-multiples, fine."""
    return cfg


def build_cell(cfg, case, mesh, *, quant_moments: bool):
    """Returns (fn, args, in_shardings, donate) for one dry-run cell."""

    if case.kind == "train":
        tcfg = TrainConfig(optimizer=AdamWConfig(
            quantize_moments=quant_moments))
        step = make_train_step(cfg, tcfg)
        params = jax.eval_shape(
            lambda: model_init(jax.random.PRNGKey(0), cfg))
        opt = jax.eval_shape(
            lambda: adamw_init(params, tcfg.optimizer))
        batch = train_input_structs(cfg, case)
        pspec = param_specs(params, mesh)
        ospec = opt_state_specs(opt, pspec)
        bspec = batch_specs(cfg, mesh)
        return (step, (params, opt, batch), (pspec, ospec, bspec), (0, 1))

    if case.kind == "prefill":
        def fn(params, batch):
            tokens = batch["tokens"]
            kw = {}
            if "patch_embeds" in batch:
                kw["extra_embeds"] = batch["patch_embeds"]
            if "frames" in batch:
                kw["frames"] = batch["frames"]
            logits, caches, _ = prefill(params, cfg, tokens, **kw)
            return logits, caches

        params = jax.eval_shape(
            lambda: model_init(jax.random.PRNGKey(0), cfg))
        batch = prefill_input_structs(cfg, case)
        pspec = param_specs(params, mesh)
        bspec = {k: batch_specs(cfg, mesh).get(
            k, P(tuple(a for a in mesh.axis_names if a != "model"), None))
            for k in batch}
        bspec["tokens"] = batch_specs(cfg, mesh)["tokens"]
        return (fn, (params, batch), (pspec, bspec), ())

    # decode ------------------------------------------------------------
    def fn(params, caches, token, index, enc_out=None):
        logits, new_caches = decode_step(params, cfg, token, caches,
                                         index, enc_out=enc_out)
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
        return nxt[:, None].astype(jnp.int32), new_caches

    params = jax.eval_shape(lambda: model_init(jax.random.PRNGKey(0), cfg))
    ins = decode_input_structs(cfg, case)
    pspec = param_specs(params, mesh)
    cspec = cache_specs(cfg, ins["caches"], mesh, batch=case.batch,
                        paged=False)
    dp = tuple(a for a in mesh.axis_names if a != "model")
    tok_spec = P(dp, None) if case.batch > 1 else P(None, None)
    args = [params, ins["caches"], ins["token"], ins["index"]]
    specs = [pspec, cspec, tok_spec, P()]
    if "enc_out" in ins:
        args.append(ins["enc_out"])
        specs.append(P(dp, None, None) if case.batch > 1
                     else P(None, None, None))
    return (fn, tuple(args), tuple(specs), (1,))


def _with_blocks(cfg, k: int):
    """Config with exactly k repeated blocks (layer-scan trip count k)."""
    n_fixed = len(cfg.prefix_pattern) + len(cfg.suffix_pattern)
    kw = {"n_layers": n_fixed + k * len(cfg.block_pattern)}
    if cfg.is_encdec:
        kw["n_enc_layers"] = k     # encoder scan scales in lockstep
    return cfg.replace(**kw)


def _lower_cost(cfg, case, mesh, quant_moments):
    """Compile one variant, return (flops, bytes, collective_bytes)."""
    fn, args, in_shardings, donate = build_cell(
        cfg, case, mesh, quant_moments=quant_moments)
    in_shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), in_shardings,
        is_leaf=lambda x: isinstance(x, P))
    from repro.distributed.sharding import ambient_mesh
    from repro.models.attention import unrolled_chunks
    from repro.models.transformer import unrolled_blocks
    with mesh, ambient_mesh(mesh), unrolled_chunks(), unrolled_blocks():
        compiled = jax.jit(fn, in_shardings=in_shardings,
                           donate_argnums=donate).lower(*args).compile()
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes_from_hlo(compiled.as_text())
    return (cost.get("flops", 0.0), cost.get("bytes accessed", 0.0),
            {k: v for k, v in coll.items() if k != "n_ops"})


def scan_extrapolated_cost(cfg, case, mesh, quant_moments) -> dict:
    """XLA's cost_analysis counts a while-loop body ONCE regardless of
    trip count (verified: a 10-step scan of matmuls reports 1 matmul).
    All models here scan over layer blocks, so raw numbers undercount by
    ~n_blocks×.  Fix, from the compiled artifacts themselves: compile
    1-block and 2-block variants; the difference isolates one body, and
    ``cost(n) = cost(1) + (n-1)·(cost(2) - cost(1))`` reconstructs the
    full-depth program (prefix/suffix/embed/loss are in both, counted
    once).  Collectives inside the body extrapolate identically."""
    n = cfg.n_blocks
    if n <= 1:
        f, b, c = _lower_cost(cfg, case, mesh, quant_moments)
        return {"flops_extrapolated": f, "bytes_extrapolated": b,
                "collective_bytes_extrapolated": c, "scan_trips": n}
    f1, b1, c1 = _lower_cost(_with_blocks(cfg, 1), case, mesh, quant_moments)
    f2, b2, c2 = _lower_cost(_with_blocks(cfg, 2), case, mesh, quant_moments)
    coll = {}
    for k in set(c1) | set(c2):
        v = c1.get(k, 0) + (n - 1) * (c2.get(k, 0) - c1.get(k, 0))
        coll[k] = max(0.0, v)
    return {
        "flops_extrapolated": f1 + (n - 1) * (f2 - f1),
        "bytes_extrapolated": b1 + (n - 1) * (b2 - b1),
        "collective_bytes_extrapolated": coll,
        "scan_trips": n,
    }


def run_cell(arch: str, shape: str, mesh, mesh_tag: str,
             *, verbose: bool = True) -> dict:
    cfg = get_config(arch)
    case = SHAPES[shape]
    reason = skip_reason(cfg, case)
    if reason:
        return {"arch": arch, "shape": shape, "mesh": mesh_tag,
                "status": "skip", "reason": reason}

    quant_moments = cfg.param_count() > 1e11   # 671B/400B: int8 moments
    t0 = time.time()
    try:
        fn, args, in_shardings, donate = build_cell(
            cfg, case, mesh, quant_moments=quant_moments)
        in_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), in_shardings,
            is_leaf=lambda x: isinstance(x, P))
        from repro.distributed.sharding import ambient_mesh
        with mesh, ambient_mesh(mesh):
            jitted = jax.jit(fn, in_shardings=in_shardings,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            # collectives exist only after SPMD partitioning → compiled HLO
            coll = collective_bytes_from_hlo(compiled.as_text())
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
        result = {
            "arch": arch, "shape": shape, "mesh": mesh_tag,
            "status": "ok",
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "collective_bytes": coll,
            "flops": cost.get("flops", 0.0) if cost else 0.0,
            "bytes_accessed": cost.get("bytes accessed", 0.0)
            if cost else 0.0,
            "params": cfg.param_count(),
            "params_active": cfg.param_count(active_only=True),
        }
        if mem is not None:
            for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                      "output_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes"):
                v = getattr(mem, k, None)
                if v is not None:
                    result[k] = int(v)
        if "2pod" not in mesh_tag:
            # roofline table is single-pod (per the brief): the cost-
            # extrapolation pass (2 extra unrolled compiles) runs only
            # there; the multi-pod cell is the compile/sharding proof.
            result.update(scan_extrapolated_cost(cfg, case, mesh,
                                                 quant_moments))
        if verbose:
            print(f"[ok] {arch:28s} {shape:12s} {mesh_tag:9s} "
                  f"lower={t_lower:6.1f}s compile={t_compile:6.1f}s "
                  f"flops={result['flops']:.3e}")
        return result
    except Exception as e:  # a failed cell is a bug — surface it loudly
        if verbose:
            print(f"[FAIL] {arch} {shape} {mesh_tag}: "
                  f"{type(e).__name__}: {e}")
            traceback.print_exc()
        return {"arch": arch, "shape": shape, "mesh": mesh_tag,
                "status": "fail", "error": f"{type(e).__name__}: {e}"}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", nargs="*", default=None)
    ap.add_argument("--shape", nargs="*", default=None)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--singlepod", action="store_true")
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--append", action="store_true")
    args = ap.parse_args()

    archs = args.arch or list(ARCH_NAMES)   # get_config accepts both forms
    shapes = args.shape or list(SHAPES)
    meshes = []
    if args.singlepod or not args.multipod:
        meshes.append(("1pod_16x16", make_production_mesh(multi_pod=False)))
    if args.multipod or not args.singlepod:
        meshes.append(("2pod_2x16x16", make_production_mesh(multi_pod=True)))

    results = []
    if args.append and os.path.exists(args.out):
        results = json.load(open(args.out))
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results}

    for mesh_tag, mesh in meshes:
        for arch in archs:
            for shape in shapes:
                if (arch, shape, mesh_tag) in done:
                    continue
                r = run_cell(arch, shape, mesh, mesh_tag)
                results.append(r)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skip" for r in results)
    n_fail = sum(r["status"] == "fail" for r in results)
    print(f"\ndry-run: {n_ok} ok, {n_skip} skip, {n_fail} FAIL "
          f"→ {args.out}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
