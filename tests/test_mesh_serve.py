"""Mesh-native serving acceptance: TP-sharded engines bit-match the
single-device engine, token for token, on a forced-host multi-device
CPU platform.

Runs in a subprocess because the forced device count must be set before
jax initializes (and must never leak into this process).  One process
covers all four quant×backend combos — the engine build is the
expensive part, and the contract is the same for each: greedy streams
from a tp=2 engine (weights and paged KV pools sharded over the mesh's
"model" axis) equal the tp=1 engine's streams exactly, with the
compiled-program pins unchanged and zero page leaks.

Token-for-token equality under TP is a property of the workload as well
as the code: psum changes float reduction order, so a prompt whose
logits plateau into near-ties can legitimately flip an argmax.  The
prompts here are fixed (seeded) and verified well-separated; a failure
on these seeds means sharding changed the computation, not the
arithmetic's last ulp.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np, jax
    from repro.configs import get_config, reduced
    from repro.models import model_init
    from repro.serve import Engine, ServeConfig

    cfg = reduced(get_config("qwen3-4b"))
    params = model_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, rng.integers(4, 9))
               for _ in range(4)]

    def run(quant, backend, tp):
        scfg = ServeConfig(batch=2, max_len=24, prefill_len=8,
                           decode_chunk=4, quant_mode=quant,
                           quant_backend=backend, page_size=4,
                           alloc_mode="incremental", num_pages=10, tp=tp)
        eng = Engine(cfg, params, scfg)
        ids = [eng.submit(p, 8) for p in prompts]
        done = eng.run()
        return ([done[i].tokens for i in ids], dict(eng.compile_counts),
                eng.leaked_pages(), list(eng.mesh_shape),
                eng.device_count)

    out = {}
    for quant, backend in [("dense", "xla"), ("dense", "pallas"),
                           ("w8a8_nibble", "xla"),
                           ("w8a8_nibble", "pallas")]:
        s1, c1, l1, m1, d1 = run(quant, backend, 1)
        s2, c2, l2, m2, d2 = run(quant, backend, 2)
        out[f"{quant}/{backend}"] = {
            "match": s1 == s2, "counts1": c1, "counts2": c2,
            "leaks": l1 + l2, "mesh2": m2, "devices2": d2}
    print(json.dumps(out))
""")


@pytest.mark.slow
def test_tp2_engine_bitmatches_single_device_all_combos():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stderr[-3000:]
    results = json.loads(out.stdout.strip().splitlines()[-1])
    pins = {"prefill": 1, "decode_chunk": 1}
    assert set(results) == {"dense/xla", "dense/pallas",
                            "w8a8_nibble/xla", "w8a8_nibble/pallas"}
    for combo, r in results.items():
        assert r["match"], f"{combo}: tp=2 streams diverge from tp=1"
        assert r["counts1"] == pins, (combo, r["counts1"])
        assert r["counts2"] == pins, (combo, r["counts2"])
        assert r["leaks"] == 0, (combo, r["leaks"])
        assert r["mesh2"] == [1, 2], (combo, r["mesh2"])
        assert r["devices2"] == 2, (combo, r["devices2"])


_DP_CHILD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import numpy as np, jax
    from repro.configs import get_config, reduced
    from repro.models import model_init
    from repro.serve import Engine, Router, ServeConfig

    cfg = reduced(get_config("qwen3-4b"))
    params = model_init(jax.random.PRNGKey(0), cfg)
    scfg = ServeConfig(batch=2, max_len=24, prefill_len=8, decode_chunk=4,
                       page_size=4)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, rng.integers(4, 9))
               for _ in range(8)]

    def streams(eng):
        ids = [eng.submit(p, 6) for p in prompts]
        done = eng.run()
        return [done[i].tokens for i in ids]

    ref = streams(Engine(cfg, params, scfg))
    router = Router(cfg, params, scfg, replicas=4)
    got = streams(router)
    param_devs = [sorted(str(d) for leaf in jax.tree_util.tree_leaves(
                      eng.params) for d in leaf.devices())
                  for eng in router.replicas]
    cache_devs = [sorted({str(d) for leaf in jax.tree_util.tree_leaves(
                      eng._caches) for d in leaf.devices()})
                  for eng in router.replicas]
    print(json.dumps({
        "param_devs": [sorted(set(p)) for p in param_devs],
        "cache_devs": cache_devs,
        "device_count": router.device_count,
        "placements": router.placements,
        "match": got == ref,
        "leaks": router.leaked_pages()}))
""")


def test_dp4_router_places_replicas_on_distinct_devices():
    """dp=4 tp=1 on four devices: every replica commits its params and
    caches to its own device, the fleet reports four devices, and dense
    greedy streams still equal one engine's."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", _DP_CHILD], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert all(len(d) == 1 for d in r["param_devs"]), r["param_devs"]
    assert r["cache_devs"] == r["param_devs"], r
    assert len({d[0] for d in r["param_devs"]}) == 4, r["param_devs"]
    assert r["device_count"] == 4
    assert all(p > 0 for p in r["placements"]), r["placements"]
    assert r["match"], "dp=4 streams diverge from the single engine"
    assert r["leaks"] == 0
