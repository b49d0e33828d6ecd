"""Small-mesh dry-run smoke (subprocess: forces 8 host devices so the main
test session keeps its single device). Verifies that the exact lowering path
of launch/dryrun.py works end-to-end on a (pod, data, model) mesh with
reduced configs — the production 16x16 / 2x16x16 sweep is run by
``python -m repro.launch.dryrun --all`` and recorded in EXPERIMENTS.md.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config, reduced
    from repro.launch.dryrun import collective_bytes
    from repro.launch.mesh import make_mesh
    from repro.models.params import (abstract_params, param_shardings,
                                     tp_adjusted_config)
    from repro.models.transformer import Model, cache_pspecs, cache_specs

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    results = {}
    for arch in ["qwen3-4b", "deepseek-v2-lite-16b", "zamba2-1.2b"]:
        cfg = tp_adjusted_config(reduced(get_config(arch)), 2)
        model = Model(cfg)
        params_abs = abstract_params(cfg, jnp.bfloat16)
        params_sh = param_shardings(cfg, mesh)
        B, S = 4, 64
        cache_abs = cache_specs(cfg, B, S, jnp.bfloat16)
        cache_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                cache_pspecs(cfg, mesh, B),
                                is_leaf=lambda x: isinstance(x, P))
        tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((B,), jnp.int32)
        dp = ("pod", "data")
        def fn(p, c, t, q):
            return model.decode_step(p, c, t, q)
        lowered = jax.jit(fn, in_shardings=(
            params_sh, cache_sh, NamedSharding(mesh, P(dp, None)),
            NamedSharding(mesh, P(dp)))).lower(params_abs, cache_abs, tok,
                                               pos)
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, list):   # older jax: one dict per computation
            cost = cost[0]
        coll = collective_bytes(compiled.as_text())
        results[arch] = {"flops": cost.get("flops", 0),
                         "collective_count": coll["count"]}
    print("RESULT " + json.dumps(results))
""")


@pytest.mark.slow
def test_small_mesh_multi_pod_lowering():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    # the child runs on host devices only: never on a chip its parent holds
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    results = json.loads(line[len("RESULT "):])
    assert set(results) == {"qwen3-4b", "deepseek-v2-lite-16b",
                            "zamba2-1.2b"}
    for arch, rec in results.items():
        assert rec["flops"] and rec["flops"] > 0, arch
