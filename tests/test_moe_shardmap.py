"""shard_map MoE dispatch (EXPERIMENTS.md §Perf pair C fix): numerics match
the GSPMD reference exactly when capacity is not binding; dispatch is local
by construction. Runs in a subprocess (needs an 8-device placeholder env)."""
import json
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.configs import registry
    from repro.models import moe as moe_ref
    from repro.models.moe_shardmap import moe_mlp_shardmap
    from repro.launch import roofline as rl
    from jax.sharding import PartitionSpec as P

    cfg = registry.smoke_arch("phi3.5-moe-42b-a6.6b")
    cfg = dataclasses.replace(cfg, num_experts=8, experts_per_token=2,
                              capacity_factor=8.0, num_shared_experts=0)
    mesh = make_mesh((2, 4), ("data", "model"), devices=jax.devices())
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    d, ff, e = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    p = {"router": jax.random.normal(ks[0], (d, e)) * 0.1,
         "w_gate": jax.random.normal(ks[1], (e, d, ff)) * 0.05,
         "w_up": jax.random.normal(ks[2], (e, d, ff)) * 0.05,
         "w_down": jax.random.normal(ks[3], (e, ff, d)) * 0.05}
    x = jax.random.normal(ks[4], (64, d))
    y_ref, _ = moe_ref.moe_mlp(cfg, p, x)
    with jax.set_mesh(mesh):
        fn = jax.jit(lambda p, x: moe_mlp_shardmap(cfg, p, x, mesh))
        y_sm, _ = fn(p, x)
        coll = rl.collective_bytes(fn.lower(p, x).compile().as_text())
    err = float(jnp.max(jnp.abs(y_sm - y_ref)))
    print(json.dumps({"err": err, "coll": coll}))
""")


def test_shardmap_moe_matches_reference():
    env = dict(os.environ, PYTHONPATH="src")
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["err"] < 1e-4, out
    # collective profile is exactly weight-AG + output-psum (+ routing aux):
    # no all-to-all, no hidden-state all-reduce blowup
    coll = out["coll"]
    assert "all-to-all" not in coll or coll["all-to-all"] == 0, coll
    assert coll.get("all-gather", 0) > 0 and coll.get("all-reduce", 0) > 0
