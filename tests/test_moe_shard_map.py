"""shard_map expert-parallel MoE == einsum MoE (no-drop capacity).

Needs multiple host devices -> subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8."""
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_arch
    from repro.launch.mesh import make_auto_mesh
    from repro.models.moe import apply_moe, init_moe
    from repro.models.moe_shard_map import apply_moe_shard_map

    cfg = get_arch("olmoe-1b-7b").reduced(d_model=64)   # E=4, top-2
    cfg = cfg.replace(num_experts=4, experts_per_token=2, d_ff=32)
    mesh = make_auto_mesh((2, 4), ("data", "model"))
    key = jax.random.key(0)
    p = init_moe(cfg, key, jnp.float32)
    B, S, d = 4, 16, cfg.d_model
    x = jax.random.normal(jax.random.key(1), (B, S, d)) * 0.5

    # reference: einsum path with no dropping (single token groups)
    y_ref, _ = apply_moe(cfg, p, x, group_size=1, capacity_factor=4.0)

    with jax.sharding.set_mesh(mesh):
        xs = jax.device_put(x, NamedSharding(mesh, P(("data",), "model", None)))
        ps = jax.tree.map(lambda v: jax.device_put(v, NamedSharding(
            mesh, P(*( ("model",) + (None,)*(v.ndim-1) if v.ndim == 3
                       else (None,)*v.ndim )))), p)
        y = jax.jit(lambda xx, pp: apply_moe_shard_map(
            cfg, pp, xx, mesh, capacity_factor=16.0))(xs, ps)
    err = float(jnp.abs(y - y_ref).max())
    print("MAXERR", err)
    assert err < 2e-4, err
""")


def test_shard_map_moe_matches_einsum():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SCRIPT],
                         capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, (out.stdout[-1000:], out.stderr[-2000:])
    assert "MAXERR" in out.stdout
