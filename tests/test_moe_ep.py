"""Expert-parallel shard_map MoE (models/moe_ep.py) == GShard-style
dispatch (models/moe.py), on 1 shard in-process and on a real 2x2 device
mesh in a subprocess (the 4-device XLA override must happen before jax
init, hence the subprocess)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.launch.mesh import make_debug_mesh
from repro.models import moe as moe_mod
from repro.models import moe_ep


def test_single_shard_equivalence(rng):
    cfg = get_config("kimi-k2-1t-a32b", smoke=True).with_(capacity_factor=8.0)
    p = moe_mod.init_moe(rng, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (2, 8, cfg.d_model))
    o1, a1 = moe_mod.moe_forward(cfg, p, x)
    o2, a2 = moe_ep.moe_forward_ep(cfg, p, x, mesh=make_debug_mesh(1, 1))
    np.testing.assert_allclose(o1, o2, rtol=2e-4, atol=2e-4)
    assert np.isclose(float(a1), float(a2))


def test_single_shard_gradients(rng):
    cfg = get_config("deepseek-v2-236b", smoke=True).with_(
        capacity_factor=8.0, num_shared_experts=0)
    p = moe_mod.init_moe(rng, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(rng, 2), (1, 8, cfg.d_model))
    mesh = make_debug_mesh(1, 1)

    g1 = jax.grad(lambda pp: moe_mod.moe_forward(cfg, pp, x)[0].sum())(p)
    g2 = jax.grad(lambda pp: moe_ep.moe_forward_ep(
        cfg, pp, x, mesh=mesh)[0].sum())(p)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-4)


SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import get_config
    from repro.models import moe as moe_mod, moe_ep
    cfg = get_config("kimi-k2-1t-a32b", smoke=True).with_(capacity_factor=8.0)
    key = jax.random.PRNGKey(0)
    p = moe_mod.init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 8, cfg.d_model))
    o1, a1 = moe_mod.moe_forward(cfg, p, x)
    # aux estimator normalizes per token-shard; groups=2 is the matching
    # gshard grouping for a 2-way expert axis
    _, a1g = moe_mod.moe_forward(cfg, p, x, groups=2)
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with mesh:
        o2, a2 = jax.jit(lambda pp, xx: moe_ep.moe_forward_ep(
            cfg, pp, xx, mesh=mesh))(p, x)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=3e-4, atol=3e-4)
    assert np.isclose(float(a1g), float(a2), rtol=1e-4), (a1, a1g, a2)
    print("EP-4DEV-OK")
""")


def test_four_device_mesh_equivalence():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", SUBPROC], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "EP-4DEV-OK" in out.stdout, out.stdout + out.stderr
