"""Expert-parallel MoE (shard_map + all_to_all) parity with grouped_local —
forward AND gradients (subprocess: needs 8 fake devices)."""

import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.models.config import ModelConfig, MoEConfig
from repro.models.param import init_params
from repro.models.moe import moe_skel, moe_apply

mesh = make_mesh((4, 2), ("data", "model"))
cfg_g = ModelConfig(name="t", family="moe", n_layers=1, d_model=32, n_heads=4,
                    n_kv_heads=4, d_ff=64, vocab=100,
                    moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=32,
                                  n_shared_experts=1,
                                  capacity_factor=8.0, impl="grouped_local"))

p = init_params(moe_skel(cfg_g), jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((8, 16, 32)), jnp.float32)

for ep_axes in (("data",), ("data", "model")):
    cfg_e = dataclasses.replace(cfg_g, moe=dataclasses.replace(
        cfg_g.moe, impl="ep_a2a", ep_axes=ep_axes))
    with jax.set_mesh(mesh):
        yg, _ = jax.jit(lambda p, x: moe_apply(p, x, cfg_g))(p, x)
        ye, _ = jax.jit(lambda p, x: moe_apply(p, x, cfg_e))(p, x)
        err = float(jnp.max(jnp.abs(yg - ye)))
        assert err < 1e-4, (ep_axes, err)

        def loss(p, cfg):
            y, _ = moe_apply(p, x, cfg)
            return jnp.sum(y ** 2)

        gg = jax.jit(jax.grad(lambda p: loss(p, cfg_g)))(p)
        ge = jax.jit(jax.grad(lambda p: loss(p, cfg_e)))(p)
        d = jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-9)),
            gg, ge)
        assert max(jax.tree.leaves(d)) < 1e-4, (ep_axes, d)
print("MOE_EP_OK")
"""


@pytest.mark.slow
def test_ep_a2a_matches_grouped_local():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True,
        cwd=os.path.join(os.path.dirname(__file__), "..", ".."),
        env=env, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "MOE_EP_OK" in out.stdout
