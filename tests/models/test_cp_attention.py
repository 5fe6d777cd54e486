"""Context-parallel attention (§Perf cell B) parity — subprocess (8 devices)."""

import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.models.attention import flash_attention, flash_attention_cp

mesh = make_mesh((4, 2), ("data", "model"))
rng = np.random.default_rng(0)
B, S, H, KV, D = 4, 64, 6, 2, 16
q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
k = jnp.asarray(rng.standard_normal((B, S, KV, D)), jnp.float32)
v = jnp.asarray(rng.standard_normal((B, S, KV, D)), jnp.float32)
with jax.set_mesh(mesh):
    for kw in ({"causal": True}, {"causal": True, "window": 24},
               {"causal": False}):
        ref = flash_attention(q, k, v, block_q=16, block_k=16, **kw)
        got = jax.jit(lambda q, k, v: flash_attention_cp(
            q, k, v, "model", block_q=16, block_k=16, **kw))(q, k, v)
        err = float(jnp.max(jnp.abs(got - ref)))
        assert err < 1e-5, (kw, err)
    # gradient parity
    g_cp = jax.jit(jax.grad(lambda q: flash_attention_cp(
        q, k, v, "model", causal=True, block_q=16, block_k=16).sum()))(q)
    g_ref = jax.grad(lambda q: flash_attention(
        q, k, v, causal=True, block_q=16, block_k=16).sum())(q)
    assert float(jnp.max(jnp.abs(g_cp - g_ref))) < 1e-4
print("CP_OK")
"""


@pytest.mark.slow
def test_cp_attention_matches_plain():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True,
        cwd=os.path.join(os.path.dirname(__file__), "..", ".."),
        env=env, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "CP_OK" in out.stdout
