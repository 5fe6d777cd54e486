"""Distributed pencil FFT — runs in a subprocess with 8 fake devices so the
rest of the suite keeps seeing exactly 1 device."""

import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core.distributed import fft2_pencil, fft2_pencil_overlapped, pencil_sharding

mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(7)

# sharded input, plain + overlapped variants, batched too
x = rng.standard_normal((64, 32)).astype(np.float32)
xs = jax.device_put(jnp.asarray(x), pencil_sharding(mesh, "data", "rows"))
ref = np.fft.fft2(x)
scale = np.max(np.abs(ref))
for fn, kw in ((fft2_pencil, {}), (fft2_pencil_overlapped, {"chunks": 4}),
               (fft2_pencil_overlapped, {"chunks": 2})):
    got = np.asarray(fn(xs, mesh, **kw))
    err = np.max(np.abs(got - ref)) / scale
    assert err < 1e-5, (fn.__name__, kw, err)

# planner integration: variant/chunks resolved through repro.plan
from repro.plan import default_cache, problem_key
got = np.asarray(fft2_pencil_overlapped(xs, mesh, variant="auto", chunks="auto"))
assert np.max(np.abs(got - ref)) / scale < 1e-5, "auto pencil mismatch"
plan = default_cache().get(problem_key("fft2d_pencil", (64, 32), n_devices=8))
assert plan is not None and plan.variant in ("looped", "unrolled", "stockham", "radix4")
assert 32 % plan.chunks == 0 and (32 // plan.chunks) % 8 == 0, plan.chunks

xb = rng.standard_normal((3, 64, 64)).astype(np.float32)
gb = np.asarray(fft2_pencil(jnp.asarray(xb), mesh))
assert np.max(np.abs(gb - np.fft.fft2(xb))) / np.max(np.abs(np.fft.fft2(xb))) < 1e-5

# output really lands column-sharded for the plain variant
y = fft2_pencil(xs, mesh)
spec = y.sharding.spec
assert tuple(spec) == (None, "data"), spec
print("DISTRIBUTED_OK")
"""


@pytest.mark.slow
def test_pencil_fft_multidevice():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True,
        text=True,
        cwd=os.path.join(os.path.dirname(__file__), "..", ".."),
        env=env,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "DISTRIBUTED_OK" in out.stdout
