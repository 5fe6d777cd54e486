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
from repro import xfft
from repro.launch.mesh import make_mesh
from repro.core.distributed import pencil_sharding, repro_pencil_fft2

mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(7)

# sharded input, the pencil program with 1, 4 and 2 corner-turn slabs
x = rng.standard_normal((64, 32)).astype(np.float32)
xs = jax.device_put(jnp.asarray(x), pencil_sharding(mesh, "data", "rows"))
ref = np.fft.fft2(x)
scale = np.max(np.abs(ref))
for chunks in (1, 4, 2):
    y = repro_pencil_fft2(xs, mesh=mesh, axis="data", layout="rows", variant="looped",
                          chunks=chunks)
    err = np.max(np.abs(np.asarray(y) - ref)) / scale
    assert err < 1e-5, (chunks, err)
    # the output really lands column-sharded, whatever the slab count
    assert tuple(y.sharding.spec) == (None, "data"), (chunks, y.sharding.spec)

# planner integration: the front door resolves variant and chunks through
# repro.plan for the sharded grid
from repro.plan import default_cache, problem_key
y = xfft.fft2(xs)
assert np.max(np.abs(np.asarray(y) - ref)) / scale < 1e-5, "planned pencil mismatch"
assert tuple(y.sharding.spec) == (None, "data"), y.sharding.spec
plan = default_cache().get(problem_key("fft2d_pencil", (64, 32), n_devices=8,
                                       layout="rows"))
assert plan is not None and plan.variant in ("looped", "unrolled", "stockham", "radix4")
assert 32 % plan.chunks == 0 and (32 // plan.chunks) % 8 == 0, plan.chunks

# batched: leading axes replicated, rows sharded
xb = rng.standard_normal((3, 64, 64)).astype(np.float32)
xbs = jax.device_put(jnp.asarray(xb), pencil_sharding(mesh, "data", "rows", ndim=3))
gb = xfft.fft2(xbs)
assert np.max(np.abs(np.asarray(gb) - np.fft.fft2(xb))) / np.max(np.abs(np.fft.fft2(xb))) < 1e-5
assert tuple(gb.sharding.spec) == (None, None, "data"), gb.sharding.spec
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
