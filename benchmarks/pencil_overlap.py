"""Distributed 2D FFT: plain pencil vs chunked corner-turn overlap.

The ping-pong insight applied to the collective itself (DESIGN.md §2):
slab i's all_to_all is independent of slab i−1's column FFT, so the
scheduler can overlap them. Runs in the calling process on every device
``jax.devices()`` reports (on the CPU, fake several with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before JAX starts);
reports wall-clock plus the compiled collective schedule structure, and
raises when the overlapped result disagrees with numpy.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit

#: Max relative error of the overlapped pencil FFT against numpy.fft.fft2.
REL_ERR_GATE = 1e-4

#: Frame edge of the square float32 input.
SIZE = 1024


def run():
    from repro.core.distributed import pencil_sharding, repro_pencil_fft2
    from repro.launch.mesh import make_mesh

    n_dev = len(jax.devices())
    if n_dev < 2:
        # One device has no corner turn to overlap: nothing to measure.
        raise RuntimeError(
            f"pencil overlap needs at least 2 devices, found {n_dev}; on the "
            "CPU set XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "before JAX starts"
        )
    print(f"# Distributed pencil FFT: corner-turn overlap ({n_dev} "
          f"{jax.devices()[0].platform} devices)")
    mesh = make_mesh((n_dev,), ("data",))
    rng = np.random.default_rng(0)
    x = rng.standard_normal((SIZE, SIZE)).astype(np.float32)
    xs = jax.device_put(jnp.asarray(x), pencil_sharding(mesh, "data", "rows"))

    def pencil(chunks):
        return jax.jit(lambda v: repro_pencil_fft2(
            v, mesh=mesh, axis="data", layout="rows", variant="stockham", chunks=chunks))

    plain, over = pencil(1), pencil(4)
    for name, fn in (("plain", plain), ("overlapped", over)):
        jax.block_until_ready(fn(xs))
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(xs))
            ts.append(time.perf_counter() - t0)
        hlo = fn.lower(xs).compile().as_text()
        n_a2a = sum(1 for l in hlo.splitlines() if "all-to-all" in l and "=" in l)
        emit(f"pencil_{name}", sorted(ts)[2] * 1e6, f"a2a_ops={n_a2a}")
    ref = np.fft.fft2(x)
    got = np.asarray(over(xs))
    err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
    emit("pencil_overlap_rel_err", 0.0, f"{err:.2e}")
    if not err <= REL_ERR_GATE:
        raise RuntimeError(
            f"overlapped pencil FFT off numpy by {err:.2e} (gate {REL_ERR_GATE})"
        )


if __name__ == "__main__":
    run()
