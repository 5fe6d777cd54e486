"""Quickstart: the paper's area-efficient FFT engine in five minutes.

  PYTHONPATH=src python examples/quickstart.py

All transforms go through ``repro.xfft`` — the scipy.fft-style front door
whose dispatch is plan-backed (``repro.plan`` picks the engine schedule).
Pinning a specific engine is a *scope*, not a kwarg.
"""

import jax.numpy as jnp
import numpy as np

import repro.xfft as xfft
from repro.core import butterfly_counts
from repro.core.fft2d import fft2_stream
from repro.kernels import fft2_kernel, fft_kernel, hbm_traffic_model
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    rng = np.random.default_rng(0)

    # 1. The paper's looped 1D engine (N/2 butterflies reused log2 N times),
    #    pinned via a config scope — the planner would pick a faster one.
    x = rng.standard_normal((4, 1024)).astype(np.float32)
    with xfft.config(variant="looped"):
        y = xfft.fft(jnp.asarray(x))
    ref = np.fft.fft(x)
    print("1D looped engine max err:", float(np.max(np.abs(np.asarray(y) - ref))))
    c_prop, c_trad = butterfly_counts(1024, True), butterfly_counts(1024, False)
    print(f"   butterflies: {c_prop['butterfly_units']} (proposed) vs "
          f"{c_trad['butterfly_units']} (traditional) — paper Table 2")

    # 2. 2D FFT = two 1D passes (paper fig. 1) + inverse roundtrip — no
    #    kwargs: repro.plan resolves the schedule per problem.
    img = rng.standard_normal((64, 64)).astype(np.float32)
    F = xfft.fft2(jnp.asarray(img))
    rt = np.asarray(xfft.ifft2(F)).real
    print("2D roundtrip err:", float(np.max(np.abs(rt - img))))

    # 2b. Real input gets the two-for-one path; norms are scipy-compatible.
    half = xfft.rfft2(jnp.asarray(img), norm="ortho")
    print("rfft2 ortho matches numpy:",
          bool(np.allclose(np.asarray(half), np.fft.rfft2(img, norm="ortho"),
                           atol=1e-3)))

    # 3. Streaming frames through the ping-pong pipeline (paper fig. 3)
    frames = rng.standard_normal((6, 32, 32)).astype(np.float32)
    outs = fft2_stream(jnp.asarray(frames))
    print("stream matches per-frame:",
          bool(np.allclose(np.asarray(outs), np.fft.fft2(frames), atol=1e-3)))

    # 4. The TPU kernels (interpret mode on CPU): one HBM round trip
    yk = fft_kernel(jnp.asarray(x))
    print("fused kernel max err:", float(np.max(np.abs(np.asarray(yk) - ref))))
    print(f"   HBM traffic fused/staged = "
          f"{hbm_traffic_model(4, 1024, True) / hbm_traffic_model(4, 1024, False):.3f}"
          f" (paper alpha = {1/np.log2(1024):.3f})")
    Fk = fft2_kernel(jnp.asarray(img))
    print("fused 2D kernel max err:",
          float(np.max(np.abs(np.asarray(Fk) - np.fft.fft2(img)))))

    # 5. The same kernels through the front door: force them by scope.
    with xfft.config(variant="fused_r4"):
        Fk2 = xfft.fft2(jnp.asarray(img))
    print("fused_r4 via config scope max err:",
          float(np.max(np.abs(np.asarray(Fk2) - np.fft.fft2(img)))))


if __name__ == "__main__":
    main()
