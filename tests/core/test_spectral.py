"""Spectral applications: FNet mixing, fftconv, STFT/log-mel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.xfft as xfft
from repro.spectral import (
    fftconv,
    fourier_mixing,
    fourier_mixing_rfft,
    log_mel,
    stft,
)


def test_fourier_mixing_matches_fnet_definition(rng):
    x = rng.standard_normal((2, 16, 32)).astype(np.float32)
    got = np.asarray(fourier_mixing(jnp.asarray(x)))
    ref = np.fft.fft2(x).real.astype(np.float32)
    np.testing.assert_allclose(got, ref, atol=1e-3)


@pytest.mark.parametrize("shape", [(1, 8, 16), (2, 32, 64), (3, 16, 128)])
def test_rfft_matches_numpy(rng, shape):
    x = rng.standard_normal(shape).astype(np.float32)
    got = np.asarray(xfft.rfft(jnp.asarray(x), axis=-1))
    ref = np.fft.rfft(x)
    scale = max(1.0, np.max(np.abs(ref)))
    np.testing.assert_allclose(got / scale, ref / scale, atol=1e-5)


@pytest.mark.parametrize("shape", [(2, 16, 32), (1, 64, 64)])
def test_rfft_mixing_matches_full(rng, shape):
    """§Perf cell C2: the real-input specialisation is exact."""
    x = rng.standard_normal(shape).astype(np.float32)
    with xfft.config(variant="stockham"):
        full = np.asarray(fourier_mixing(jnp.asarray(x)))
    half = np.asarray(fourier_mixing_rfft(jnp.asarray(x)))
    scale = max(1.0, np.max(np.abs(full)))
    np.testing.assert_allclose(half / scale, full / scale, atol=1e-5)


def test_rfft_mixing_differentiable(rng):
    x = jnp.asarray(rng.standard_normal((1, 8, 16)).astype(np.float32))
    g = jax.grad(lambda v: jnp.sum(fourier_mixing_rfft(v) ** 2))(x)
    assert bool(jnp.isfinite(g).all())


def test_fourier_mixing_differentiable(rng):
    x = jnp.asarray(rng.standard_normal((1, 8, 8)).astype(np.float32))
    g = jax.grad(lambda v: jnp.sum(fourier_mixing(v) ** 2))(x)
    assert bool(jnp.isfinite(g).all())


def test_fftconv_matches_direct(rng):
    L, D = 64, 4
    x = rng.standard_normal((2, L, D)).astype(np.float32)
    k = rng.standard_normal((L, D)).astype(np.float32)
    got = np.asarray(fftconv(jnp.asarray(x), jnp.asarray(k)))
    ref = np.zeros_like(x)
    for t in range(L):
        for s in range(t + 1):
            ref[:, t] += k[s] * x[:, t - s]
    np.testing.assert_allclose(got, ref, atol=2e-3)


def test_fftconv_short_kernel(rng):
    x = rng.standard_normal((1, 32, 2)).astype(np.float32)
    k = rng.standard_normal((4, 2)).astype(np.float32)
    got = np.asarray(fftconv(jnp.asarray(x), jnp.asarray(k)))
    ref = np.zeros_like(x)
    for t in range(32):
        for s in range(min(t + 1, 4)):
            ref[:, t] += k[s] * x[:, t - s]
    np.testing.assert_allclose(got, ref, atol=1e-3)


def test_fftconv_is_causal(rng):
    """Changing the future must not change the past."""
    x1 = rng.standard_normal((1, 32, 2)).astype(np.float32)
    x2 = x1.copy()
    x2[:, 20:] += 1.0
    k = rng.standard_normal((32, 2)).astype(np.float32)
    y1 = np.asarray(fftconv(jnp.asarray(x1), jnp.asarray(k)))
    y2 = np.asarray(fftconv(jnp.asarray(x2), jnp.asarray(k)))
    np.testing.assert_allclose(y1[:, :20], y2[:, :20], atol=1e-4)


def test_stft_pure_tone_peak():
    sr, f0 = 16000.0, 1000.0
    t = np.arange(8192) / sr
    audio = np.sin(2 * np.pi * f0 * t).astype(np.float32)
    spec = np.abs(np.asarray(stft(jnp.asarray(audio), frame=512, hop=256)))
    peak_bin = spec.mean(axis=0).argmax()
    expected = round(f0 * 512 / sr)
    assert abs(int(peak_bin) - expected) <= 1


def test_log_mel_shape_and_finite(rng):
    a = rng.standard_normal((2, 4096)).astype(np.float32)
    lm = np.asarray(log_mel(jnp.asarray(a), n_mels=80))
    assert lm.shape == (2, 15, 80)
    assert np.isfinite(lm).all()
