"""LM-facing applications of the paper's FFT engine, on the front door.

Every transform here goes through ``repro.xfft``, so each call is planned
and served through the degradation ladder like any other application;
pin an engine with ``repro.xfft.config(variant=...)``.

* ``fourier_mixing`` — FNet-style token mixing: Re(FFT2(x)) over (seq, d).
  FNet's mixing sublayer *is* a 2D Fourier transform, so the paper's
  area-efficient 2D engine drops in as the mixing layer of a trainable LM
  (``configs/fourier_lm.py``, the paper's technique as an LM architecture).
* ``fftconv`` — long convolution via the engine (Hyena-style), the opt-in
  spectral primitive offered to the SSM/hybrid archs.
* ``stft`` / ``log_mel`` — a real spectrogram frontend for the audio arch
  (the assignment mandates a stub frontend; this is the optional real one,
  and it is itself a direct application of the paper: a streamed bank of
  1D FFTs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import xfft
from repro.core.fft1d import _next_pow2
from repro.core.rfft import _is_real

__all__ = ["fourier_mixing", "fftconv", "correlate2", "stft", "log_mel"]


def fourier_mixing(x: jax.Array, variant: str = "auto") -> jax.Array:
    """FNet mixing sublayer: real part of the 2D FFT over (seq, hidden).

    x: (..., seq, d) real. Both dims must be powers of two (pad upstream).
    variant="rfft" uses the real-input specialisation (beyond-paper
    optimization, §Perf cell C): ~2× fewer FLOPs/bytes via conjugate
    symmetry; "auto" lets the planner pick the complex 2D transform's
    engine, and an engine name pins it.
    """
    if variant == "rfft":
        return fourier_mixing_rfft(x)
    with xfft.config(variant=None if variant == "auto" else variant):
        return jnp.real(xfft.fft2(x.astype(jnp.complex64))).astype(x.dtype)


def fourier_mixing_rfft(x: jax.Array) -> jax.Array:
    """Re(FFT_seq(FFT_d(x))) for real x, computing only the non-redundant
    half of the d-spectrum and mirroring the real part back:

      Re(Y)[s, k] = Re(Y)[(S−s) mod S, D−k]   for k > D/2
    """
    s, d = x.shape[-2], x.shape[-1]
    xh = xfft.rfft(x, axis=-1)                     # (..., S, D/2+1)
    yh = xfft.fft(xh, axis=-2)                     # seq-axis complex FFT
    re = jnp.real(yh)
    s_mirror = (-jnp.arange(s)) % s
    tail_k = jnp.arange(d // 2 - 1, 0, -1)         # D−k for k = D/2+1 .. D−1
    tail = jnp.take(jnp.take(re, s_mirror, axis=-2), tail_k, axis=-1)
    return jnp.concatenate([re, tail], axis=-1).astype(x.dtype)


def fftconv(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """Causal long convolution y[t] = sum_s k[s]·x[t−s] via the FFT engine.

    x: (..., seq, d); kernel: (seq_k, d) with seq_k <= seq. O(L log L) versus
    the O(L²) direct form — the spectral primitive for SSM/hybrid archs.
    Real inputs (the usual case) take the two-for-one ``rfft``/``irfft``
    path: half-size transforms over the non-redundant half spectrum.
    """
    seq = x.shape[-2]
    n = _next_pow2(2 * seq)  # zero-pad to avoid circular wrap
    xt = jnp.swapaxes(x, -1, -2)                      # (..., d, seq)
    kt = jnp.swapaxes(kernel, -1, -2)                 # (d, seq_k)
    xp = jnp.pad(xt, [(0, 0)] * (xt.ndim - 1) + [(0, n - seq)])
    kp = jnp.pad(kt, [(0, 0)] * (kt.ndim - 1) + [(0, n - kt.shape[-1])])
    if _is_real(x) and _is_real(kernel):
        y = xfft.irfft(xfft.rfft(xp) * xfft.rfft(kp))[..., :seq]
        return jnp.swapaxes(y, -1, -2).astype(x.dtype)
    y = xfft.ifft(xfft.fft(xp) * xfft.fft(kp))[..., :seq]
    return jnp.swapaxes(jnp.real(y), -1, -2).astype(x.dtype)


def correlate2(scene: jax.Array, template: jax.Array) -> jax.Array:
    """Matched-filter cross-correlation entirely in the Fourier domain:

        corr = IFFT2( FFT2(scene) · conj(FFT2(template)) )

    — the paper's correlation-pattern-recognition application. Real inputs
    (camera frames, templates) take the two-for-one ``rfft2``/``irfft2``
    path: the conjugate-symmetric half spectrum carries all the
    information, so the whole pipeline runs at half the arithmetic and
    HBM traffic of the complex transform.
    """
    if _is_real(scene) and _is_real(template):
        fs = xfft.rfft2(scene)
        ft = xfft.rfft2(template)
        return xfft.irfft2(fs * jnp.conj(ft))
    fs = xfft.fft2(jnp.asarray(scene).astype(jnp.complex64))
    ft = xfft.fft2(jnp.asarray(template).astype(jnp.complex64))
    return jnp.real(xfft.ifft2(fs * jnp.conj(ft)))


@functools.lru_cache(maxsize=8)
def _hann(n: int) -> np.ndarray:
    return (0.5 - 0.5 * np.cos(2 * np.pi * np.arange(n) / n)).astype(np.float32)


def stft(audio: jax.Array, frame: int = 512, hop: int = 256) -> jax.Array:
    """Short-time Fourier transform: (..., T) -> (..., frames, frame//2+1)."""
    t = audio.shape[-1]
    n_frames = 1 + (t - frame) // hop
    idx = np.arange(frame)[None, :] + hop * np.arange(n_frames)[:, None]
    windows = audio[..., idx] * jnp.asarray(_hann(frame))
    spec = xfft.fft(windows.astype(jnp.complex64))
    return spec[..., : frame // 2 + 1]


@functools.lru_cache(maxsize=8)
def _mel_filterbank(n_fft_bins: int, n_mels: int, sr: float = 16000.0) -> np.ndarray:
    """Triangular mel filterbank (slaney-style, simplified)."""
    def hz_to_mel(f):
        return 2595.0 * np.log10(1.0 + f / 700.0)

    def mel_to_hz(m):
        return 700.0 * (10.0 ** (m / 2595.0) - 1.0)

    mel_pts = np.linspace(hz_to_mel(0.0), hz_to_mel(sr / 2), n_mels + 2)
    hz_pts = mel_to_hz(mel_pts)
    bins = np.floor((n_fft_bins - 1) * 2 * hz_pts / sr).astype(int)
    bins = np.clip(bins, 0, n_fft_bins - 1)
    fb = np.zeros((n_mels, n_fft_bins), dtype=np.float32)
    for m in range(1, n_mels + 1):
        lo, c, hi = bins[m - 1], bins[m], bins[m + 1]
        if c > lo:
            fb[m - 1, lo:c] = (np.arange(lo, c) - lo) / (c - lo)
        if hi > c:
            fb[m - 1, c:hi] = (hi - np.arange(c, hi)) / (hi - c)
    return fb


def log_mel(
    audio: jax.Array,
    frame: int = 512,
    hop: int = 256,
    n_mels: int = 80,
) -> jax.Array:
    """Whisper-style log-mel spectrogram built on the paper's engine."""
    spec = stft(audio, frame=frame, hop=hop)
    power = jnp.abs(spec) ** 2
    fb = jnp.asarray(_mel_filterbank(frame // 2 + 1, n_mels))
    mel = jnp.einsum("...tf,mf->...tm", power, fb)
    return jnp.log10(jnp.maximum(mel, 1e-10))
