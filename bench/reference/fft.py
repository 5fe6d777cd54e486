"""Plain reference transforms in numpy float64, and the number compared.

Imports nothing of the program under test. The control (the reference at
bfloat16 in the program's place) is ``bench/control.py``.
"""

from __future__ import annotations

import numpy as np


def rfft2(x: np.ndarray) -> np.ndarray:
    """Real-input 2D FFT over the last two axes: (..., H, W) -> (..., H, W/2+1)."""
    return np.fft.rfft2(np.asarray(x, np.float64))


def dft_matrix(n: int, freqs: np.ndarray) -> np.ndarray:
    """(n, len(freqs)) matrix of exp(-2 pi i t f / n), with t * f reduced
    modulo n in integers so that the phase keeps float64's precision."""
    t = np.arange(n, dtype=np.int64)[:, None]
    return np.exp(-2j * np.pi * ((t * np.asarray(freqs, np.int64)) % n) / n)


def rfft2_bins(x: np.ndarray, rows: np.ndarray, cols: np.ndarray,
               chunk: int = 64) -> tuple:
    """Bins ``[rows] x [cols]`` of the 2D DFT of each real frame of ``x``
    (F, H, W), by direct DFT in float64, and each frame's spectrum rms
    (by Parseval, the frame's own L2 norm): ((F, K, L) bins, (F,) rms)."""
    f, h, w = x.shape
    ew = dft_matrix(w, cols)                  # (W, L)
    ew_ri = np.concatenate([ew.real, ew.imag], axis=1)
    eh = dft_matrix(h, rows).T                # (K, H)
    bins = np.empty((f, len(rows), len(cols)), np.complex128)
    rms = np.empty(f)
    for i in range(0, f, chunk):
        xs = np.asarray(x[i:i + chunk], np.float64)
        a = xs @ ew_ri                        # (n, H, 2L): rows' DFT at cols
        a = a[..., :len(cols)] + 1j * a[..., len(cols):]
        bins[i:i + chunk] = eh @ a
        rms[i:i + chunk] = np.sqrt(np.einsum("nhw,nhw->n", xs, xs))
    return bins, rms


def scaled_error(got: np.ndarray, ref: np.ndarray) -> float:
    """Worst frame's max |got - ref| over that frame's max |ref|; frames are
    the leading axis of both stacks. A value that is not finite reads
    ``inf``."""
    worst = 0.0
    for g, r in zip(got, ref):
        r = np.asarray(r)
        err = float(np.max(np.abs(np.asarray(g) - r)) / np.max(np.abs(r)))
        worst = max(worst, err if np.isfinite(err) else np.inf)
    return worst


def bin_error(got: np.ndarray, ref: np.ndarray, rms: np.ndarray) -> float:
    """Worst frame's max |got - ref| over its sampled bins, over that
    frame's spectrum rms. A value that is not finite reads ``inf``."""
    err = np.max(np.abs(np.asarray(got) - ref).reshape(len(ref), -1), axis=1) / rms
    return float(np.max(err)) if np.all(np.isfinite(err)) else np.inf
