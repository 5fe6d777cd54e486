"""Whole rows and columns of a large inverse 2D DFT in numpy float64.

The grid is read once, in row blocks, so that a grid larger than any one
chip's memory (and too large to hold in float64) fits the host. Imports
nothing of the program under test.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Iterable, Optional, Tuple

import numpy as np

from bench.reference.fft import dft_matrix


def ifft2_lines(blocks: Iterable[Tuple[int, np.ndarray]], h: int, w: int,
                rows: np.ndarray, cols: np.ndarray, step: int = 512,
                workers: Optional[int] = None) -> tuple:
    """Rows ``rows`` and columns ``cols`` of ``numpy.fft.ifft2`` of the
    (h, w) grid given as ``(first row, block)`` pairs that cover its rows
    once, in any order.

    Output row r is (1/h) ifft over W of sum_t x[t, :] e^{2 pi i r t / h}, and
    output column c is (1/w) ifft over H of x @ e^{2 pi i c u / w}: a (K x H)
    phase matrix times the row blocks, and the row blocks times a (W x L)
    one, ``step`` rows at a time on ``workers`` threads (numpy and BLAS
    release the interpreter lock). The partial sums are added in row order,
    so the result does not depend on the threads. Returns ((K, w) rows,
    (h, L) columns, the grid's L2 norm).
    """
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    e_rows = np.conj(dft_matrix(h, rows)).T          # (K, H)
    e_cols = np.conj(dft_matrix(w, cols))            # (W, L)

    def part(task):
        lo, block = task
        xb = np.asarray(block, np.complex128)
        return (lo, e_rows[:, lo:lo + len(xb)] @ xb, xb @ e_cols,
                float(np.vdot(xb, xb).real))

    tasks = [(first + i, block[i:i + step]) for first, block in blocks
             for i in range(0, block.shape[0], step)]
    acc_rows = np.zeros((len(rows), w), np.complex128)
    acc_cols = np.zeros((h, len(cols)), np.complex128)
    norm_sq = 0.0
    covered = 0
    workers = workers or min(16, os.cpu_count() or 1)
    with ThreadPoolExecutor(workers) as pool:
        for lo, part_rows, part_cols, part_sq in pool.map(part, sorted(tasks, key=lambda t: t[0])):
            acc_rows += part_rows
            acc_cols[lo:lo + len(part_cols)] = part_cols
            norm_sq += part_sq
            covered += len(part_cols)
    if covered != h:
        raise ValueError(f"the blocks cover {covered} rows of {h}")
    ref_rows = np.fft.ifft(acc_rows, axis=1) / h
    ref_cols = np.fft.ifft(acc_cols, axis=0) / w
    return ref_rows, ref_cols, float(np.sqrt(norm_sq))


def line_error(got: np.ndarray, ref: np.ndarray) -> float:
    """Worst line's max |got - ref| over that line's max |ref|; lines are
    the leading axis of both. A value that is not finite reads ``inf``."""
    err = np.max(np.abs(got - ref), axis=1) / np.max(np.abs(ref), axis=1)
    return float(np.max(err)) if np.all(np.isfinite(err)) else np.inf


def bin_error(got: np.ndarray, ref: np.ndarray, rms: float) -> float:
    """Max |got - ref| over every compared bin, over the output's rms. A
    value that is not finite reads ``inf``."""
    err = np.max(np.abs(got - ref)) / rms
    return float(err) if np.isfinite(err) else np.inf
