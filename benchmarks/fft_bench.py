"""Engine × realness benchmark: the hot-path matrix as one JSON report.

For each frame size N the script times 2D transforms for EVERY engine in
the ``repro.engines`` registry that can serve the problem — no hardcoded
variant list: a newly registered engine (a plugin, a new radix, a new
backend) shows up in ``BENCH_fft.json`` automatically. Each engine gets a
complex ``fft2`` cell and (when it serves ``rfft2d``) a two-for-one real
``rfft2`` cell, timed under a scoped ``xfft.config(variant=..., precision
=...)`` override; the ``reference_x64`` engine is swept at double
precision.

Each cell reports median wall time plus the *modeled* HBM traffic of the
equivalent fused kernel (``repro.kernels.ops.hbm_traffic_model``), so the
report tracks both what we measure today (CPU/interpret in CI) and what
the memory system will see on TPU. The acceptance gate of ISSUE 2 —
two-for-one real input ≥ 1.5× faster than the complex transform in the
bandwidth-lean radix-2 engine class (selected from the registry by
capability metadata, not by name) — is ``gate_speedup`` per size.

  PYTHONPATH=src python benchmarks/fft_bench.py --sizes 256,512,1024
  PYTHONPATH=src python -m benchmarks.run fft
"""

from __future__ import annotations

import argparse
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

import repro.xfft as xfft
from repro.engines import iter_engines
from repro.kernels.ops import hbm_traffic_model
from repro.plan import problem_key

try:  # python -m benchmarks.fft_bench (repo root on sys.path)
    from benchmarks.common import emit, time_fn
except ImportError:  # python benchmarks/fft_bench.py (script dir on sys.path)
    from common import emit, time_fn


def _cell(transform, variant, precision):
    """One benchmark cell: the xfft entry point under a scoped config
    override (the post-ISSUE-3 way to pin an engine — no variant kwargs)."""

    def run(x):
        with xfft.config(variant=variant, precision=precision):
            return transform(x)

    return run


def _engine_cells(n: int):
    """(label, runner, spec, real, precision) cells from the live registry:
    every engine that can serve an (n, n) frame, complex and (when it can)
    real, at EVERY precision it declares — an engine spanning both tiers
    gets a row per tier (the double row tagged ``@f64``; a single-tier
    engine keeps the bare name)."""
    cells = []
    for spec in iter_engines():
        for precision in spec.precisions:
            tag = "@f64" if precision == "double" and len(spec.precisions) > 1 \
                else ""
            if "fft2d" in spec.kinds and spec.supports(
                problem_key("fft2d", (n, n), precision=precision)
            ):
                cells.append((f"fft2/{spec.name}{tag}",
                              _cell(xfft.fft2, spec.name, precision),
                              spec, False, precision))
            if "rfft2d" in spec.kinds and spec.supports(
                problem_key("rfft2d", (n, n), dtype="float32",
                            precision=precision)
            ):
                cells.append((f"rfft2/{spec.name}{tag}",
                              _cell(xfft.rfft2, spec.name, precision),
                              spec, True, precision))
    return cells


def _gate_engine():
    """The ISSUE-2 gate engine: the bandwidth-lean radix-2 schedule —
    chosen by capability metadata (lowest traffic factor among non-fused
    single-precision radix-2 engines serving both 2D kinds), never by a
    hardcoded name. With the seed registry this resolves to ``stockham``,
    exactly the class the pre-registry gate pinned, so the criterion did
    not weaken when the sweep generalized."""
    cands = [
        s for s in iter_engines(precision="single")
        if not s.fused and s.radix == 2
        and "fft2d" in s.kinds and "rfft2d" in s.kinds
    ]
    return min(cands, key=lambda s: s.cost.traffic_factor) if cands else None


def _iters_for(n: int) -> int:
    """Fewer timing reps for big frames so the 2048 sweep stays minutes —
    but never so few that one scheduler hiccup owns the median."""
    return max(5, 12 - int(np.log2(n)))


def bench_size(n: int) -> dict:
    from repro._x64 import enable_x64

    rng = np.random.default_rng(0)
    real64 = rng.standard_normal((n, n))
    cplx64 = rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n))
    xr = jnp.asarray(real64.astype(np.float32))
    xc = jnp.asarray(cplx64.astype(np.complex64))
    iters = _iters_for(n)
    cells = {}
    for label, runner, spec, real, precision in _engine_cells(n):
        fn = jax.jit(runner)
        if precision == "double":
            # Double cells must trace and move TRUE 64-bit inputs — and
            # that only survives the jit boundary inside enable_x64.
            with enable_x64():
                arg = jnp.asarray(real64 if real else cplx64)
                us = time_fn(fn, arg, warmup=1, iters=iters)
        else:
            us = time_fn(fn, xr if real else xc, warmup=1, iters=iters)
        # Modeled HBM bytes of the equivalent fused kernel: row pass (n rows
        # of length n) + column pass, one fused round trip each; double
        # precision moves twice the bytes per element.
        width = 2 if precision == "double" else 1
        bytes_fused = (
            2 * width * hbm_traffic_model(n, n, True, radix=spec.radix, real=real)
        )
        bytes_staged = (
            2 * width * hbm_traffic_model(n, n, False, radix=spec.radix, real=real)
        )
        cells[label] = {
            "us_per_call": round(us, 2),
            "engine": spec.name,
            "backend": spec.backend,
            "radix": spec.radix,
            "precision": precision,
            "modeled_hbm_bytes_fused": bytes_fused,
            "modeled_hbm_bytes_staged": bytes_staged,
        }
        emit(f"fft_bench/{label}/{n}", us, f"fused_bytes={bytes_fused}")
    # Real-vs-complex speedup per (engine, precision) row with both cells.
    speedups = {}
    for base in sorted({label.split("/", 1)[1] for label in cells}):
        c, r = cells.get(f"fft2/{base}"), cells.get(f"rfft2/{base}")
        if c and r:
            speedups[base] = round(c["us_per_call"] / r["us_per_call"], 3)
    gate_spec = _gate_engine()
    gate = speedups.get(gate_spec.name, 0.0) if gate_spec else 0.0
    real_cell = gate_spec and cells.get(f"rfft2/{gate_spec.name}")
    complex_cell = gate_spec and cells.get(f"fft2/{gate_spec.name}")
    hbm_ratio = (
        round(real_cell["modeled_hbm_bytes_fused"]
              / complex_cell["modeled_hbm_bytes_fused"], 3)
        if real_cell and complex_cell else None
    )
    return {
        "size": n,
        "cells": cells,
        "speedup_real_vs_complex": speedups,
        "gate_engine": gate_spec.name if gate_spec else None,
        "gate_speedup": gate,
        "hbm_bytes_real_over_complex": hbm_ratio,
    }


def run() -> None:
    """benchmarks.run entry point: small sweep, report to BENCH_fft.json."""
    main(["--sizes", "256,512", "--out", "/tmp/BENCH_fft.json"])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sizes", default="256,512,1024,2048",
                    help="comma-separated frame sizes N (frames are NxN)")
    ap.add_argument("--out", default=None, help="write the JSON report here")
    args = ap.parse_args(argv)

    sizes = [int(s) for s in args.sizes.split(",") if s]
    entries = [bench_size(n) for n in sizes]
    # Gate on every size >= 1024 (the ISSUE 2 criterion); a small sweep
    # gates on its largest size so "ok" is never vacuously true.
    gated = [e for e in entries if e["size"] >= 1024] or \
        [max(entries, key=lambda e: e["size"])]
    report = {
        "backend": jax.default_backend(),
        "sizes": sizes,
        "engines_swept": [s.name for s in iter_engines()],
        "entries": entries,
        "gated_sizes": [e["size"] for e in gated],
        "ok": all(e["gate_speedup"] >= 1.5 for e in gated),
    }
    text = json.dumps(report, indent=2)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
