"""End-to-end driver: a streaming 2D-FFT *service* — the paper's processor
as a deployable system. Batched frame requests flow through the ping-pong
pipeline continuously (RAM1/RAM2 never idle), with checkpointed stream
offsets so a killed worker resumes mid-stream.

  PYTHONPATH=src python examples/serve_fft2d.py --frames 64 --hw 128
"""

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.xfft as xfft
from repro.plan import default_cache, execute, plan_fft
from repro.compile_cache import enable_compile_cache


def frame_source(step: int, batch: int, hw: int, seed: int = 0) -> np.ndarray:
    """Deterministic synthetic camera: frame t is a drifting 2-D chirp."""
    rng = np.random.default_rng(seed ^ step)
    yy, xx = np.mgrid[0:hw, 0:hw].astype(np.float32) / hw
    base = np.sin(2 * np.pi * (3 + step % 5) * xx) * np.cos(2 * np.pi * 2 * yy)
    noise = rng.standard_normal((batch, hw, hw)).astype(np.float32) * 0.1
    return base[None] + noise


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=64, help="total frames to serve")
    ap.add_argument("--batch", type=int, default=8, help="frames per request")
    ap.add_argument("--hw", type=int, default=128)
    ap.add_argument("--state", default="/tmp/fft2d_service_state.json")
    ap.add_argument("--reset", action="store_true")
    ap.add_argument(
        "--plan-mode",
        choices=["estimate", "measure"],
        default="measure",
        help="autotune mode used to warm the plan cache at startup",
    )
    args = ap.parse_args()

    # Warm the plan cache before serving: tune once for the request shape,
    # then serve every batch on that plan (engine and scan unroll).
    t_plan = time.time()
    plan = plan_fft(
        "fft2d_stream", (args.batch, args.hw, args.hw), mode=args.plan_mode
    )
    print(
        f"[service] plan ({plan.mode}, {time.time() - t_plan:.2f}s): "
        f"variant={plan.variant} unroll={plan.unroll} "
        f"cache={default_cache().path or 'memory'}"
    )

    # resume support: the service remembers which frame it served last
    start = 0
    if not args.reset and os.path.exists(args.state):
        with open(args.state) as f:
            start = json.load(f)["next_frame"]
        print(f"[service] resuming at frame {start}")

    pipeline = jax.jit(lambda f: execute(plan, f))
    served = 0
    t0 = time.time()
    checks = []
    for step in range(start, args.frames, args.batch):
        frames = frame_source(step, args.batch, args.hw)
        spectra = np.asarray(pipeline(jnp.asarray(frames)))
        # response: dominant spatial frequency per frame (the "detection")
        mags = np.abs(spectra.reshape(args.batch, -1))
        mags[:, 0] = 0  # ignore DC
        peaks = mags.argmax(axis=1)
        checks.append(int(peaks[0]))
        served += args.batch
        # checkpoint the stream offset (atomic rename)
        tmp = args.state + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"next_frame": step + args.batch}, f)
        os.replace(tmp, args.state)
    dt = time.time() - t0
    print(f"[service] served {served} frames of {args.hw}x{args.hw} in {dt:.2f}s "
          f"({served/max(dt,1e-9):.1f} frames/s)")
    print(f"[service] sample peak bins: {checks[:6]}")
    # verify one batch against numpy and against the xfft front door
    # (whose bare call resolves through the same warmed plan cache)
    frames = frame_source(start, args.batch, args.hw)
    ref = np.fft.fft2(frames)
    got = np.asarray(pipeline(jnp.asarray(frames)))
    err = np.max(np.abs(got - ref)) / np.max(np.abs(ref))
    print(f"[service] spectrum rel. error vs numpy: {err:.2e}")
    direct = np.asarray(xfft.fft2(jnp.asarray(frames)))
    agree = np.max(np.abs(got - direct)) / np.max(np.abs(ref))
    print(f"[service] stream vs xfft.fft2 rel. diff: {agree:.2e}")


if __name__ == "__main__":
    main()
