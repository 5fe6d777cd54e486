"""``repro.xfft.rfft2`` over a device-resident stack of real frames.

Configuration keys: ``frame`` [H, W], ``frames_per_call``, ``check_frames``
(how many whole frames of the window's last output are compared, drawn from
the seed), ``check_bins`` (K: on every frame, a K x K grid of bins drawn
from the seed is compared) and ``limits`` {"max_err": ..., "bin_err": ...}.

The input is standard normal float32, made on the chip in one jitted call
from the seed. Each call transforms the whole stack through the front door,
so the planner, the degradation ladder, the engine and its kernels are on
the timed path.
"""

from __future__ import annotations

import numpy as np

from bench import harness
from bench.reference import fft as ref_fft


def work_bytes(h: int, w: int) -> int:
    """Least HBM traffic of one real frame: one read of the input, one
    write of the half spectrum."""
    return 4 * h * w + 8 * h * (w // 2 + 1)


class Workload:
    def __init__(self, ctx: harness.RunContext):
        import jax
        import jax.numpy as jnp

        cfg = ctx.config
        self.ctx = ctx
        self.h, self.w = cfg["frame"]
        self.frames_per_call = int(cfg["frames_per_call"])
        self.work_bytes_per_chip = self.frames_per_call * work_bytes(self.h, self.w)
        shape = (self.frames_per_call, self.h, self.w)
        with jax.default_device(ctx.devices[0]):
            self.x = jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32))(
                harness.device_key(ctx.seed))
        if ctx.override is None:
            import repro.xfft as xfft

            self.transform = xfft.rfft2
        else:  # the control, or a planted fault
            self.transform = ctx.override

    def call(self):
        return self.transform(self.x)

    def sample(self) -> tuple:
        """(frames compared whole, rows and columns of the bins compared on
        every frame), drawn from the seed."""
        cfg = self.ctx.config
        rng = harness.host_rng(self.ctx.seed, 1)
        k = min(int(cfg["check_frames"]), self.frames_per_call)
        idx = np.sort(rng.choice(self.frames_per_call, k, replace=False))
        n = int(cfg["check_bins"])
        rows = np.sort(rng.choice(self.h, min(n, self.h), replace=False))
        cols = np.sort(rng.choice(self.w // 2 + 1, min(n, self.w // 2 + 1), replace=False))
        return idx, rows, cols

    def check(self, y) -> dict:
        """Compare the window's last output with the reference: sampled
        frames whole, and a grid of bins on every frame (so a frame left out
        or taken from another shows, whichever frames were sampled)."""
        idx, rows, cols = self.sample()
        got = np.asarray(y[idx])
        got_bins = np.asarray(y[:, rows[:, None], cols[None, :]])
        del y
        x = np.asarray(self.x)
        self.x = None  # the stack is freed before the reference runs
        limits = self.ctx.config["limits"]
        err = ref_fft.scaled_error(got, ref_fft.rfft2(x[idx]))
        bins, rms = ref_fft.rfft2_bins(x, rows, cols)
        bin_err = ref_fft.bin_error(got_bins, bins, rms)
        return {"max_err": (err, limits["max_err"], err <= limits["max_err"]),
                "bin_err": (bin_err, limits["bin_err"], bin_err <= limits["bin_err"])}
