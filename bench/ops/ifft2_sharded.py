"""``repro.xfft.ifft2`` on one complex grid sharded in rows over the cell's chips.

Configuration keys: ``frame`` [H, W], ``frames_per_call`` (1: one grid per
call), ``mesh_axis`` (the name of the cell's one mesh axis), ``check_rows``
and ``check_cols`` (how many whole output rows and whole output columns of
the window's last output are compared, drawn from the seed) and ``limits``
{"max_err": ..., "bin_err": ...}.

The grid is complex standard normal (``jax.random.normal``, complex64),
made from the seed in one jitted call whose output is sharded in rows over
the chips, so each chip makes its own share. Each call transforms the whole
grid through the front door: the planner, the degradation ladder and the
sharded engine are on the timed path.

A grid this size exists only sharded. Before the first call the op
transforms a small grid sharded the same way and stops, with an error, if
the program hands back an output that is not split evenly over every chip
(a program that gathers the grid would need it whole on each chip).

The op also holds its control (:func:`control`): the plain reference, XLA's
own FFT, with its input, the spectrum between its two passes and its output
rounded to bfloat16, run where the program's call would be.
"""

from __future__ import annotations

import functools

import numpy as np

from bench import harness
from bench.reference import ifft2_lines as ref

#: Side of the small grid (per chip) the op transforms before the first
#: call, to see that the output stays split over the chips.
PROBE_PER_CHIP = 128


def work_bytes(h: int, w: int, chips: int) -> int:
    """Least HBM traffic per chip of one call: its share of the complex64
    grid read once and of the output written once."""
    return 2 * 8 * h * w // chips


def _split_evenly(y, devices, shape) -> bool:
    """True when ``y`` lies on every one of ``devices``, each holding
    exactly its 1/len(devices) share."""
    shards = y.addressable_shards
    share = int(np.prod(shape)) // len(devices)
    return ({s.device for s in shards} == set(devices) and len(shards) == len(devices)
            and all(int(np.prod(s.data.shape)) == share for s in shards))


class Workload:
    def __init__(self, ctx: harness.RunContext):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        cfg = ctx.config
        self.ctx = ctx
        self.h, self.w = cfg["frame"]
        self.frames_per_call = int(cfg["frames_per_call"])
        if self.frames_per_call != 1:
            raise SystemExit("ifft2_sharded transforms one grid per call")
        chips = len(ctx.devices)
        self.work_bytes_per_chip = work_bytes(self.h, self.w, chips)
        mesh = Mesh(np.array(ctx.devices), (cfg["mesh_axis"],))
        self.sharding = NamedSharding(mesh, P(cfg["mesh_axis"], None))
        if ctx.override is None:
            import repro.xfft as xfft

            self.transform = xfft.ifft2
        else:  # the control, or a planted fault
            self.transform = ctx.override
        self._probe(chips)
        shape = (self.h, self.w)
        self.x = jax.jit(lambda k: jax.random.normal(k, shape, jnp.complex64),
                         out_shardings=self.sharding)(harness.device_key(ctx.seed))

    def _probe(self, chips: int) -> None:
        import jax
        import jax.numpy as jnp

        n = PROBE_PER_CHIP * chips
        small = jax.device_put(jnp.ones((n, n), jnp.complex64), self.sharding)
        y = jax.block_until_ready(self.transform(small))
        if not _split_evenly(y, self.ctx.devices, (n, n)):
            raise RuntimeError(
                f"the transform of a {n}x{n} grid sharded in rows over {chips} chips "
                f"came back as {y.sharding}, not split evenly over the chips: the "
                f"{self.h}x{self.w} grid would not fit gathered; refusing to run")

    def call(self):
        return self.transform(self.x)

    def sample(self) -> tuple:
        """(output rows, output columns) compared whole, drawn from the seed."""
        cfg = self.ctx.config
        rng = harness.host_rng(self.ctx.seed, 1)
        rows = np.sort(rng.choice(self.h, min(int(cfg["check_rows"]), self.h), replace=False))
        cols = np.sort(rng.choice(self.w, min(int(cfg["check_cols"]), self.w), replace=False))
        return rows, cols

    def _input_blocks(self) -> list:
        """The input's row blocks on the host, one per chip, copied from
        the chips at once."""
        shards = self.x.addressable_shards
        for shard in shards:
            r, c = shard.index
            if (c.start or 0) != 0 or (c.stop or self.w) != self.w:
                raise ValueError(f"input shard {shard.index} does not hold whole rows")
            shard.data.copy_to_host_async()
        return [(s.index[0].start or 0, np.asarray(s.data)) for s in shards]

    def check(self, y) -> dict:
        """Compare the window's last output with the float64 reference:
        whole sampled rows (each crosses every chip's share of a
        column-split output) and whole sampled columns (each covers every
        row of one chip's share)."""
        rows, cols = self.sample()
        got_rows = np.asarray(y[rows, :])
        got_cols = np.asarray(y[:, cols]).T
        del y
        ref_rows, ref_cols, norm = ref.ifft2_lines(self._input_blocks(), self.h, self.w,
                                                   rows, cols)
        self.x = None
        ref_cols = ref_cols.T
        limits = self.ctx.config["limits"]
        err = max(ref.line_error(got_rows, ref_rows), ref.line_error(got_cols, ref_cols))
        # Parseval: the inverse transform's rms is the grid's norm over H*W.
        rms = norm / (self.h * self.w)
        bin_err = max(ref.bin_error(got_rows, ref_rows, rms),
                      ref.bin_error(got_cols, ref_cols, rms))
        return {"max_err": (err, limits["max_err"], err <= limits["max_err"]),
                "bin_err": (bin_err, limits["bin_err"], bin_err <= limits["bin_err"])}


def _round_bf16(v):
    """float32 ``v`` rounded to the nearest bfloat16 (ties to even), kept in
    float32. Done on the bits: the TPU compiler may drop a float32 ->
    bfloat16 -> float32 pair of converts as excess precision (the control
    then read as exact on the chip), and cannot drop integer arithmetic."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000), jnp.float32)


def _bf16(a):
    import jax

    return jax.lax.complex(_round_bf16(a.real), _round_bf16(a.imag))


@functools.cache
def _control_program(mesh, axis: str):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P(axis, None),
                       out_specs=P(None, axis))
    def run(block):
        rows = _bf16(jnp.fft.ifft(_bf16(block), axis=-1))
        turned = jax.lax.all_to_all(rows, axis, split_axis=1, concat_axis=0, tiled=True)
        return _bf16(jnp.fft.ifft(turned, axis=-2))

    return jax.jit(run)


def control(x):
    """The reference inverse 2D FFT at bfloat16 for a grid sharded in rows:
    XLA's FFT along the rows, one all_to_all, XLA's FFT along the columns,
    each of input, intermediate spectrum and output rounded to bfloat16."""
    sharding = x.sharding
    return _control_program(sharding.mesh, sharding.spec[0])(x)
