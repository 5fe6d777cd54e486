"""``reference_x64`` — the first post-registry backend: real double precision.

The paper's butterfly datapath is complex64 end to end, and so are the six
seed engines. Scientific workloads (k-space reconstruction, long
correlation chains) sometimes need a float64 reference path, and ROADMAP
has carried "a real ``precision='double'`` path" since the xfft PR. This
engine is that path: ``jnp.fft`` executed under ``jax.enable_x64`` so the
whole transform — input cast, twiddles, accumulation, output — is
complex128, regardless of the process-wide x64 flag. It registers with
``precisions=("double",)`` only, so the planner proposes it exactly when a
scope asks for ``xfft.config(precision="double")`` (or builds a
double-precision :class:`~repro.plan.plan.ProblemKey` directly) and never
lets it leak into single-precision sweeps.

It is a *reference* engine: correctness first (≤1e-10 vs ``numpy.fft`` in
the conformance suite), speed second — the cost hints model it like a
bandwidth-lean library transform at double the bytes per element.
"""

from __future__ import annotations

from repro.engines.registry import CostHints, engine

_KINDS = ("fft1d", "fft2d", "fft2d_stream", "rfft1d", "rfft2d")


def _off_tpu(key) -> bool:
    """XLA:TPU has no complex128 FFT (its compiler rejects the op with
    ``Unexpected operand type for FFT: c128``), so the engine declines TPU
    keys: a double request there fails in the planner, by name, instead of
    inside dispatch."""
    return key.backend != "tpu"


@engine(
    "reference_x64",
    backend="x64",
    kinds=_KINDS,
    precisions=("double",),
    dtypes=("complex128", "float64"),
    requires_x64=True,
    predicate=_off_tpu,
    # The double ladder's always-works rung: jnp.fft under enable_x64,
    # immune to quarantine exhaustion like stockham is for single.
    reliable=True,
    cost=CostHints(traffic_factor=4.0, stage_overhead_s=0.8e-6),
)
def _reference_x64_ops(kind: str, direction: str):
    import jax.numpy as jnp
    from repro._x64 import enable_x64

    inv = direction == "inv"

    def under_x64(fn, real_in: bool = False):
        # The cast MUST happen inside the enable_x64 scope: outside it,
        # jax canonicalizes explicit 64-bit dtypes back down to 32.
        def run(x):
            with enable_x64():
                x = jnp.asarray(x)
                x = x.astype(jnp.float64 if real_in else jnp.complex128)
                return fn(x)

        return run

    if kind == "fft1d":
        return under_x64(jnp.fft.ifft if inv else jnp.fft.fft)
    if kind == "fft2d":
        return under_x64(jnp.fft.ifft2 if inv else jnp.fft.fft2)
    if kind == "rfft1d":
        if inv:
            return under_x64(jnp.fft.irfft)
        return under_x64(jnp.fft.rfft, real_in=True)
    if kind == "rfft2d":
        if inv:
            return under_x64(jnp.fft.irfft2)
        return under_x64(jnp.fft.rfft2, real_in=True)
    if kind == "fft2d_stream" and not inv:
        # Same ping-pong dataflow as repro.core.fft2d.fft2_stream (rows of
        # frame t and columns of frame t-1 in one scan step, a drain frame
        # to flush the pipe), self-contained so the whole scan — carried
        # RAM state included — lives inside enable_x64 at complex128.
        def stream(frames):
            import jax

            with enable_x64():
                frames = jnp.asarray(frames).astype(jnp.complex128)
                if frames.ndim < 3:
                    raise ValueError(
                        "fft2_stream expects (T, H, W) or (T, ..., H, W)"
                    )

                def step(ram, frame):
                    return (jnp.fft.fft(frame, axis=-1),
                            jnp.fft.fft(ram, axis=-2))

                seq = jnp.concatenate([frames, jnp.zeros_like(frames[:1])], 0)
                _, outs = jax.lax.scan(step, jnp.zeros_like(frames[0]), seq)
                return outs[1:]  # drop the pipeline-fill output

        return stream
    return None
