"""2D engine + ping-pong streaming pipeline tests."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import repro.xfft as xfft
from repro.core.fft2d import fftshift2
from repro.plan import execute, resolve


def fft2(x, variant=None):
    """The front door's fft2, on engine ``variant`` (None: the planner's)."""
    with xfft.config(variant=variant):
        return xfft.fft2(x)


def ifft2(x):
    return xfft.ifft2(x)


def fft2_stream(frames, unroll=None):
    """The planned ping-pong stream (``unroll`` pins the scan unroll)."""
    plan = resolve("fft2d_stream", frames.shape)
    if unroll is not None:
        plan = dataclasses.replace(plan, unroll=unroll)
    return execute(plan, frames)


@pytest.mark.parametrize("hw", [(8, 8), (16, 32), (64, 64)])
@pytest.mark.parametrize("variant", ["looped", "unrolled", "stockham"])
def test_fft2_matches_numpy(rng, hw, variant):
    x = rng.standard_normal((2, *hw)).astype(np.float32)
    got = np.asarray(fft2(jnp.asarray(x), variant=variant))
    ref = np.fft.fft2(x)
    scale = max(1.0, np.max(np.abs(ref)))
    np.testing.assert_allclose(got / scale, ref / scale, atol=1e-5)


def test_ifft2_roundtrip(rng):
    x = (rng.standard_normal((3, 16, 16)) + 1j * rng.standard_normal((3, 16, 16))).astype(
        np.complex64
    )
    rt = np.asarray(ifft2(fft2(jnp.asarray(x))))
    np.testing.assert_allclose(rt, x, atol=1e-4)


def test_stream_equals_per_frame(rng):
    """Ping-pong pipelined output == frame-at-a-time output (paper fig. 3/4)."""
    frames = rng.standard_normal((7, 16, 32)).astype(np.float32)
    got = np.asarray(fft2_stream(jnp.asarray(frames)))
    ref = np.fft.fft2(frames)
    scale = max(1.0, np.max(np.abs(ref)))
    np.testing.assert_allclose(got / scale, ref / scale, atol=1e-5)


def test_stream_single_frame(rng):
    frames = rng.standard_normal((1, 8, 8)).astype(np.float32)
    got = np.asarray(fft2_stream(jnp.asarray(frames)))
    np.testing.assert_allclose(got, np.fft.fft2(frames), atol=1e-4)


def test_stream_batched_frames(rng):
    frames = rng.standard_normal((4, 2, 8, 8)).astype(np.float32)
    got = np.asarray(fft2_stream(jnp.asarray(frames)))
    np.testing.assert_allclose(got, np.fft.fft2(frames), atol=1e-4)


@pytest.mark.parametrize("unroll", [2, 4])
def test_stream_unrolled_scan_matches(rng, unroll):
    """Unrolling the ping-pong scan must not change the pipeline semantics,
    including when T is not a multiple of the unroll factor."""
    frames = rng.standard_normal((7, 16, 16)).astype(np.float32)
    ref = np.fft.fft2(frames)
    scale = max(1.0, np.max(np.abs(ref)))
    got = np.asarray(fft2_stream(jnp.asarray(frames), unroll=unroll))
    np.testing.assert_allclose(got / scale, ref / scale, atol=1e-5)
    base = np.asarray(fft2_stream(jnp.asarray(frames), unroll=1))
    np.testing.assert_allclose(got, base, atol=1e-6)


def test_stream_auto_plan(rng):
    """The stream's engine and unroll resolve through repro.plan and stay exact."""
    frames = rng.standard_normal((4, 8, 8)).astype(np.float32)
    got = np.asarray(fft2_stream(jnp.asarray(frames)))
    np.testing.assert_allclose(got, np.fft.fft2(frames), atol=1e-4)

    from repro.plan import default_cache, problem_key

    plan = default_cache().get(problem_key("fft2d_stream", (4, 8, 8)))
    assert plan is not None and plan.unroll >= 1


def test_fftshift2_centers_dc(rng):
    x = jnp.ones((8, 8), jnp.float32)  # all energy in DC bin
    y = np.asarray(fftshift2(fft2(x)))
    assert np.abs(y[4, 4]) == pytest.approx(64.0, rel=1e-4)
