"""Planner API: ESTIMATE/MEASURE plans, planned calls' numerical
equivalence to the float64 DFT oracle, and execute() dispatch."""

import numpy as np
import pytest

import jax.numpy as jnp

import repro.xfft as xfft
from repro.core.fft2d import fft2_stream
from repro.engines import registered_variants
from repro.plan import (
    PlanCache,
    chunk_candidates,
    execute,
    plan_fft,
    problem_key,
    resolve,
)


def _pinned(variant, transform, x):
    with xfft.config(variant=variant):
        return transform(x)


def _dft_oracle(x, axes):
    """Float64 DFT reference (np.fft over complex128)."""
    return np.fft.fftn(np.asarray(x, np.complex128), axes=axes)


@pytest.fixture
def crand(rng):
    def make(shape):
        return (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(
            np.complex64
        )

    return make


def test_estimate_plan_is_concrete_and_deterministic():
    cache = PlanCache()
    p1 = plan_fft("fft2d", (64, 64), cache=cache)
    p2 = plan_fft("fft2d", (64, 64), cache=cache)
    assert p1.variant in registered_variants(precision="single")
    assert p1 is p2  # second call is a cache hit, not a re-plan
    assert cache.hits >= 1


def test_estimate_crossover_small_vs_large():
    """The analytic model prefers low-overhead schedules at small N and
    bandwidth-lean schedules at large N (matches MEASURE on CPU). Within
    the radix-2 trio that is the seed's unrolled->stockham crossover; the
    radix-4 family (half the passes and overheads) may better either end
    but the losing schedules must stay losers."""
    from repro.plan.autotune import estimate_variant_time

    cache = PlanCache()
    small = plan_fft("fft1d", (4, 16), cache=cache)
    large = plan_fft("fft1d", (4, 4096), cache=cache)
    ks = problem_key("fft1d", (4, 16))
    kl = problem_key("fft1d", (4, 4096))
    # seed crossover, preserved within the radix-2 schedules
    assert estimate_variant_time(ks, "unrolled") < estimate_variant_time(ks, "stockham")
    assert estimate_variant_time(kl, "stockham") < estimate_variant_time(kl, "unrolled")
    # winners are overhead-lean (small) / bandwidth-lean (large)
    assert small.variant in ("unrolled", "radix4")
    assert large.variant in ("stockham", "radix4", "fused_r4")
    assert small.variant != "looped" and large.variant != "looped"


def test_fft1d_auto_matches_float64_oracle(crand):
    x = crand((3, 128))
    got = np.asarray(xfft.fft(jnp.asarray(x)))
    ref = _dft_oracle(x, axes=(-1,))
    scale = max(1.0, np.max(np.abs(ref)))
    np.testing.assert_allclose(got / scale, ref / scale, atol=5e-6)


def test_fft2_auto_matches_float64_oracle(crand):
    x = crand((2, 32, 64))
    got = np.asarray(xfft.fft2(jnp.asarray(x)))
    ref = _dft_oracle(x, axes=(-2, -1))
    scale = max(1.0, np.max(np.abs(ref)))
    np.testing.assert_allclose(got / scale, ref / scale, atol=1e-5)


def test_fft2_stream_auto_matches_float64_oracle(crand):
    frames = crand((5, 16, 32))
    got = np.asarray(execute(resolve("fft2d_stream", frames.shape), jnp.asarray(frames)))
    ref = _dft_oracle(frames, axes=(-2, -1))
    scale = max(1.0, np.max(np.abs(ref)))
    np.testing.assert_allclose(got / scale, ref / scale, atol=1e-5)


def test_measure_plan_beats_nothing_but_is_concrete(crand):
    """MEASURE on a small problem: timings for every candidate, winner
    concrete, measured time recorded, and the plan replaces the ESTIMATE
    entry in the cache."""
    cache = PlanCache()
    est = plan_fft("fft1d", (2, 64), cache=cache)
    timings = {}
    plan = plan_fft(
        "fft1d", (2, 64), mode="measure", cache=cache, measure_iters=2,
        timings_out=timings,
    )
    assert set(timings) == set(registered_variants(precision="single"))
    assert plan.mode == "measure" and plan.measured_us is not None
    assert plan.measured_us == pytest.approx(min(timings.values()))
    assert cache.get(plan.key).mode == "measure"  # MEASURE displaced ESTIMATE
    assert est.key == plan.key
    # a later measure call hits the cache instead of re-timing
    again = plan_fft("fft1d", (2, 64), mode="measure", cache=cache)
    assert again is cache.get(plan.key)


def test_resolve_uses_cached_measure_plan():
    cache = PlanCache()
    measured = plan_fft("fft2d", (16, 16), mode="measure", cache=cache,
                        measure_iters=1)
    hit = resolve("fft2d", (16, 16), cache=cache)
    assert hit is cache.get(measured.key)
    assert hit.mode == "measure"


def test_execute_dispatch_matches_direct_calls(crand):
    cache = PlanCache()
    x2 = crand((32, 32))
    p2 = plan_fft("fft2d", (32, 32), cache=cache)
    np.testing.assert_array_equal(
        np.asarray(execute(p2, jnp.asarray(x2))),
        np.asarray(_pinned(p2.variant, xfft.fft2, jnp.asarray(x2))),
    )
    frames = crand((3, 16, 16))
    ps = plan_fft("fft2d_stream", (3, 16, 16), cache=cache)
    np.testing.assert_array_equal(
        np.asarray(execute(ps, jnp.asarray(frames))),
        np.asarray(
            fft2_stream(jnp.asarray(frames), variant=ps.variant, unroll=ps.unroll)
        ),
    )
    pp = plan_fft("fft2d_pencil", (64, 32), n_devices=8, cache=cache)
    with pytest.raises(ValueError):
        execute(pp, jnp.zeros((64, 32)))  # pencil plans need a mesh


def test_pencil_chunks_are_legal_divisors():
    for w, d in ((32, 8), (64, 4), (128, 8), (96, 4)):
        cands = chunk_candidates(w, d)
        assert cands, (w, d)
        for c in cands:
            assert w % c == 0 and (w // c) % d == 0
        plan = plan_fft("fft2d_pencil", (64, w), n_devices=d, cache=PlanCache())
        assert plan.chunks in cands


def test_measure_rejects_pencil_without_mesh():
    # MEASURE can't time a collective without devices; pencil falls back to
    # the analytic model rather than raising.
    plan = plan_fft("fft2d_pencil", (64, 32), n_devices=8, mode="measure",
                    cache=PlanCache())
    assert plan.mode == "estimate"


def test_plan_fft_autosaves_file_backed_cache(tmp_path):
    path = str(tmp_path / "wisdom.json")
    cache = PlanCache(path=path)
    plan_fft("fft2d", (32, 32), cache=cache)
    # a brand-new cache (fresh process analogue) re-tunes nothing
    fresh = PlanCache(path=path)
    assert fresh.get(problem_key("fft2d", (32, 32))) is not None
    assert fresh.hits == 1 and fresh.misses == 0


def test_bad_inputs():
    with pytest.raises(ValueError):
        plan_fft("fft3d", (8, 8, 8))
    with pytest.raises(ValueError):
        plan_fft("fft2d", (8, 8), mode="exhaustive")
