"""PR-2 planner surface: radix-4/fused/real candidates, the transform
direction key, and the schema-version bump that forces stale wisdom to
re-tune."""

import json

import numpy as np
import pytest

import jax.numpy as jnp

import repro.xfft as xfft
from repro.engines import registered_variants
from repro.plan import (
    PLAN_SCHEMA_VERSION,
    PlanCache,
    plan_fft,
    problem_key,
    resolve,
    variant_candidates,
)

PLAN_VARIANTS = registered_variants(precision="single")


def _pinned(variant, transform, x):
    with xfft.config(variant=variant):
        return transform(x)


def test_new_variants_are_first_class():
    for v in ("radix4", "fused", "fused_r4"):
        assert v in PLAN_VARIANTS


def test_variant_candidates_gating():
    # pow2 single-device 1D/2D problems sweep everything, fused included
    assert set(variant_candidates(problem_key("fft2d", (64, 64)))) == set(PLAN_VARIANTS)
    assert set(variant_candidates(problem_key("rfft1d", (4, 128), dtype="float32"))) \
        == set(PLAN_VARIANTS)
    # stream/pencil kinds and multi-device problems keep the jnp engines only
    for key in (
        problem_key("fft2d_stream", (4, 32, 32)),
        problem_key("fft2d_pencil", (64, 32), n_devices=8),
        problem_key("fft2d", (64, 64), n_devices=4),
        # a single length-2^20 row cannot tile into VMEM: no fused candidate
        problem_key("fft1d", (4, 1 << 20)),
    ):
        cands = variant_candidates(key)
        assert "fused" not in cands and "fused_r4" not in cands
        assert "radix4" in cands


def test_measure_sweeps_new_variants(rng):
    """MEASURE times radix4 and both fused kernels alongside the seed trio."""
    timings = {}
    plan = plan_fft("fft1d", (2, 64), mode="measure", cache=PlanCache(),
                    measure_iters=1, timings_out=timings)
    assert set(timings) == set(PLAN_VARIANTS)
    assert plan.variant in PLAN_VARIANTS


def test_measure_real_kind_runs_real_candidates(rng):
    timings = {}
    plan = plan_fft("rfft2d", (16, 16), dtype="float32", mode="measure",
                    cache=PlanCache(), measure_iters=1, timings_out=timings)
    assert set(timings) == set(PLAN_VARIANTS)
    assert plan.mode == "measure"
    # the winning plan really runs the real transform
    from repro.plan import execute

    x = jnp.asarray(np.random.default_rng(0).standard_normal((16, 16)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(execute(plan, x)), np.fft.rfft2(np.asarray(x)), atol=1e-3
    )


def test_inverse_direction_plans_separately():
    cache = PlanCache()
    fwd = plan_fft("fft2d", (32, 32), cache=cache)
    inv = plan_fft("fft2d", (32, 32), cache=cache, direction="inv")
    assert fwd.key.direction == "fwd" and inv.key.direction == "inv"
    assert fwd.key.cache_key() != inv.key.cache_key()
    # both live in the cache side by side
    assert cache.get(fwd.key) is fwd and cache.get(inv.key) is inv


def test_ifft2_auto_resolves_inverse_key(rng):
    """ifft2 no longer reuses the forward "fft2d" plan entry."""
    from repro.plan import default_cache

    x = (rng.standard_normal((16, 16)) + 1j * rng.standard_normal((16, 16))).astype(
        np.complex64
    )
    got = np.asarray(xfft.ifft2(jnp.asarray(x)))
    np.testing.assert_allclose(got, np.fft.ifft2(x), atol=1e-4)
    inv_key = problem_key("fft2d", (16, 16), direction="inv")
    assert default_cache().get(inv_key) is not None


@pytest.mark.parametrize("variant", ["radix4", "fused", "fused_r4"])
def test_execute_variants_numerically_exact(rng, variant):
    cache = PlanCache()
    x = (rng.standard_normal((32, 32)) + 1j * rng.standard_normal((32, 32))).astype(
        np.complex64
    )
    got = np.asarray(_pinned(variant, xfft.fft2, jnp.asarray(x)))
    ref = np.fft.fft2(x)
    scale = max(1.0, np.max(np.abs(ref)))
    np.testing.assert_allclose(got / scale, ref / scale, atol=1e-5)
    # planned rfft2 with an explicitly pinned variant matches numpy too
    xr = rng.standard_normal((32, 32)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(_pinned(variant, xfft.rfft2, jnp.asarray(xr))),
        np.fft.rfft2(xr),
        atol=1e-3,
    )
    del cache


def test_schema_bump_orphans_preexisting_wisdom(tmp_path):
    """A wisdom file tuned under the previous schema version re-tunes: its
    keys carry the old version prefix, so load() drops every entry."""
    path = str(tmp_path / "wisdom.json")
    cache = PlanCache(path=path)
    plan = plan_fft("fft2d", (64, 64), mode="measure", cache=cache, measure_iters=1)
    assert plan.mode == "measure"

    # Rewrite the file as PR-1 code would have written it (schema v1 keys).
    with open(path) as f:
        payload = json.load(f)
    prev = PLAN_SCHEMA_VERSION - 1
    payload["plan_schema_version"] = prev
    payload["plans"] = {
        f"v{prev}|" + k.split("|", 1)[1]: v for k, v in payload["plans"].items()
    }
    with open(path, "w") as f:
        json.dump(payload, f)

    stale = PlanCache(path=path)
    assert len(stale) == 0  # nothing deserialises from the old schema
    replan = plan_fft("fft2d", (64, 64), cache=stale)
    assert stale.misses >= 1  # the lookup missed -> a fresh tune happened
    assert replan.key.cache_key().startswith(f"v{PLAN_SCHEMA_VERSION}|")


def test_estimate_prefers_fused_on_tpu_keys():
    """On a TPU problem key the one-round-trip fused kernels win ESTIMATE;
    on CPU (interpret mode) they don't get the HBM credit."""
    from repro.plan import ProblemKey, estimate_plan

    tpu = ProblemKey(kind="fft2d", backend="tpu", device_kind="TPU v5 lite",
                     shape=(1024, 1024), dtype="complex64")
    cpu = ProblemKey(kind="fft2d", backend="cpu", device_kind="cpu",
                     shape=(1024, 1024), dtype="complex64")
    assert estimate_plan(tpu).variant in ("fused", "fused_r4")
    assert estimate_plan(cpu).variant not in ("fused", "fused_r4")
