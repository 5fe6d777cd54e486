"""The repro.engines registry: registration API, capability filtering
and dynamic error messages."""

import dataclasses

import pytest

import repro.engines as engines
from repro.engines import CostHints, EngineSpec
from repro.plan import FFTPlan, problem_key, variant_candidates

SEED_SINGLE = ("looped", "unrolled", "stockham", "radix4", "fused", "fused_r4")


def test_builtin_engines_registered():
    names = engines.registered_variants()
    for name in SEED_SINGLE + ("reference_x64",):
        assert name in names
        assert engines.has_engine(name)
    assert engines.get_engine("radix4").radix == 4
    assert engines.get_engine("fused").fused
    assert engines.get_engine("fused_r4").radix == 4
    assert not engines.get_engine("stockham").fused
    assert engines.get_engine("looped").cost.entry_overhead_s > 0


def test_backend_families():
    assert engines.get_engine("looped").backend == "jnp"
    assert engines.get_engine("fused").backend == "pallas"
    assert engines.get_engine("reference_x64").backend == "x64"
    assert set(engines.registered_backends()) >= {"jnp", "pallas", "x64"}


def test_precision_capabilities():
    for name in SEED_SINGLE:
        assert engines.get_engine(name).precisions == ("single",)
    assert engines.get_engine("reference_x64").precisions == ("double",)


def test_register_rejects_duplicates_and_bad_specs():
    with pytest.raises(ValueError, match="unknown kind"):
        engines.register_engine(
            EngineSpec(name="toy_badkind", backend="jnp", kinds=("fft9d",))
        )
    with pytest.raises(ValueError, match="unknown precision"):
        engines.register_engine(
            EngineSpec(name="toy_badprec", backend="jnp", kinds=("fft1d",),
                       precisions=("half",))
        )
    assert not engines.has_engine("toy_badkind")
    toy = EngineSpec(name="toy_dup", backend="jnp", kinds=("fft1d",))
    engines.register_engine(toy)
    try:
        with pytest.raises(ValueError, match="already registered"):
            engines.register_engine(toy)
        # replace=True is the plugin-iteration escape hatch (non-builtins)
        engines.register_engine(
            EngineSpec(name="toy_dup", backend="jnp", kinds=("fft2d",)),
            replace=True,
        )
        assert engines.get_engine("toy_dup").kinds == ("fft2d",)
    finally:
        engines.unregister_engine("toy_dup")


def test_builtin_engines_cannot_be_replaced_or_removed():
    """Persisted wisdom, the ladder's bottom rung and the fused kernels'
    failovers all mean the six seed engines by name, so registration
    refuses to replace or remove one (register under a new name instead)."""
    spec = EngineSpec(name="stockham", backend="jnp", kinds=("fft1d",))
    with pytest.raises(ValueError, match="cannot be replaced"):
        engines.register_engine(spec)
    with pytest.raises(ValueError, match="cannot be replaced"):
        engines.register_engine(spec, replace=True)
    with pytest.raises(ValueError, match="cannot be unregistered"):
        engines.unregister_engine("fused_r4")
    assert engines.has_engine("stockham") and engines.has_engine("fused_r4")


def test_decorator_registration_and_teardown():
    @engines.engine("toy_passthrough", backend="jnp", kinds=("fft1d",),
                    cost=CostHints(traffic_factor=1.0))
    def toy_ops(kind, direction):
        return lambda x: x

    try:
        assert isinstance(toy_ops, EngineSpec)  # decorator returns the spec
        assert engines.has_engine("toy_passthrough")
        # immediately a planner candidate for its kind...
        assert "toy_passthrough" in variant_candidates(problem_key("fft1d", (4, 16)))
        # ...and absent for kinds it did not declare
        assert "toy_passthrough" not in variant_candidates(
            problem_key("fft2d", (16, 16))
        )
        # its executor is reachable through the generic apply path
        assert engines.apply_engine("toy_passthrough", "fft1d", 7) == 7
    finally:
        engines.unregister_engine("toy_passthrough")
    assert not engines.has_engine("toy_passthrough")


def test_candidates_filter_by_precision():
    assert variant_candidates(problem_key("fft2d", (32, 32), precision="double")) \
        == ("reference_x64",)
    single = variant_candidates(problem_key("fft2d", (32, 32)))
    assert "reference_x64" not in single
    assert set(single) == set(SEED_SINGLE)


def test_candidates_filter_by_backend_scope():
    key = problem_key("fft2d", (32, 32), backends=("pallas",))
    assert set(variant_candidates(key)) == {"fused", "fused_r4"}
    key = problem_key("fft2d", (32, 32), backends=("jnp",))
    assert set(variant_candidates(key)) == {"looped", "unrolled", "stockham",
                                            "radix4"}


def test_unsatisfiable_capability_errors_name_registry():
    # no double-capable engine serves the pencil kind
    key = problem_key("fft2d_pencil", (64, 32), n_devices=8, precision="double")
    with pytest.raises(ValueError, match="reference_x64"):
        variant_candidates(key)


def test_vmem_working_set_gates_fused():
    spec = engines.get_engine("fused")
    small = problem_key("fft1d", (4, 128))
    huge = problem_key("fft1d", (4, 1 << 20))
    assert spec.supports(small)
    assert not spec.supports(huge)  # no row tile fits VMEM
    from repro.kernels.ops import vmem_budget_bytes

    assert spec.working_set(small) <= vmem_budget_bytes()
    assert spec.working_set(huge) > vmem_budget_bytes()


def test_plan_validation_error_is_dynamic():
    key = problem_key("fft2d", (16, 16))
    with pytest.raises(ValueError) as ei:
        FFTPlan(key=key, variant="definitely_not_an_engine")
    # the message names the live registry, not a stale tuple
    assert "reference_x64" in str(ei.value)
    assert "registered engines" in str(ei.value)


def test_cache_keys_gain_precision_and_backend_scope():
    base = problem_key("fft2d", (64, 64))
    assert base.cache_key() != problem_key(
        "fft2d", (64, 64), precision="double"
    ).cache_key()
    assert base.cache_key() != problem_key(
        "fft2d", (64, 64), backends=("jnp",)
    ).cache_key()
    # backend scopes are canonicalized: order/duplicates never split keys
    assert problem_key("fft2d", (64, 64), backends=("pallas", "jnp")).cache_key() \
        == problem_key("fft2d", (64, 64), backends=("jnp", "pallas", "jnp")).cache_key()


def test_specs_are_frozen():
    spec = engines.get_engine("stockham")
    with pytest.raises(dataclasses.FrozenInstanceError):
        spec.name = "renamed"
