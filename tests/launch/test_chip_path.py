"""What the chip path refuses, checked on the CPU: unknown TPU kinds have no
peaks, double precision has no TPU engine, the compile cache has one home,
and the chip smoke run will not run without a chip."""

import os
import pathlib
import subprocess
import sys

import pytest

from repro.plan import ProblemKey

REPO = pathlib.Path(__file__).resolve().parents[2]


def _key(**kw):
    base = dict(kind="fft2d", backend="tpu", device_kind="TPU v5 lite",
                shape=(512, 512), dtype="complex64")
    base.update(kw)
    return ProblemKey(**base)


def test_peak_table_knows_v5e_and_refuses_unknown_tpu_kinds():
    from repro.launch.roofline import chip_peaks
    from repro.plan.autotune import estimate_variant_time

    v5e = chip_peaks("TPU v5 lite")
    assert (v5e.flops_bf16, v5e.hbm_bw) == (197e12, 819e9)
    assert v5e.source
    with pytest.raises(ValueError, match="TPU v99"):
        chip_peaks("TPU v99")
    # ESTIMATE prices a TPU key against its own kind's entry, or raises.
    assert estimate_variant_time(_key(), "stockham") > 0
    with pytest.raises(ValueError, match="no published peaks"):
        estimate_variant_time(_key(device_kind="TPU v99"), "stockham")
    # Other backends keep the ranking-only scale and need no entry.
    assert estimate_variant_time(
        _key(backend="cpu", device_kind="cpu"), "stockham"
    ) > estimate_variant_time(_key(), "stockham")


def test_double_precision_on_tpu_is_the_planners_named_error():
    from repro.plan import estimate_plan
    from repro.plan.autotune import variant_candidates

    key = _key(dtype="complex128", precision="double")
    with pytest.raises(ValueError, match="no registered engine supports.*'tpu'"):
        variant_candidates(key)
    with pytest.raises(ValueError, match="no registered engine supports"):
        estimate_plan(key)
    # Off the chip the float64 reference still serves the key.
    cpu = _key(backend="cpu", device_kind="cpu", dtype="complex128",
               precision="double")
    assert variant_candidates(cpu) == ("reference_x64",)


def test_fused_engines_refuse_backends_that_neither_compile_nor_interpret():
    from repro.plan.autotune import variant_candidates

    assert "fused" in variant_candidates(_key())
    assert "fused" in variant_candidates(_key(backend="cpu", device_kind="cpu"))
    gpu = variant_candidates(_key(backend="gpu", device_kind="NVIDIA H100"))
    assert not {"fused", "fused_r4"} & set(gpu)


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, env_set):
    from repro import compile_cache

    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
        assert compile_cache.cache_dir() == tmp_path / "c"
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        first = compile_cache.cache_dir()
        assert first == REPO / ".jax_cache"
        assert compile_cache.cache_dir() == first   # same path every time
        assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_compile_cache_honours_env_and_sets_nothing(monkeypatch, tmp_path):
    import jax

    from repro import compile_cache

    target = tmp_path / "jaxcache"
    target.mkdir()
    (target / "entry").write_bytes(b"x")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(target))
    before = jax.config.jax_compilation_cache_dir
    path, held = compile_cache.enable_compile_cache()
    assert (path, held) == (str(target), True)
    assert jax.config.jax_compilation_cache_dir == before


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300,
    )
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr
    assert "platform=cpu" in out.stdout
