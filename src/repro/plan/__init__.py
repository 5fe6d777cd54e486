"""repro.plan — FFT execution planner, autotuner, and plan cache.

The software control unit: picks the 1D schedule, streaming unroll and
pencil chunking per ``(backend, device_kind, shape, dtype, n_devices)``
problem key, FFTW-style (ESTIMATE analytically, MEASURE by timing), and
remembers the decision in a versioned JSON-backed cache.
"""

from repro.plan.api import execute, plan_fft, resolve, resolve_call
from repro.plan.autotune import (
    chunk_candidates,
    estimate_plan,
    measure_plan,
    oaconv_tile_candidates,
    variant_candidates,
)
from repro.plan.cache import PlanCache, default_cache, reset_default_cache
from repro.plan.plan import (
    DIRECTIONS,
    KINDS,
    NORMS,
    PLAN_SCHEMA_VERSION,
    PRECISIONS,
    FFTPlan,
    ProblemKey,
    problem_key,
)


__all__ = [
    "FFTPlan",
    "ProblemKey",
    "PlanCache",
    "DIRECTIONS",
    "KINDS",
    "NORMS",
    "PLAN_SCHEMA_VERSION",
    "PRECISIONS",
    "chunk_candidates",
    "default_cache",
    "estimate_plan",
    "execute",
    "measure_plan",
    "oaconv_tile_candidates",
    "plan_fft",
    "problem_key",
    "reset_default_cache",
    "resolve",
    "resolve_call",
    "variant_candidates",
]
