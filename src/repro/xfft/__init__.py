"""repro.xfft — the unified, scipy.fft-style front door to the engine.

One namespace, eight transforms (`fft`/`ifft`, `fft2`/`ifft2`, `rfft`/
`irfft`, `rfft2`/`irfft2`), N-D helpers (`fftn`/`ifftn` and the real-input
`rfftn`/`irfftn`), shift utilities
(`fftshift`/`ifftshift`, plus the 2D conveniences `fftshift2`/
`ifftshift2`), sample-frequency grids (`fftfreq`/`rfftfreq`),
`norm="backward"|"ortho"|"forward"` conventions and
arbitrary `axes=` — all dispatched through ``repro.plan`` over the
pluggable engine registry (``repro.engines``). `config(precision=
"double")` routes every call through an x64-capable engine (complex128
end to end); `config(backend=...)` restricts which engine backends the
planner may consider.

**The unified default.** Before this namespace existed, every entry point
carried its own ``variant=`` kwarg with *inconsistent* defaults: ``fft``/
``fft2`` defaulted to ``"looped"`` (the paper-faithful schedule — also the
slowest one XLA can emit, kept as a baseline), while ``rfft*`` defaulted
to ``"stockham"`` (the variant that happened to be fastest when PR 2
landed). Both were accidents of history, and both pushed a scheduling
decision onto every caller. The one default now is: **no per-call variant
at all — dispatch resolves through the planner** (``repro.plan``: a cached
MEASURE plan when wisdom exists, the analytic ESTIMATE model otherwise).
That is the right default because the best schedule is a property of the
*problem* (backend, shape, dtype, direction), not of the call site; it is
also the prerequisite shape for multi-backend dispatch — later PRs change
what the planner may pick without changing any signature here.

Engine selection is scoped, not threaded::

    import repro.xfft as xfft

    y = xfft.rfft2(frames)                  # plan-backed, no kwargs
    with xfft.config(variant="fused_r4"):   # force the Pallas kernel...
        y = xfft.rfft2(frames)              # ...only inside this scope
    xfft.config(mode="measure")             # tune-on-miss, process-wide

Layers, top to bottom: ``xfft`` (this front door) -> ``plan`` (resolve)
-> ``resilience.ladder`` (``run_plan``) -> ``engines`` (the registry: name
-> op) -> ``kernels`` (Pallas entry programs) and ``core`` (jnp pass
programs, 2D compositions, the pencil program).
"""

from repro.xfft._config import XFFTConfig, config, get_config
from repro.xfft._report import report, report_data
from repro.xfft._transforms import (
    fft,
    fft2,
    fftfreq,
    fftn,
    fftshift,
    fftshift2,
    ifft,
    ifft2,
    ifftn,
    ifftshift,
    ifftshift2,
    irfft,
    irfft2,
    irfftn,
    rfft,
    rfft2,
    rfftfreq,
    rfftn,
)

__all__ = [
    "fft",
    "ifft",
    "fft2",
    "ifft2",
    "fftn",
    "ifftn",
    "rfft",
    "irfft",
    "rfft2",
    "irfft2",
    "rfftn",
    "irfftn",
    "fftshift",
    "ifftshift",
    "fftshift2",
    "ifftshift2",
    "fftfreq",
    "rfftfreq",
    "config",
    "get_config",
    "report",
    "report_data",
    "XFFTConfig",
]
