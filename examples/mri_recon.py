"""End-to-end MRI reconstruction on the planned FFT stack.

The PR-10 workload, in ~60 lines:

1. **acquire** — undersample the Shepp-Logan phantom's multi-coil
   k-space with a seeded variable-density Cartesian mask (R≈2) and
   estimate coil sensitivities from the data's own calibration block
   (ESPIRiT-lite) — no ground-truth maps anywhere downstream;
2. **warm start** — load the packaged wisdom artifact so the service's
   CG transforms resolve MEASURE-grade plans with zero tuning cost;
3. **reconstruct** — submit :class:`repro.serve.ReconRequest`s to the
   ``ImagingService`` recon lane: the queue coalesces into ONE batched
   CG-SENSE solve (tens of planned centered transforms over two
   problem keys, all plan-cache hits after the first);
4. **introspect** — NRMSE vs the phantom for zero-filled and CG, then
   ``xfft.report()``: the plan table, counters and the recon lane's
   latency histogram, straight from the flight recorder.

  PYTHONPATH=src python examples/mri_recon.py --size 64 --requests 4
"""

import argparse

import numpy as np

import repro.xfft as xfft
from repro import mri
from repro.plan import PlanCache
from repro.serve import ImagingService, ReconRequest, wisdom
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=64, help="frame size (pow2)")
    ap.add_argument("--coils", type=int, default=4)
    ap.add_argument("--accel", type=int, default=2)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--requests", type=int, default=4)
    args = ap.parse_args()

    # 1. the acquisition: phantom -> coil k-space -> undersample -> maps
    phantom = np.asarray(mri.shepp_logan(args.size))
    truth_maps = mri.birdcage_maps(args.coils, args.size)
    mask = mri.variable_density_mask(
        (args.size, args.size), args.accel, seed=1
    )
    kspace = np.asarray(mri.sense_forward(phantom, truth_maps, mask))
    smaps = np.asarray(mri.estimate_sensitivities(kspace, calib=16, mask=mask))
    print(f"acquired {args.coils}-coil k-space at "
          f"R={mri.acceleration(mask):.2f} "
          f"({args.size}x{args.size}, maps estimated from calibration)")

    # 2. warm-started serving: MEASURE-grade plans, zero tuning cost
    cache = PlanCache()
    report = wisdom.warm_start(cache=cache)
    svc = ImagingService(
        plan_mode="measure" if report.kept else None, cache=cache
    )

    # 3. the recon lane: N requests -> one batched CG-SENSE solve
    reqs = [
        ReconRequest(kspace=kspace, smaps=smaps, mask=mask,
                     iters=args.iters, lam=1e-3)
        for _ in range(args.requests)
    ]
    svc.serve(reqs)

    zf = mri.nrmse(mri.recon_zero_filled(kspace, smaps, mask), phantom)
    cg = mri.nrmse(reqs[0].image, phantom)
    print(f"zero-filled NRMSE = {zf:.4f}")
    print(f"CG-SENSE    NRMSE = {cg:.4f}  "
          f"({args.iters} iterations, batch of {args.requests})")
    assert cg < zf, "CG-SENSE must beat the zero-filled baseline"

    # 4. what the planner and the recon lane actually did
    print()
    print(xfft.report())


if __name__ == "__main__":
    main()
