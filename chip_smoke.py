"""Chip smoke run: drive the planned FFT service once on a TPU and check it.

    python chip_smoke.py [--seed N]      # one chip: lanes (a)-(c), MEASURE, double refusal
    python chip_smoke.py --four-chips    # four chips: xfft.fft2 on a row-sharded grid only

One process, no children. The default run serves, through one
``ImagingService`` at its default planning mode:

  (a) 64 complex64 frames of 512x512 on the ``fft2d`` lane — the fused
      kernel's VMEM census is exactly full at this frame;
  (b) 16 float32 frames of 2048x2048 on the ``rfft2d`` lane — over the
      census, so the fused row kernel plus a column pass serve it;
  (c) one CG-SENSE ``ReconRequest`` at 512x512 with 16 coils, R=4, 10
      iterations;

each twice (first call = compile, second = warm), checked against float64
``numpy.fft`` (a, b) or against the zero-filled reconstruction (c). Then it
MEASURE-resolves lane (a)'s key, so every single-precision engine compiles
and runs on the chip once, and checks that ``precision="double"`` is
refused by the planner by name. Any failover, lane error, failed MEASURE
candidate or open breaker fails the run, as does lane (a) running anything
but a compiled fused kernel.

The last line of standard output is one JSON object, ``{"ok": true,
"device": {...}}``, printed only when every phase passed. Without a TPU
the script exits non-zero before doing any work.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

#: Max |got - ref| / max(1, max |ref|) against float64 numpy, the
#: single-precision kernel tests' gate (tests/kernels/test_fft_radix4.py).
SINGLE_ATOL = 1e-5

#: CG-SENSE must beat zero-filled NRMSE by this factor at R=4, the gate of
#: tests/mri/test_recon.py and benchmarks/mri_bench.py.
RECON_R4_MARGIN = 0.7

#: Lane sizes: (frames, frame edge) for (a) and (b), (edge, coils) for (c),
#: and the four-chip frame edge.
LANE_A = (64, 512)
LANE_B = (16, 2048)
LANE_C = (512, 16)
PENCIL_N = 8192

#: Events that mean a fallback hid the device or a request failed.
FORBIDDEN = ("resilience.failover", "serve.lane.error")


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    info = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    print(f"device: platform={info['platform']} kind={info['kind']!r} "
          f"count={info['count']}", flush=True)
    return info


def scaled_err(got, ref) -> float:
    ref = np.asarray(ref)
    scale = max(1.0, float(np.max(np.abs(ref))))
    return float(np.max(np.abs(np.asarray(got) - ref))) / scale


def forbidden_events(trace) -> list:
    bad = [e for name in FORBIDDEN for e in trace.select(name)]
    bad += [e for e in trace.select("plan.degrade")
            if e["reason"] == "measure_failed"]
    bad += [e for e in trace.select("resilience.breaker")
            if e["state"] == "open"]
    return bad


def serve_lane(svc, name: str, requests_fn, obs) -> dict:
    """Serve a lane twice (compile, then warm) and report what ran."""
    report = {}
    for phase in ("first", "warm"):
        reqs = requests_fn()
        with obs.capture() as trace:
            t0 = time.perf_counter()
            svc.serve(reqs)
            report[f"{phase}_s"] = time.perf_counter() - t0
        engines = {e["engine"] for e in trace.select("engine.apply")}
        report["engines"] = sorted(engines | set(report.get("engines", ())))
        report.setdefault("kernel_failovers", sorted(
            {e["kind"] for e in trace.select("kernel.failover")}))
        check(all(r.done for r in reqs), f"lane {name}: a request was not served")
        report["requests"] = reqs
    return report


def run_one_chip(seed: int) -> None:
    import jax

    import repro.xfft as xfft
    from repro import mri, obs
    from repro.engines import get_engine
    from repro.plan import PlanCache, plan_fft
    from repro.resilience import quarantine
    from repro.serve import ImagingService, ReconRequest, SpectrumRequest

    rng = np.random.default_rng(seed)
    svc = ImagingService()
    results = {}
    with obs.capture() as whole:
        # (a) complex frames at the fused kernel's census.
        fa, na = LANE_A
        frames_a = (rng.standard_normal((fa, na, na))
                    + 1j * rng.standard_normal((fa, na, na))).astype(np.complex64)
        lane_a = serve_lane(
            svc, "a", lambda: [SpectrumRequest(frame=f) for f in frames_a], obs
        )
        got = np.stack([r.spectrum for r in lane_a.pop("requests")])
        lane_a["err"] = scaled_err(got, np.fft.fft2(frames_a.astype(np.complex128)))
        results["a"] = lane_a
        del got

        # (b) real frames over the census: row kernel + column pass.
        fb, nb = LANE_B
        frames_b = rng.standard_normal((fb, nb, nb)).astype(np.float32)
        lane_b = serve_lane(
            svc, "b", lambda: [SpectrumRequest(frame=f) for f in frames_b], obs
        )
        got = np.stack([r.spectrum for r in lane_b.pop("requests")])
        lane_b["err"] = scaled_err(got, np.fft.rfft2(frames_b.astype(np.float64)))
        results["b"] = lane_b
        del got, frames_b

        # (c) one CG-SENSE reconstruction.
        nc, coils = LANE_C
        x = np.asarray(mri.shepp_logan(nc))
        smaps = np.asarray(mri.birdcage_maps(coils, nc))
        mask = np.asarray(mri.variable_density_mask((nc, nc), 4, seed=seed))
        k = np.asarray(mri.sense_forward(x, smaps, mask))
        zf = mri.nrmse(np.asarray(mri.recon_zero_filled(k, smaps, mask)), x)
        lane_c = serve_lane(
            svc, "c",
            lambda: [ReconRequest(kspace=k, smaps=smaps, mask=mask, iters=10)], obs,
        )
        (req,) = lane_c.pop("requests")
        lane_c["nrmse"] = mri.nrmse(req.image, x)
        lane_c["nrmse_zero_filled"] = zf
        results["c"] = lane_c

    labels = {
        "a": f"fft2d {fa}x{na}x{na} complex64",
        "b": f"rfft2d {fb}x{nb}x{nb} float32",
        "c": f"recon {coils} coils {nc}x{nc} R=4 10 iters",
    }
    for name, lane in results.items():
        print(f"lane {name} ({labels[name]}): {json.dumps(lane)}", flush=True)
    check(lane_a["err"] <= SINGLE_ATOL, f"lane a off numpy: {lane_a['err']:.3e}")
    check(lane_b["err"] <= SINGLE_ATOL, f"lane b off numpy: {lane_b['err']:.3e}")
    check(lane_c["nrmse"] < RECON_R4_MARGIN * lane_c["nrmse_zero_filled"],
          f"lane c NRMSE {lane_c['nrmse']:.4f} does not beat "
          f"{RECON_R4_MARGIN} x zero-filled {lane_c['nrmse_zero_filled']:.4f}")

    # Lane (a) must have run the paper's kernel, compiled for the chip.
    check(len(lane_a["engines"]) == 1 and lane_a["engines"][0] in ("fused", "fused_r4"),
          f"lane a ran {lane_a['engines']}, not one fused kernel")
    engine = lane_a["engines"][0]
    shape = jax.ShapeDtypeStruct((fa, na, na), np.complex64)
    hlo = jax.jit(get_engine(engine).op("fft2d")).lower(shape).as_text()
    check("tpu_custom_call" in hlo, f"lane a's {engine!r} lowers to no TPU kernel")
    print(f"lane a engine {engine!r}: tpu_custom_call in lowered HLO", flush=True)

    # One MEASURE sweep of lane (a)'s key: every candidate compiles and runs.
    with obs.capture() as sweep:
        t0 = time.perf_counter()
        plan = plan_fft("fft2d", (na, na), "complex64", mode="measure",
                        cache=PlanCache(), force=True)
        sweep_s = time.perf_counter() - t0
    span = sweep.first("plan.measure")
    check(span is not None, "MEASURE resolve ran no sweep")
    skipped = dict(span.fields.get("skipped") or {})
    print(f"measure fft2d {na}x{na}: chosen={plan.variant!r} {sweep_s:.3f}s "
          f"timings_us={json.dumps(span['timings'])} skipped={json.dumps(skipped)}",
          flush=True)
    errored = {k: v for k, v in skipped.items() if str(v).startswith("error:")}
    check(not errored, f"MEASURE candidates failed on the chip: {errored}")

    # Double precision has no engine on the chip: the planner says so.
    try:
        with xfft.config(precision="double"):
            xfft.fft2(np.zeros((8, 8), np.complex128))
    except ValueError as e:
        check("no registered engine supports" in str(e),
              f"double precision failed with an unexpected error: {e}")
        print(f"double precision refused: {e}", flush=True)
    else:
        raise SmokeFailure("precision='double' ran on the chip; expected a refusal")

    bad = forbidden_events(whole) + forbidden_events(sweep)
    check(not bad, f"fallback or failure events: {[(e.name, e.fields) for e in bad]}")
    opened = [row for row in quarantine().table() if row["state"] == "open"]
    check(not opened, f"open breakers: {opened}")


def run_four_chips(seed: int, info: dict) -> None:
    import jax

    import repro.xfft as xfft
    from repro import obs
    from repro.core.distributed import pencil_sharding, repro_pencil_fft2
    from repro.launch.mesh import make_mesh

    check(info["count"] == 4, f"--four-chips needs 4 devices, found {info['count']}")
    n = PENCIL_N
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((n, n), np.float32)
         + 1j * rng.standard_normal((n, n), np.float32)).astype(np.complex64)
    mesh = make_mesh((4,), ("data",))
    xs = jax.device_put(x, pencil_sharding(mesh, "data", "rows"))
    # The front door plans the row-sharded grid as a pencil transform.
    with obs.capture() as trace:
        t0 = time.perf_counter()
        y = jax.block_until_ready(xfft.fft2(xs))
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        y = jax.block_until_ready(xfft.fft2(xs))
        warm_s = time.perf_counter() - t0
    resolved = trace.select("plan.resolve")
    check(resolved and all(e["kind"] == "fft2d_pencil" and e["n_devices"] == 4
                           for e in resolved), f"not planned as a pencil: {resolved}")
    dispatch = trace.select("pencil.dispatch")
    check(len(dispatch) == 2, f"{len(dispatch)} pencil dispatches for 2 calls")
    bad = forbidden_events(trace)
    check(not bad, f"fallback or failure events: {[(e.name, e.fields) for e in bad]}")

    def quarters(arr, shard_shape):
        shards = arr.addressable_shards
        return (len({s.device for s in shards}) == 4
                and all(s.data.shape == shard_shape for s in shards))

    check(quarters(xs, (n // 4, n)), "input is not split into row quarters")
    check(quarters(y, (n, n // 4)), "output is not split into column quarters")
    plan = dispatch[-1]
    hlo = repro_pencil_fft2.lower(xs, mesh=mesh, axis="data", layout="rows",
                                  variant=plan["variant"],
                                  chunks=plan["chunks"]).compile().as_text()
    check("all-to-all" in hlo, "compiled pencil FFT has no all-to-all")

    got = np.asarray(y)
    one = np.asarray(xfft.fft2(jax.device_put(x, jax.devices()[0])))
    ref = np.fft.fft2(x.astype(np.complex128))
    err_np = scaled_err(got, ref)
    err_one = scaled_err(got, one)
    print(f"four-chip pencil fft2 {n}x{n} complex64: " + json.dumps({
        "first_s": first_s, "warm_s": warm_s, "variant": plan["variant"],
        "chunks": plan["chunks"], "err_vs_numpy": err_np,
        "err_vs_one_chip": err_one, "one_chip_err_vs_numpy": scaled_err(one, ref),
    }), flush=True)
    check(err_np <= SINGLE_ATOL, f"pencil FFT off numpy: {err_np:.3e}")
    check(err_one <= SINGLE_ATOL, f"pencil FFT off one-chip xfft.fft2: {err_one:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded xfft.fft2 phase")
    args = ap.parse_args(argv)

    info = device_info()
    if info["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {info['platform']!r}); "
              "this run needs the chip", file=sys.stderr)
        return 2

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import enable_compile_cache

    cache, held = enable_compile_cache()
    print(f"compile cache: {cache} (held entries at start: {held})", flush=True)

    try:
        if args.four_chips:
            run_four_chips(args.seed, info)
        else:
            run_one_chip(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
