"""End-to-end driver: the continuous-batching serve loop as a fleet server.

The production startup sequence for a serving process, in ~60 lines:

1. **warm start** — load the packaged wisdom artifact
   (``repro.serve.wisdom``) into a fresh plan cache, so a MEASURE-grade
   plan serves every covered shape with zero tuning cost;
2. **start the loop** — one background scheduler thread
   (``svc.loop.start()``) coalesces streaming submits into per-lane
   batches under a max-batch / max-wait policy, with ``Overloaded``
   backpressure past the queue limit;
3. **stream requests** — mixed real/complex frames from independent
   "clients" ride the same loop; each submitter holds a Ticket and
   blocks only on its own result;
4. **introspect** — ``xfft.report()`` shows the wisdom entries that
   served the traffic (and would show per-service quarantine rows if an
   engine had been benched mid-stream).

  PYTHONPATH=src python examples/serve_loop.py --requests 48 --hw 64
"""

import argparse
import time

import numpy as np

import repro.xfft as xfft
from repro import obs
from repro.plan import PlanCache
from repro.resilience import ServicePolicy
from repro.serve import BatchPolicy, SpectrumRequest, SpectrumService, wisdom
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--hw", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    args = ap.parse_args()

    # 1. warm start: the fleet never pays MEASURE cost per process
    cache = PlanCache()
    report = wisdom.warm_start(cache=cache)
    print(f"wisdom: kept={report.kept} dropped={report.dropped} "
          f"({report.file_error or 'packaged artifact'})")

    # 2. the service + its long-lived scheduler
    svc = SpectrumService(
        plan_mode="measure" if report.kept else None,
        cache=cache,
        policy=ServicePolicy(max_queue=4 * args.requests),
        batch=BatchPolicy(max_batch=args.max_batch,
                          max_wait_s=args.max_wait_ms / 1e3),
    )
    svc.loop.start()

    # 3. streaming clients: interleaved real/complex frames -> two lanes
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    tickets = []
    for i in range(args.requests):
        if i % 2 == 0:
            frame = rng.standard_normal((args.hw, args.hw)).astype(np.float32)
        else:
            frame = (rng.standard_normal((args.hw, args.hw))
                     + 1j * rng.standard_normal((args.hw, args.hw))
                     ).astype(np.complex64)
        tickets.append(svc.loop.submit(SpectrumRequest(frame=frame)))
    for t in tickets:
        t.result(timeout=60.0)  # blocks until this ticket's batch ran
    dt = time.perf_counter() - t0
    svc.loop.stop()

    ref = np.fft.rfft2(np.asarray(tickets[0].request.frame))
    np.testing.assert_allclose(tickets[0].request.spectrum, ref,
                               rtol=1e-4, atol=1e-4)
    print(f"served {args.requests} requests in {dt * 1e3:.1f} ms "
          f"({args.requests / dt:.0f} req/s), "
          f"lanes={len(svc.plans)}, ticks={obs.counters().get('serve.loop.tick')}")

    # 4. what the planner learned (FFTW export_wisdom-style)
    print(xfft.report(cache))


if __name__ == "__main__":
    main()
