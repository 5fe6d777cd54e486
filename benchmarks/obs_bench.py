"""Observability overhead + event-stream acceptance: BENCH_obs.json.

Four gates, all about trusting the ``repro.obs`` layer:

1. **Overhead, recorder ON** — the fully instrumented cached hot path
   (``xfft.fft2`` at NxN, plan already in cache, the always-on flight
   recorder at its default capacity AND an active ``obs.capture()``
   scope) must stay within ``--gate-pct`` (default 3%) of the identical
   loop gone fully dark (``xfft.config(flight_recorder=False)``, no
   scope). Baseline and instrumented reps are interleaved so clock
   drift hits both equally.

2. **"Second run re-tunes nothing", proven by events** — under a
   file-backed MEASURE-mode scope, the cold call must emit exactly one
   ``plan.measure`` sweep; the warm call and a fresh-cache "second
   process" (a new ``PlanCache`` loading the same wisdom file) must emit
   zero, with their ``plan.resolve`` events reading ``outcome="hit"``.

3. **Flight dump fidelity** — an injected engine failure drives a real
   ``resilience.failover``, which must auto-dump a JSONL snapshot whose
   trailing events are exactly the live trace up to and including the
   trigger: the black box replays what the caller saw.

4. **Calibration coverage** — after warm loops over three transform
   kinds, the planner calibration ledger must hold >= 3 (engine, kind)
   rows with observed dispatch samples and an observed/predicted ratio.

Also writes a CI-artifact snapshot: a Prometheus text exposition of
counters + latency histograms (``--prom-out``).

  PYTHONPATH=src python benchmarks/obs_bench.py --size 256
  PYTHONPATH=src python -m benchmarks.run obs
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.xfft as xfft
from repro import obs, resilience
from repro.obs import telemetry
from repro.obs.export import write_prometheus
from repro.plan import PlanCache, reset_default_cache
from repro.plan.api import resolve_call
from repro.resilience import FaultPlan, FaultSpec

try:  # python -m benchmarks.obs_bench (repo root on sys.path)
    from benchmarks.common import emit
except ImportError:  # python benchmarks/obs_bench.py (script dir on sys.path)
    from common import emit


def _hot_loop_us(x, iters: int) -> float:
    """Wall time per cached fft2 call (µs), one rep."""
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(xfft.fft2(x))
    return (time.perf_counter() - t0) * 1e6 / iters


def bench_overhead(n: int, iters: int, reps: int) -> dict:
    """Median per-call time of the cached hot loop, fully dark vs fully
    instrumented (default flight recorder + capture scope)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(
        (rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n)))
        .astype(np.complex64)
    )
    # Warm: plan resolved into the cache, kernels compiled.
    jax.block_until_ready(xfft.fft2(x))

    def dark() -> float:
        with xfft.config(flight_recorder=False):
            return _hot_loop_us(x, iters)

    def lit() -> float:
        with obs.capture():  # default recorder stays installed
            return _hot_loop_us(x, iters)

    baseline, instrumented = [], []
    for rep in range(reps):
        # Interleave AND alternate order per rep: running second in a pair
        # is measurably slower on shared CPUs, so a fixed order would book
        # that position bias as instrumentation overhead.
        if rep % 2:
            instrumented.append(lit())
            baseline.append(dark())
        else:
            baseline.append(dark())
            instrumented.append(lit())
    baseline.sort()
    instrumented.sort()
    base_us = baseline[len(baseline) // 2]
    instr_us = instrumented[len(instrumented) // 2]
    rec = obs.flight_recorder()
    return {
        "size": n,
        "iters": iters,
        "reps": reps,
        "recorder_capacity": rec.capacity if rec else 0,
        "baseline_us": round(base_us, 2),
        "instrumented_us": round(instr_us, 2),
        "overhead_pct": round((instr_us - base_us) / base_us * 100.0, 3),
    }


def bench_events(n: int) -> dict:
    """Cold MEASURE sweep then two warm paths, judged by the event stream."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(
        (rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n)))
        .astype(np.complex64)
    )
    with tempfile.TemporaryDirectory() as d:
        with xfft.config(cache_dir=d, mode="measure"):
            with obs.capture() as cold:
                jax.block_until_ready(xfft.fft2(x))
            with obs.capture() as warm:
                jax.block_until_ready(xfft.fft2(x))
        # "Second process": a fresh cache object loads the wisdom file the
        # sweep persisted; resolution must hit with zero MEASURE work.
        fresh = PlanCache(path=os.path.join(d, "xfft_plans.json"))
        with obs.capture() as second:
            resolve_call("fft2d", (n, n), cache=fresh, mode="measure")
        return {
            "size": n,
            "cold_outcome": cold.first("plan.resolve")["outcome"],
            "cold_measure_events": len(cold.select("plan.measure")),
            "cold_candidates": cold.first("plan.measure").get("candidates"),
            "warm_outcome": warm.first("plan.resolve")["outcome"],
            "warm_measure_events": len(warm.select("plan.measure")),
            "second_process_outcome": second.first("plan.resolve")["outcome"],
            "second_process_measure_events": len(second.select("plan.measure")),
            "wisdom_load": fresh.load_report.to_dict(),
        }


def bench_flight_dump(n: int, dump_dir: str) -> dict:
    """Inject one engine failure; the failover must auto-dump a JSONL
    snapshot whose tail is exactly the live trace up to the trigger."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(
        (rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n)))
        .astype(np.complex64)
    )
    jax.block_until_ready(xfft.fft2(x))  # warm: plan + kernels ready
    resilience.reset()
    rec = telemetry.FlightRecorder(capacity=1024, dump_dir=dump_dir)
    fp = FaultPlan(FaultSpec("engine.apply", mode="error", times=1))
    with xfft.config(flight_recorder=rec, faults=fp):
        with obs.capture() as trace:
            jax.block_until_ready(xfft.fft2(x))
    resilience.reset()  # do not leave the benched engine quarantined

    live = [e.name for e in trace]
    upto = live[: live.index("resilience.failover") + 1]
    # the breaker-open dump fires first (record_failure precedes the
    # failover emit); the gate is on the failover snapshot
    dump = next(
        (d for d in rec.stats()["dumps"]
         if d["trigger"] == "resilience.failover"),
        None,
    )
    tail_matches = False
    if dump is not None:
        dumped = [json.loads(line)["name"] for line in open(dump["path"])]
        tail_matches = dumped[-len(upto):] == upto
    return {
        "size": n,
        "dumps": [
            {"trigger": d["trigger"], "events": d["events"]}
            for d in rec.stats()["dumps"]
        ],
        "live_events_to_trigger": len(upto),
        "dump_tail_matches_live_trace": tail_matches,
        "ok": dump is not None and tail_matches,
    }


def bench_calibration(n: int, iters: int) -> dict:
    """Warm loops over three transform kinds; the ledger must join
    observed dispatch durations against planner predictions for >= 3
    (engine, kind) rows."""
    ledger = obs.calibration_ledger()
    ledger.reset()
    rng = np.random.default_rng(3)
    cx = jnp.asarray(
        (rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n)))
        .astype(np.complex64)
    )
    re = jnp.asarray(rng.standard_normal((n, n)).astype(np.float32))
    v = jnp.asarray(
        (rng.standard_normal(n * 4) + 1j * rng.standard_normal(n * 4))
        .astype(np.complex64)
    )
    for _ in range(iters):
        jax.block_until_ready(xfft.fft2(cx))
        jax.block_until_ready(xfft.rfft2(re))
        jax.block_until_ready(xfft.fft(v))
    rows = [r for r in ledger.table() if r["observed_n"] > 0]
    covered = sorted({(r["engine"], r["kind"]) for r in rows})
    return {
        "size": n,
        "iters": iters,
        "observed_rows": len(rows),
        "engine_kind_pairs": [list(p) for p in covered],
        "all_have_ratio": all(r["ratio"] is not None for r in rows),
        "table": ledger.table()[:10],
        "ok": len(covered) >= 3 and all(r["ratio"] is not None for r in rows),
    }


def export_snapshots(prom_out: str) -> dict:
    """Write the CI-artifact view: Prometheus exposition of counters +
    histograms, with the flight recorder's retention as gauges."""
    rec = obs.flight_recorder()
    gauges = {}
    if rec is not None:
        stats = rec.stats()
        gauges = {
            "flight_recorder_retained": stats["retained"],
            "flight_recorder_recorded_total": stats["recorded_total"],
        }
    write_prometheus(
        prom_out,
        counters=obs.counters(),
        gauges=gauges,
        histograms=obs.histograms(),
    )
    return {
        "prometheus": prom_out,
        "histograms_exported": len(obs.histograms()),
    }


def run() -> None:
    """benchmarks.run entry point: default sweep, report to BENCH_obs.json."""
    main(["--out", "/tmp/BENCH_obs.json"])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--size", type=int, default=256,
                    help="frame size N for the cached overhead loop (NxN)")
    ap.add_argument("--measure-size", type=int, default=64,
                    help="frame size for the MEASURE event-stream proof")
    ap.add_argument("--iters", type=int, default=30,
                    help="hot-loop calls per rep")
    ap.add_argument("--reps", type=int, default=7,
                    help="interleaved baseline/instrumented reps (median)")
    ap.add_argument("--gate-pct", type=float, default=3.0,
                    help="max tolerated instrumentation overhead, percent")
    ap.add_argument("--out", default=None, help="write the JSON report here")
    ap.add_argument("--prom-out", default="/tmp/obs_metrics.prom",
                    help="write the Prometheus exposition here")
    args = ap.parse_args(argv)

    reset_default_cache()
    overhead = bench_overhead(args.size, args.iters, args.reps)
    events = bench_events(args.measure_size)
    events_ok = (
        events["cold_outcome"] == "measured"
        and events["cold_measure_events"] == 1
        and events["warm_outcome"] == "hit"
        and events["warm_measure_events"] == 0
        and events["second_process_outcome"] == "hit"
        and events["second_process_measure_events"] == 0
        and events["wisdom_load"]["kept"] >= 1
    )
    overhead_ok = overhead["overhead_pct"] < args.gate_pct
    with tempfile.TemporaryDirectory() as dump_dir:
        flight = bench_flight_dump(args.measure_size, dump_dir)
    calibration = bench_calibration(args.measure_size, iters=5)
    snapshots = export_snapshots(args.prom_out)
    report = {
        "backend": jax.default_backend(),
        "gate_pct": args.gate_pct,
        "overhead": overhead,
        "overhead_ok": overhead_ok,
        "events": events,
        "events_ok": events_ok,
        "flight_dump": flight,
        "calibration": calibration,
        "snapshots": snapshots,
        "counters": obs.counters(),
        "ok": overhead_ok and events_ok and flight["ok"] and calibration["ok"],
    }
    emit(f"obs_bench/hot_loop/{args.size}", overhead["instrumented_us"],
         f"overhead_pct={overhead['overhead_pct']}")
    text = json.dumps(report, indent=2)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
