"""One run of one benchmark cell, driven by data.

``BENCHMARK.json`` names each cell's configuration and traffic mix. Everything
that belongs to one of them sits in files of its own, found by name:

* ``bench/configs/<config>.json``  the deployment: its source, shapes, the
  limits of its comparisons, and the lists ``reduced`` and ``assumed``;
* ``bench/traffic/<traffic>.json`` the traffic mix: which generator drives it
  (``driver``), what each operation is (``op``), and their parameters;
* ``bench/ops/<op>.py``            builds one configuration's input, calls the
  system under test, and checks what came back against the plain reference;
* ``bench/drivers/<driver>.py``    the general generators (so far one: a
  closed loop);
* ``bench/metrics/<metric>.py``    one reader per per-layer metric;
* ``bench/peaks.json``             published peaks, keyed by ``device_kind``.

A run names its device, refuses to run without the chips its cell asks for,
warms up, measures for ``--seconds``, checks what the timed path produced,
and prints one JSON line last on standard output.
"""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

#: Program events that mean an operation failed: a lane error fails every
#: request of its batch. Copied from the program's chip smoke run.
FAILURE_EVENTS = ("serve.lane.error",)

#: Program events that mean an operation was not served as planned, but was
#: served: a failover hides a failed engine behind the next rung of the
#: ladder, whose answer ``correct`` judges. The breaker re-probes a failed
#: engine by the wall clock (every 30 s by default), so how many calls of a
#: window fail over depends on when the window opens: they are counted and
#: printed, not failed.
FAILOVER_EVENTS = ("resilience.failover",)

#: JAX's monitoring event for a backend compile or a persistent-cache load.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_spec(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    known = ", ".join(c["name"] for c in spec["workloads"])
    raise SystemExit(f"unknown workload {name!r}; known: {known}")


def load_config(spec: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    for cfg in spec["configs"]:
        if cfg["name"] == name:
            return load_json(root / cfg["file"])
    raise SystemExit(f"unknown configuration {name!r}")


def load_traffic(name: str) -> dict:
    return load_json(BENCH_DIR / "traffic" / f"{name}.json")


def peaks_for(kind: str) -> dict:
    """The published peaks of ``kind``. A kind missing from the table is an
    error, never a default."""
    table = load_json(BENCH_DIR / "peaks.json")
    if kind not in table:
        raise SystemExit(
            f"no peaks for device kind {kind!r} in bench/peaks.json "
            f"(known: {sorted(table)})"
        )
    return table[kind]


def load_op(name: str):
    return importlib.import_module(f"bench.ops.{name}")


def load_driver(name: str):
    return importlib.import_module(f"bench.drivers.{name}")


def load_metric_reader(name: str) -> Callable:
    """``bench/metrics/<name>.py``'s ``read``; names may hold dots."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read


def seed_words(seed: int) -> tuple:
    """Split a seed of any size into 32-bit words (numpy and JAX keys)."""
    seed = int(seed)
    if seed < 0:
        raise SystemExit(f"--seed must be >= 0, got {seed}")
    words = []
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return tuple(words)


def device_key(seed: int):
    import jax

    words = seed_words(seed)
    key = jax.random.key(words[0])
    for w in words[1:]:
        key = jax.random.fold_in(key, w)
    return key


def host_rng(seed: int, *salt: int):
    import numpy as np

    return np.random.default_rng([*seed_words(seed), *salt])


def chips(n: int, require_tpu: bool = True) -> list:
    """The first ``n`` devices; raises :class:`NoChip` without a TPU or
    with fewer than ``n`` devices."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {devs[0].platform!r})")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX finds {len(devs)}")
    return devs[:n]


def memory_peak_bytes(devs) -> Optional[int]:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class EventLog:
    """A process-wide ``repro.obs`` sink: keeps the program's failure and
    failover events from every thread (a capture scope sees only its own)
    and counts those that fall in the open window."""

    def __init__(self):
        self.seen: List[tuple] = []  # (in the window, event)
        self.failures = 0
        self.failovers = 0
        self.on = False

    def __call__(self, event) -> None:
        if event.name in FAILURE_EVENTS:
            self.seen.append((self.on, event))
            self.failures += self.on
        elif event.name in FAILOVER_EVENTS:
            self.seen.append((self.on, event))
            self.failovers += self.on

    def report(self) -> None:
        """One line on standard error per failure or failover event, set-up
        included: a failover there routes the window's calls to another
        engine."""
        for in_window, e in self.seen:
            fields = ", ".join(f"{k}={str(v)[:200]}" for k, v in e.fields.items())
            where = "window" if in_window else "outside the window"
            print(f"program event ({where}): {e.name}: {fields}", file=sys.stderr)


class CompileCounter:
    """Counts backend compiles (and persistent-cache loads) while armed."""

    def __init__(self):
        self.armed = False
        self.count = 0

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if self.armed and event == COMPILE_EVENT:
            self.count += 1


@dataclass
class Window:
    """What a driver hands back after its measured window."""

    attempted: int                    # operations (frames, requests) sent
    failed: int                       # of those, failed or never answered
    e2e: Dict[str, float]             # end-to-end metric values
    checks: Dict[str, tuple]          # name -> (value, limit, passed)
    work_bytes_per_chip: float = 0.0  # the transform's minimal HBM traffic
    notes: Dict[str, Any] = field(default_factory=dict)


@dataclass
class RunContext:
    """Everything a driver and a metric reader may look at."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    peaks: dict
    events: EventLog
    compiles: CompileCounter
    t_process: float
    window: Optional[Window] = None
    reduced: Optional[Any] = None      # trace_reduce.Reduction, traced runs
    setup_s: Optional[float] = None
    override: Optional[Callable] = None  # stands in for the timed call

    def annotate(self, name: str):
        """A host span in the profiler's trace (traced runs only)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start_trace(self, trace_dir) -> None:
        """Start the profiler (traced runs): device operations and host
        spans, without the Python function tracer's per-call events."""
        if trace_dir is None:
            return
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)

    def stop_trace(self, trace_dir) -> None:
        if trace_dir is not None:
            import jax

            jax.profiler.stop_trace()

    def observe(self):
        """The program's own spans as profiler annotations (traced runs)."""
        if not self.trace:
            return contextlib.nullcontext()
        import repro.xfft as xfft

        return xfft.config(observe=True)

    def open_window(self) -> float:
        """Mark the end of set-up; returns the window's start time."""
        now = time.perf_counter()
        self.setup_s = now - self.t_process
        self.compiles.armed = True
        self.events.on = True
        return now


def per_layer_metrics(spec: dict, cell_name: str, e2e_names: List[str]) -> list:
    """The per-layer metrics a cell reports: those that list it, and those
    without a list whose end-to-end metric the cell reports."""
    out = []
    for m in spec["per_layer"]:
        cells = m.get("workloads")
        if (cell_name in cells) if cells is not None else (m["moves"] in e2e_names):
            out.append(m)
    return out


def e2e_metrics(spec: dict, cell_name: str) -> list:
    return [m for m in spec["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def run_cell(
    name: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    spec: Optional[dict] = None,
    config: Optional[dict] = None,
    require_tpu: bool = True,
    t_process: Optional[float] = None,
    override: Optional[Callable] = None,
) -> dict:
    """Run one cell once and return the result object (not yet printed).

    ``config`` replaces the cell's configuration file (tests run a small
    copy on the CPU with ``require_tpu=False``); ``override`` takes the
    place of the system's call in the op (``bench/ops/<op>.py`` says which
    call): the control, or a planted fault.
    """
    t_process = time.perf_counter() if t_process is None else t_process
    spec = load_spec() if spec is None else spec
    cell = find_cell(spec, name)
    config = load_config(spec, cell["config"]) if config is None else config
    traffic = load_traffic(cell["traffic"])

    devs = chips(int(cell["chips"]), require_tpu=require_tpu)
    kind = devs[0].device_kind
    print(f"device: platform={devs[0].platform} kind={kind!r} count={len(devs)}",
          file=sys.stderr, flush=True)
    peaks = peaks_for(kind) if require_tpu else load_json(BENCH_DIR / "peaks.json")[
        "TPU v5 lite"]

    import jax

    from repro import obs
    from repro.compile_cache import enable_compile_cache

    cache, held = enable_compile_cache()
    # Every program goes to the persistent cache, however quick its compile:
    # otherwise the many small eager programs compile again in every run.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    print(f"compile cache: {cache} (held entries at start: {held})",
          file=sys.stderr, flush=True)

    log = EventLog()
    obs.add_sink(log)
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    ctx = RunContext(cell=cell, config=config, traffic=traffic, seed=seed,
                     seconds=seconds, trace=trace, devices=devs, peaks=peaks,
                     events=log, compiles=compiles, t_process=t_process,
                     override=override)
    driver = load_driver(traffic["driver"])
    trace_dir = pathlib.Path(tempfile.mkdtemp(prefix="bench_trace_")) if trace else None
    try:
        window = driver.run(ctx, trace_dir)
    finally:
        obs.remove_sink(log)
        compiles.armed = False
    ctx.window = window
    print(f"compiles in window: {compiles.count}", file=sys.stderr, flush=True)
    log.report()

    result: Dict[str, Any] = {
        "correct": bool(window.checks) and all(ok for _, _, ok in window.checks.values()),
        "attempted": window.attempted,
        "failed": window.failed,
        "metrics": {},
        "device": {
            "platform": devs[0].platform,
            "kind": kind,
            "count": len(devs),
            "memory_peak_bytes": window.notes.pop("memory_peak_bytes", None),
        },
    }
    units = {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}
    if not trace:
        values = dict(window.e2e, setup_s=ctx.setup_s)
        for m in e2e_metrics(spec, name):
            result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        from bench import trace_reduce

        try:
            ctx.reduced = trace_reduce.reduce_dir(
                trace_dir, n_devices=len(devs), window_name="bench.window")
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        red = ctx.reduced
        result["device"]["busy_s"] = red.busy_s
        result["device"]["window_s"] = red.window_s
        e2e_names = [m["name"] for m in e2e_metrics(spec, name)]
        for m in per_layer_metrics(spec, name, e2e_names):
            value = load_metric_reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": units[m["name"]]}
        result["breakdown"] = red.breakdown()
    result["compiles_in_window"] = compiles.count
    result["program_events_outside_window"] = sum(not w for w, _ in log.seen)
    result.update(window.notes)
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim, _) in window.checks.items()}
    return result


def report_checks(result: dict) -> None:
    """The numbers compared, each beside its limit: the last lines on
    standard error."""
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
