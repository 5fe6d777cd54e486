"""Reduce a profiler trace of one measured window to device metrics.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes. Device planes
(``/device:TPU:<n>``) hold the operations that ran on each chip; the host
plane holds the spans open on the host: the harness's own (``bench.*``) and,
under ``xfft.config(observe=True)``, the program's (``engine.apply``,
``serve.batch``, ...).

* busy time: on each chip, the union of the intervals in which an
  operation ran, clipped to the window; averaged over the chips;
* idle share: 1 - busy / window;
* device time per operation, averaged over the chips, named
  ``<program>/<operation>`` (``jit_rfft2_fused/rfft2_fused``,
  ``jit_add/custom-call:X64Combine``): the jitted program it ran in, without
  its hash, and the HLO operation without its number;
* idle gaps: each stretch of the window in which a chip ran nothing,
  named after the innermost host span open at its midpoint.
"""

from __future__ import annotations

import pathlib
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: Host spans that name what the host was doing: dotted lower-case names
#: (``bench.call``, ``engine.apply``); the profiler's own Python frames
#: (``$file.py:12 fn``) and runtime events are not spans of this kind.
SPAN_NAME = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
NO_SPAN = "(no host span)"
#: The device lines that hold one event per operation run, and one per
#: program (jitted module) run.
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Event = Tuple[str, int, int]  # (name, start_ns, end_ns)


@dataclass
class Reduction:
    window_s: float
    busy_s: float                           # per chip, averaged
    op_s: Dict[str, float]                  # per chip, averaged
    gap_s: Dict[str, float]                 # idle time by host span, per chip
    gaps: List[Tuple[str, float]] = field(default_factory=list)  # longest gaps
    n_devices: int = 1

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_time(self, pattern: str) -> float:
        """Device seconds per chip in operations whose name matches."""
        rx = re.compile(pattern)
        return sum(s for name, s in self.op_s.items() if rx.search(name))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gap_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def merged(intervals: Iterable[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Sorted, non-overlapping union of ``intervals`` clipped to [lo, hi]."""
    out: List[List[int]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def overlap(a: int, b: int, lo: int, hi: int) -> int:
    return max(0, min(b, hi) - max(a, lo))


def innermost_spans(spans: Sequence[Event], times: Sequence[int]) -> List[str]:
    """For each of ``times``, the innermost (latest-starting) host span open
    then, by a sweep over the spans in time order."""
    spans = sorted((s, e, n) for n, s, e in spans if SPAN_NAME.match(n))
    order = sorted(range(len(times)), key=lambda i: times[i])
    out = [NO_SPAN] * len(times)
    active: List[Tuple[int, int, str]] = []
    k = 0
    for i in order:
        t = times[i]
        while k < len(spans) and spans[k][0] <= t:
            active.append(spans[k])
            k += 1
        active = [a for a in active if a[1] >= t]
        if active:
            out[i] = max(active)[2]
    return out


def reduce_events(
    device_ops: Dict[int, Sequence[Event]],
    host_spans: Sequence[Event],
    window: Tuple[int, int],
) -> Reduction:
    """The reduction of one window from events already read from a trace."""
    lo, hi = window
    n = max(1, len(device_ops))
    busy = 0
    op_ns: Dict[str, float] = defaultdict(float)
    idle: List[Tuple[int, int]] = []
    for ops in device_ops.values():
        union = merged(((s, e) for _, s, e in ops), lo, hi)
        busy += sum(b - a for a, b in union)
        for name, s, e in ops:
            op_ns[name] += overlap(s, e, lo, hi)
        edges = [lo] + [t for iv in union for t in iv] + [hi]
        idle += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    names = innermost_spans(host_spans, [(a + b) // 2 for a, b in idle])
    gap_ns: Dict[str, float] = defaultdict(float)
    for what, (a, b) in zip(names, idle):
        gap_ns[what] += b - a
    gaps = sorted(((w, (b - a) / 1e9) for w, (a, b) in zip(names, idle)),
                  key=lambda g: -g[1])
    return Reduction(
        window_s=(hi - lo) / 1e9,
        busy_s=busy / n / 1e9,
        op_s={k: v / n / 1e9 for k, v in op_ns.items()},
        gap_s={k: v / n / 1e9 for k, v in gap_ns.items()},
        gaps=gaps[:10],
        n_devices=n,
    )


def find_xplane(trace_dir: pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _device_index(plane_name: str) -> Optional[int]:
    m = re.fullmatch(r"/device:TPU:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def op_name(hlo: str) -> str:
    """Short name of an HLO operation from its text in the trace:
    ``%copy.1 = f32[...] copy(...)`` -> ``copy``; a custom call names its
    target: ``custom-call:X64Combine``."""
    name = re.sub(r"\.\d+$", "", hlo.split(" = ", 1)[0].lstrip("%").strip())
    target = re.search(r'custom_call_target="([^"]+)"', hlo)
    if target and target.group(1) != "tpu_custom_call":
        name = f"{name}:{target.group(1)}"
    return name


def module_name(name: str) -> str:
    """``jit_add(2991796107358011162)`` -> ``jit_add``."""
    return re.sub(r"\(\d+\)$", "", name)


def _events(line) -> List[Event]:
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)) for e in line.events]


def named_ops(ops: List[Event], modules: List[Event]) -> List[Event]:
    """Each operation named ``<module>/<op>`` after the module run that
    holds it (``?`` where none does)."""
    modules = sorted(modules, key=lambda m: m[1])
    out = []
    k = 0
    for name, s, e in sorted(ops, key=lambda o: o[1]):
        while k < len(modules) and modules[k][2] < s:
            k += 1
        held = k < len(modules) and modules[k][1] <= s
        out.append((f"{module_name(modules[k][0]) if held else '?'}/{op_name(name)}", s, e))
    return out


def read_xplane(path: pathlib.Path, window_name: str):
    """(device_ops, host_spans, window) from a saved trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    device_ops: Dict[int, List[Event]] = {}
    host_spans: List[Event] = []
    for plane in data.planes:
        dev = _device_index(plane.name)
        if dev is not None:
            lines = {line.name: _events(line) for line in plane.lines}
            device_ops[dev] = named_ops(lines.get(OPS_LINE, []), lines.get(MODULES_LINE, []))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host_spans.extend(_events(line))
    windows = [(s, e) for n, s, e in host_spans if n == window_name]
    if not windows:
        raise ValueError(f"no {window_name!r} span in {path}")
    return device_ops, host_spans, windows[0]


def reduce_file(path: pathlib.Path, n_devices: int, window_name: str) -> Reduction:
    device_ops, host_spans, window = read_xplane(path, window_name)
    device_ops = {d: ops for d, ops in device_ops.items() if d < n_devices}
    if len(device_ops) < n_devices:
        raise ValueError(
            f"trace has operations on {len(device_ops)} chips, the run used {n_devices}")
    return reduce_events(device_ops, host_spans, window)


def reduce_dir(trace_dir: pathlib.Path, n_devices: int, window_name: str) -> Reduction:
    return reduce_file(find_xplane(trace_dir), n_devices, window_name)
