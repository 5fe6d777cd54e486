"""The front door's own host cost per call, in microseconds: the median,
over the window's ``xfft.call`` span records, of the call's duration less
the ``engine.apply`` spans inside it. What is left is config, planner
(``plan.resolve``), degradation ladder and event sinks.

The records come from ``repro.obs.spans()``, which the program fills only
while profiling is on: in a traced run, the window alone. A program
without span records reports nothing.
"""

import statistics
import sys
from collections import defaultdict


def _outermost(record, name, by_id):
    """The outermost record named ``name`` among ``record``'s ancestors,
    or None."""
    found = None
    parent = by_id.get(record.parent_id)
    while parent is not None:
        if parent.name == name:
            found = parent
        parent = by_id.get(parent.parent_id)
    return found


def host_path_us(records):
    by_id = {r.span_id: r for r in records}
    calls = [r for r in records
             if r.name == "xfft.call" and _outermost(r, "xfft.call", by_id) is None]
    applied = defaultdict(int)
    for r in records:
        if r.name == "engine.apply" and _outermost(r, "engine.apply", by_id) is None:
            call = _outermost(r, "xfft.call", by_id)
            if call is not None:
                applied[call.span_id] += r.end_ns - r.start_ns
    costs = [(c.end_ns - c.start_ns - applied[c.span_id]) / 1e3 for c in calls]
    return (statistics.median(costs) if costs else None), len(calls)


def read(ctx):
    from repro import obs

    if not hasattr(obs, "spans"):
        return None
    value, n = host_path_us(obs.spans())
    print(f"host_path_us.frames: {n} xfft.call records, "
          f"{ctx.window.notes.get('calls')} calls", file=sys.stderr)
    return value
