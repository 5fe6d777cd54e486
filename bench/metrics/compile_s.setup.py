"""Seconds the process spent tracing, lowering, and compiling or loading
from the persistent cache: the sum of the program's ``compile.*_us``
counters (``repro.compile_cache`` feeds them from ``jax.monitoring``).

Read after the window, so it also holds the few small programs the
correctness check compiles there; nothing compiles inside the window. The
cache's hits and misses go to standard error. A program without the
counters reports nothing.
"""

import sys


def read(ctx):
    from repro import obs

    counts = obs.counters()
    phases = {k: v for k, v in counts.items()
              if k.startswith("compile.") and k.endswith("_us")}
    if not phases:
        return None
    print(f"compile_s.setup: {phases}, cache hits "
          f"{counts.get('compile.cache.hit', 0)}, misses "
          f"{counts.get('compile.cache.miss', 0)}", file=sys.stderr)
    return sum(phases.values()) / 1e6
