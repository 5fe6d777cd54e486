"""Closed loop: one client calls the system back to back.

One client transforms a device-resident stack call after call, as an
offline pipeline does. It keeps ``calls_ahead`` calls sent ahead of the one
it waits for, holding their outputs, so that the chip does not idle while
the host wakes up and sends the next call; with ``calls_ahead`` 0 each call
waits for its result before the next is sent. The window sends calls until
``--seconds`` have passed, then sends nothing more, waits for every call
sent, and reads the clock: ``frames_per_s`` is every frame of those calls
over that whole time.

Traffic parameters: ``op`` (``bench/ops/<op>.py``), which builds the input
from the configuration and makes the call, and ``calls_ahead``: each call
ahead holds one more output on the chip.
"""

from __future__ import annotations

import collections
import time

from bench import harness


def run(ctx: harness.RunContext, trace_dir) -> harness.Window:
    import jax

    work = harness.load_op(ctx.traffic["op"]).Workload(ctx)
    ahead = int(ctx.traffic.get("calls_ahead", 0))
    # Set-up: the first call compiles, the second shows that nothing is
    # left to compile or warm.
    for _ in range(2):
        jax.block_until_ready(work.call())

    ctx.start_trace(trace_dir)
    log = ctx.events
    calls = failed_calls = failover_calls = 0
    sent: collections.deque = collections.deque()  # outputs not waited for
    y = None
    t0 = ctx.open_window()
    with ctx.annotate("bench.window"), ctx.observe():
        while True:
            seen = (log.failures, log.failovers)
            with ctx.annotate("bench.call"):
                y = None  # only `sent` keeps an earlier output
                y = work.call()
                sent.append(y)
            calls += 1
            failed_calls += log.failures != seen[0]
            failover_calls += log.failovers != seen[1]
            if len(sent) > ahead:
                with ctx.annotate("bench.block"):
                    jax.block_until_ready(sent.popleft())
            if time.perf_counter() - t0 >= ctx.seconds:
                break
        with ctx.annotate("bench.block"):
            jax.block_until_ready(tuple(sent))
        sent.clear()
        t_end = time.perf_counter()
    ctx.stop_trace(trace_dir)
    ctx.events.on = False
    ctx.compiles.armed = False

    notes = {"memory_peak_bytes": harness.memory_peak_bytes(ctx.devices),
             "calls": calls, "failover_calls": failover_calls}
    checks = work.check(y)
    frames = work.frames_per_call
    window_s = t_end - t0
    return harness.Window(
        attempted=calls * frames, failed=failed_calls * frames,
        e2e={"frames_per_s": calls * frames / window_s},
        checks=checks, work_bytes_per_chip=calls * work.work_bytes_per_chip,
        notes=notes,
    )
