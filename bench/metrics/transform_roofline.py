"""The transform's share of its HBM roofline, in percent.

Work is counted from the problem alone (``bench/ops/<op>.py``: one read of
the input and one write of the output per frame, per chip), whatever engine
computes it. The least time for that traffic at the chip's published HBM
bandwidth (``bench/peaks.json``), over the device busy time the trace shows
in the same window. FFTs are bound by bandwidth, so this is the bound used.
"""


def read(ctx):
    red, window = ctx.reduced, ctx.window
    if red is None or red.busy_s <= 0 or not window.work_bytes_per_chip:
        return None
    return 100.0 * window.work_bytes_per_chip / ctx.peaks["hbm_bytes_per_s"] / red.busy_s
