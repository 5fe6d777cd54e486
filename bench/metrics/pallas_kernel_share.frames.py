"""Share of device busy time spent in the program's Pallas kernels, in
percent: device seconds in operations whose name starts ``repro_`` (every
``pallas_call`` of the program passes ``name="repro_<entry point>"``) over
busy seconds, both from the trace reduction.

A program whose kernels carry no stable name (one without span records,
which came with the names) reports nothing.
"""


def read(ctx):
    from repro import obs

    red = ctx.reduced
    if red is None or red.busy_s <= 0 or not hasattr(obs, "spans"):
        return None
    return 100.0 * red.op_time(r"(^|/)repro_") / red.busy_s
