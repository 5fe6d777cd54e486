"""Share of device busy time spent in all-to-all operations, in percent:
device seconds in operations named ``all-to-all`` or ``all_to_all`` (the
instruction name JAX's ``lax.all_to_all`` gives; a ``-start``/``-done``
pair where the compiler splits it) over busy seconds, both per chip and
averaged over the chips, in the window (``Reduction.op_time``).

A run whose trace holds no such operation (a program that does not split
the transform over its chips) reports nothing.
"""

import re

ALL_TO_ALL = r"(^|/)all[-_]to[-_]all"


def read(ctx):
    red = ctx.reduced
    if red is None or red.busy_s <= 0 or not any(re.search(ALL_TO_ALL, n) for n in red.op_s):
        return None
    return 100.0 * red.op_time(ALL_TO_ALL) / red.busy_s
