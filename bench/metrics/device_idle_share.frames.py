"""Share of the window in which the chip ran no operation, in percent
(averaged over the chips), in the cells that report ``frames_per_s``."""


def read(ctx):
    red = ctx.reduced
    return None if red is None else 100.0 * red.idle_share
