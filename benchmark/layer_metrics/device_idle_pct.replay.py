"""Share of the traced replay window in which no operation ran on the
device.  Moves verdicts_per_s (n110.replay)."""


def read(ctx):
    if ctx.reduced is None or ctx.reduced.n_devices == 0:
        return None
    return 100.0 * ctx.reduced.idle_share
