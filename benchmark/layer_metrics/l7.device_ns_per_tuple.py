"""Device nanoseconds per tuple of the L7 program of the persistent
launch path (l7.fleet.fleet_l7_program, XLA module `jit_l7_program`,
chained after the fused program): its summed device time in the
traced window over the tuples of the launches it ran there.  Moves
verdicts_per_s (l7gw.replay)."""

MODULE = "jit_l7_program"


def read(ctx):
    if ctx.reduced is None:
        return None
    ns, launches = ctx.reduced.program_ns(MODULE)
    if launches == 0:
        return None
    return ns / (launches * ctx.loop.tuples_per_launch)
