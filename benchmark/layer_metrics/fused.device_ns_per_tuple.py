"""Device nanoseconds per tuple of the persistent fused pair program
(engine.datapath.persistent_pair_program, XLA module `jit_program`):
its summed device time in the traced window over the tuples of the
launches it ran there.  Moves verdicts_per_s (n110.replay)."""

MODULE = "jit_program"


def read(ctx):
    if ctx.reduced is None:
        return None
    ns, launches = ctx.reduced.program_ns(MODULE)
    if launches == 0:
        return None
    return ns / (launches * ctx.loop.tuples_per_launch)
