"""Share of the traced replay window in which the device is idle while
the host is inside `datapath.stack`, `datapath.upload` or
`datapath.enqueue`: the host staging a launch before the program runs
(benchmark/program_trace.py).  Moves verdicts_per_s (n110.replay)."""

from benchmark import program_trace as P


def read(ctx):
    pt = P.for_run(ctx)
    return None if pt is None else pt.idle_under(P.STAGING)
