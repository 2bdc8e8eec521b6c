"""Share of the traced replay window in which the device is idle while
the host is inside `datapath.outputs`: the per-pair slices of a
launch's outputs (benchmark/program_trace.py).  Moves verdicts_per_s
(n110.replay)."""

from benchmark import program_trace as P


def read(ctx):
    pt = P.for_run(ctx)
    return None if pt is None else pt.idle_under(P.OUTPUTS)
