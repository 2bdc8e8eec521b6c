"""Mean duration, in ms, of the traced window's `datapath.l7` spans:
the host's dispatch of the L7 program after the fused program's call
(PersistentPairDispatcher.submit with an L7 stage); read by
benchmark/program_trace.py.  Moves verdicts_per_s (l7gw.replay)."""

from benchmark import program_trace as P


def read(ctx):
    pt = P.for_run(ctx)
    return None if pt is None else pt.span_ms("datapath.l7")
