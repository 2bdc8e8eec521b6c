"""Mean duration, in ms, of the traced window's `datapath.stack` spans:
the host's np.stack of a launch's pairs.  Recorded by
PersistentPairDispatcher.submit; read by benchmark/program_trace.py.
Moves verdicts_per_s (n110.replay)."""

from benchmark import program_trace as P


def read(ctx):
    pt = P.for_run(ctx)
    return None if pt is None else pt.span_ms("datapath.stack")
