"""Mean duration, in ms, of the traced window's `datapath.upload` spans:
the host's device_put call for a launch's stacked pairs (an
asynchronous transfer may outlast it).  Recorded by
PersistentPairDispatcher.submit; read by benchmark/program_trace.py.
Moves verdicts_per_s (n110.replay)."""

from benchmark import program_trace as P


def read(ctx):
    pt = P.for_run(ctx)
    return None if pt is None else pt.span_ms("datapath.upload")
