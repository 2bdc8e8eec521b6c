"""Device nanoseconds per tuple of the persistent fused pair program
(XLA module `jit_program`) outside the five stages with a metric of
their own: the unpack, prefilter and verdict scopes, operations with
no stage scope, and module time between operations.  The six
fused.*_ns_per_tuple readings sum to fused.device_ns_per_tuple
(benchmark/program_trace.py).  Moves verdicts_per_s (n110.replay)."""

from benchmark import program_trace as P


def read(ctx):
    stages = P.stages_per_tuple(ctx)
    return None if stages is None else stages["other"]
