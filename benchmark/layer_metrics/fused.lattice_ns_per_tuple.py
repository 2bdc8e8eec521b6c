"""Device nanoseconds per tuple of the persistent fused pair program (XLA
module `jit_program`) under the stage scope `lattice` (the policy
probes and their combine), over the launches and tuples of
fused.device_ns_per_tuple (benchmark/program_trace.py).  Moves
verdicts_per_s (n110.replay)."""

from benchmark import program_trace as P


def read(ctx):
    stages = P.stages_per_tuple(ctx)
    return None if stages is None else stages["lattice"]
