"""Share (%) of the HBM roofline that the L7 program (XLA module
`jit_l7_program`) reaches: the L7 work of its launches in the traced
window (benchmark/l7_work.py: bytes counted from the requests it
decides, not from the program's layout) over its device time and the
chip's HBM peak.  None for a chip not in l7_work's peaks table, or a
run without the L7 program.  Moves verdicts_per_s (l7gw.replay)."""

MODULE = "jit_l7_program"


def read(ctx):
    import jax

    from benchmark import l7_work

    if ctx.reduced is None or not getattr(ctx.loop, "launch_bytes", None):
        return None
    ns, launches = ctx.reduced.program_ns(MODULE)
    peak = l7_work.peak_bytes_per_s(jax.devices()[0].device_kind)
    if launches == 0 or peak is None:
        return None
    per_launch = sum(ctx.loop.launch_bytes) / len(ctx.loop.launch_bytes)
    return 100.0 * per_launch * launches / (ns / 1e9) / peak
