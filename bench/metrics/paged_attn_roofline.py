"""Pallas paged decode attention against its roofline: the mean least
time of a call in the traced window (each layer of each step: the
step's rows over its block-table width; ``flops.paged_attention_call``)
over the mean device time of a run of the kernel's program."""
import flops
import profile_reduce

FUNCTION = "paged_attention"


def read(ctx):
    prof = ctx.profile
    if prof is None or not ctx.traced.shapes:
        return None
    ns, n = profile_reduce.module_ns(prof, FUNCTION)
    if not n:
        return None
    d = ctx.dims
    least = [flops.least_time(*flops.paged_attention_call(
        rows, width, ctx.block_size, d.H, d.KV, d.hd), ctx.peaks)[0]
        for width, rows in ctx.traced.shapes]
    return 100.0 * (sum(least) / len(least)) / (ns * 1e-9 / n)
