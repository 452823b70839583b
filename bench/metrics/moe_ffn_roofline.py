"""Grouped expert FFN program (``_grouped_ffn``: the Pallas grouped
SwiGLU kernel, the broadcast of its rows and the combine) against its
roofline: the mean least time of a call in the traced window (each
union chunk of U experts over the step's rows; ``flops.moe_ffn_call``)
over the mean device time of a run of the program."""
import flops
import profile_reduce

FUNCTION = "_grouped_ffn"


def read(ctx):
    prof = ctx.profile
    if prof is None:
        return None
    ns, n = profile_reduce.module_ns(prof, FUNCTION)
    d = ctx.dims
    least = []
    for _, union in ctx.traced.unions:
        for c0 in range(0, union, ctx.slots):
            f, b = flops.moe_ffn_call(min(ctx.slots, union - c0), ctx.rows,
                                      d.d, d.F)
            least.append(flops.least_time(f, b, ctx.peaks)[0])
    if not n or not least:
        return None
    return 100.0 * (sum(least) / len(least)) / (ns * 1e-9 / n)
