"""The compiled MLA decode attention core (``mla_attend_paged``: the
gather of each row's latent and rope-key strip, the scores, the softmax
and the context in the latent space) against its roofline: the mean
least time of a call in the traced window (each layer of each step: the
step's rows over its block-table width; the model module's
``mla_attend_call``) over the mean device time of a run of the
program. Nothing to read where the program has no such program or the
model module no such count."""
import flops
import profile_reduce

FUNCTION = "mla_attend_paged"


def read(ctx):
    prof = ctx.profile
    count = getattr(ctx.model, "mla_attend_call", None)
    if prof is None or count is None or not ctx.traced.shapes:
        return None
    ns, n = profile_reduce.module_ns(prof, FUNCTION)
    if not n:
        return None
    least = [flops.least_time(*count(rows, width, ctx.block_size, ctx.dims),
                              ctx.peaks)[0]
             for width, rows in ctx.traced.shapes]
    return 100.0 * (sum(least) / len(least)) / (ns * 1e-9 / n)
