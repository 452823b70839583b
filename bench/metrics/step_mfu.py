"""Model FLOP/s utilization of the serving step, over the untraced
window: tokens processed (active rows: prompt chunks and decode) per
second on the host clock, times the model's FLOPs per token at the
window's mean context, over the chips' bf16 peak."""


def read(ctx):
    tokens = sum(n for _, _, n in ctx.steps)
    if not tokens or ctx.mean_context is None:
        return None
    per_token = ctx.model.token_flops(ctx.cfg, ctx.mean_context)
    rate = tokens / ctx.window_s
    return 100.0 * rate * per_token / (ctx.peaks["bf16_flops_per_s"]
                                       * ctx.chips)
