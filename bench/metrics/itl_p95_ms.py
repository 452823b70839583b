"""95th percentile, in ms, of every gap between consecutive output
tokens of every request, both tokens inside the window (host clock,
taken when the step that produced each token returned)."""
import numpy as np

MIN_GAPS = 20


def read(ctx):
    gaps = [b - a for ts in ctx.times.values() for a, b in zip(ts, ts[1:])
            if a >= ctx.t_start and b <= ctx.t_end]
    if len(gaps) < MIN_GAPS:
        return None
    return 1000.0 * float(np.percentile(gaps, 95))
