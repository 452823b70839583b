"""Output tokens produced in the window over the window's seconds (host
clock; the window closes at the end of the first step past its
length)."""


def read(ctx):
    n = sum(1 for ts in ctx.times.values() for t in ts
            if ctx.t_start < t <= ctx.t_end)
    return n / ctx.window_s if n else None
