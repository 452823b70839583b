"""Share of demand expert accesses found resident, from the window's
deltas of the expert caches' ``hits`` and ``misses`` counters."""


def read(ctx):
    h, m = ctx.counts["hits"], ctx.counts["misses"]
    return 100.0 * h / (h + m) if h + m else None
