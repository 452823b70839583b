"""Share of the traced window in which the chip runs nothing while the
innermost program span on the host is ``expert_cache.install`` (one
expert's fetch from the host store, hand-over and slot writes;
``span_reduce.idle_ns_by_span``)."""
import span_reduce

SPAN = "expert_cache.install"


def read(ctx):
    return span_reduce.idle_pct(ctx.profile, lambda name: name == SPAN)
