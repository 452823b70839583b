"""Share of the traced window in which the chip runs nothing while the
innermost program span on the host is ``engine.shared`` (an MoE layer's
shared experts, one compiled call; ``span_reduce.idle_ns_by_span``)."""
import span_reduce

SPAN = "engine.shared"


def read(ctx):
    return span_reduce.idle_pct(ctx.profile, lambda name: name == SPAN)
