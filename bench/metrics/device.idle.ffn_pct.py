"""Share of the traced window in which the chip runs nothing while the
innermost program span on the host is ``engine.ffn`` (one union chunk's
gather, combine weights and grouped FFN;
``span_reduce.idle_ns_by_span``)."""
import span_reduce

SPAN = "engine.ffn"


def read(ctx):
    return span_reduce.idle_pct(ctx.profile, lambda name: name == SPAN)
