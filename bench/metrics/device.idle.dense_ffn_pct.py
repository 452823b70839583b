"""Share of the traced window in which the chip runs nothing while the
innermost program span on the host is ``engine.dense_ffn`` (a dense
layer's FFN, one compiled call; ``span_reduce.idle_ns_by_span``).
Nothing to read in a trace without that span."""
import span_reduce

SPAN = "engine.dense_ffn"


def read(ctx):
    return span_reduce.idle_pct(ctx.profile, lambda name: name == SPAN)
