"""Share of the traced window in which the chip runs nothing while the
innermost program span on the host is ``server.sample``: one request's
argmax and the read back of its token. One of the six parts of
``device.idle.step_other_pct`` (``span_reduce.idle_ns_by_span``)."""
import span_reduce

SPAN = "server.sample"


def read(ctx):
    return span_reduce.idle_pct(ctx.profile, lambda name: name == SPAN)
