"""Share of the traced window in which the chip runs nothing while the
innermost program span on the host is ``server.schedule``: the step's
admission, chunk plan, KV pages, row layout and block-table upload. One
of the six parts of ``device.idle.step_other_pct``
(``span_reduce.idle_ns_by_span``)."""
import span_reduce

SPAN = "server.schedule"


def read(ctx):
    return span_reduce.idle_pct(ctx.profile, lambda name: name == SPAN)
