"""Share of the traced window in which the chip runs nothing while the
host is in no program span: the harness between steps
(``span_reduce.idle_ns_by_span``). With the five other
``device.idle.*`` buckets it sums to ``device.idle_pct``."""
import span_reduce


def read(ctx):
    return span_reduce.idle_pct(ctx.profile, lambda name: name is None)
