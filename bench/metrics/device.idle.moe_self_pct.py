"""Share of the traced window in which the chip runs nothing while the
innermost program span on the host is ``engine.moe``: a layer's MoE code
outside routing, installs and FFN chunks (the norm, the union of
experts, the cache lookups, the residual add). One of the six parts of
``device.idle.step_other_pct`` (``span_reduce.idle_ns_by_span``)."""
import span_reduce

SPAN = "engine.moe"


def read(ctx):
    return span_reduce.idle_pct(ctx.profile, lambda name: name == SPAN)
