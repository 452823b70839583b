"""Share of the traced window in which the chip runs nothing while the
innermost program span on the host is ``engine.decode``: the engine
call's own code outside its layers' spans (embedding, layer slices,
prefetch guesses, the cost-model clock). One of the six parts of
``device.idle.step_other_pct`` (``span_reduce.idle_ns_by_span``)."""
import span_reduce

SPAN = "engine.decode"


def read(ctx):
    return span_reduce.idle_pct(ctx.profile, lambda name: name == SPAN)
