"""Share of the traced window in which the chip runs nothing while the
host is inside the program's serving step but in none of the spans the
four other buckets read: the self time of ``server.step``,
``server.schedule``, ``server.sample``, ``engine.decode``, ``engine.moe``
and ``engine.logits`` (``span_reduce.idle_ns_by_span``). It is the sum
of ``device.idle.{schedule,sample,logits,step_self,decode_self,
moe_self}_pct``, which name each of those spans alone."""
import span_reduce

BUCKETED = ("engine.attention", "engine.route", "expert_cache.install",
            "engine.ffn")


def read(ctx):
    return span_reduce.idle_pct(
        ctx.profile, lambda name: name is not None and name not in BUCKETED)
