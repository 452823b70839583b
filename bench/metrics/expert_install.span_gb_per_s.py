"""Expert install rate, host to HBM, from the program's own spans: the
``bytes`` of the ``expert_cache.install`` spans over the union of those
spans and the chip's runs of the slot write (``jit__set_slot``). Spans
and writes are taken by one rule: each that overlaps the traced window,
clipped to it, a span's bytes scaled by the share of it inside. The
spans hold the host's fetch, hand-over and its wait inside the donated
write. Prints the bytes of the spans that start in the window beside
the expert caches' ``bytes_transferred`` delta over the same window."""
import sys

import profile_reduce

SPAN = "expert_cache.install"
WRITE = "jit__set_slot("


def read(ctx):
    prof = ctx.profile
    if prof is None or not prof.device:
        return None
    t0, t1 = prof.window
    started = [e for e in prof.host
               if e.name == SPAN and t0 <= e.start < t1]
    print(f"install spans in the traced window: {len(started)}, "
          f"{sum(int(e.stats.get('bytes', 0)) for e in started)} bytes; "
          f"the expert caches' bytes_transferred delta "
          f"{ctx.traced.counts['bytes']}", file=sys.stderr, flush=True)
    spans = [e for e in prof.host
             if e.name == SPAN and e.end > t0 and e.start < t1]
    if not spans:
        return None
    nbytes = sum(int(e.stats.get("bytes", 0))
                 * (prof.clipped(e) / e.dur if e.dur > 0 else 1.0)
                 for e in spans)
    writes = [e for e in prof.other if e.line == profile_reduce.MODULES_LINE
              and e.name.startswith(WRITE) and e.end > t0 and e.start < t1]
    iv = profile_reduce.merge([(max(e.start, t0), min(e.end, t1))
                               for e in spans + writes])
    return nbytes / sum(b - a for a, b in iv)
