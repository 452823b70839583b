"""Share of the traced window in which no operation ran on the chip:
1 - the union of the device operations' intervals over the window."""
import profile_reduce


def read(ctx):
    prof = ctx.profile
    if prof is None or not prof.device:
        return None
    w = prof.window[1] - prof.window[0]
    return 100.0 * (1.0 - profile_reduce.busy_ns(prof) / w)
