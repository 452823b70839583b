"""Expert install rate, host to HBM: the traced window's delta of the
expert caches' ``bytes_transferred`` over the time in that window in
which some part of an install was under way, as the device trace shows
it (``profile_reduce.install_ns``: the host's layout transposes, the
hand-overs they run inside and their transfers to the device, and the
chip's slot writes)."""
import profile_reduce


def read(ctx):
    prof, w = ctx.profile, ctx.traced
    if prof is None or not w.counts["bytes"]:
        return None
    ns, writes = profile_reduce.install_ns(prof)
    return w.counts["bytes"] / ns if writes else None
