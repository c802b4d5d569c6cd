"""Counters: generated lock requests whose owner is another device than
their source (``remote_lock_lanes``) over all lock requests, in percent:
~75 % on four devices, whatever the mix. None where the program has no
such counter."""


def read(ctx):
    c = ctx["counters"] or {}
    if "remote_lock_lanes" not in c or not c.get("lock_requests"):
        return None
    return 100.0 * c["remote_lock_lanes"] / c["lock_requests"]
