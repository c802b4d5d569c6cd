"""Counters: lanes whose key an earlier lane of the same step carries
(``store_dup_lanes``) over the operations attempted, in percent: how much
of a step the same-key serialisation has to resolve. Set by the traffic's
skew and the step's width, not by the program: ~34.5 % at 8,192 lanes of
Zipfian 0.99 over 24 M keys. None where the program has no such
counter."""


def read(ctx):
    dup = ctx["counters"].get("store_dup_lanes")
    if dup is None:
        return None
    return 100.0 * dup / ctx["totals"]["attempted"]
