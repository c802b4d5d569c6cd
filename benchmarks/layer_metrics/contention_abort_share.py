"""Counters: transactions the protocol refused for contention (lock
rejected, OCC validation failed) over those attempted, in percent. An
answer the protocol gives, not a failure; a change that trades commits
for aborts shows here."""


def read(ctx):
    t = ctx["totals"]
    return 100.0 * (t["ab_lock"] + t["ab_validate"]) / t["attempted"]
