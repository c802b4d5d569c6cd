"""Counters: transactions the protocol refused for contention (the
deployment's ``contention`` outcomes: for TATP, lock rejected and OCC
validation failed) over those attempted, in percent. An answer the
protocol gives, not a failure; a change that trades commits for aborts
shows here."""


def read(ctx):
    t = ctx["totals"]
    return 100.0 * sum(t[n] for n in ctx["contention"]) / t["attempted"]
