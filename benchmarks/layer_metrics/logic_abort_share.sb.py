"""Counters: transactions the window's data refused (``ab_logic``:
insufficient funds) over those attempted, in percent. Not contention: it
grows with the steps run since the tables were populated, and bounds how
far ``committed_txn_per_s`` moved for that reason alone. None for a
deployment without that outcome."""


def read(ctx):
    t = ctx["totals"]
    if "ab_logic" not in t:
        return None
    return 100.0 * t["ab_logic"] / t["attempted"]
