"""Counters: generated transactions whose lock set names rows of more
than one owner device (``xshard_txns``, counted at the source) over
those attempted, in percent. Set by the traffic and the partition (~30 %
under the reference's mix and ``account % 4``), not by the program's
speed: the share of the work that is distributed. None where the program
has no such counter."""


def read(ctx):
    c = ctx["counters"] or {}
    if "xshard_txns" not in c:
        return None
    return 100.0 * c["xshard_txns"] / ctx["totals"]["attempted"]
