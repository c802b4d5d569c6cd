"""Device trace: ms per engine step of the exchange of
parallel/dense_sharded_sb.py, mean over devices: everything under the
waves ``dint.dense_sharded_sb.route`` and ``.reply`` and, under
``.install_route``, the parts that route (``route_addr``, ``a2a_rank``,
``a2a_pack``, ``a2a_installs``): the arrival ranks, the bucket scatters,
the nine all_to_alls and the replies' unpack. What a transaction pays for
its rows living on other devices, the collectives' own time included.
None where the trace has no parts or lacks one of the three waves."""
from benchmarks import part_times

WAVE = "dint.dense_sharded_sb."
ROUTING = ("route_addr", "a2a_rank", "a2a_pack", "a2a_installs")


def read(ctx):
    found = part_times.read(ctx)
    waves = found["by_wave"] if found else {}
    if not all(WAVE + w in waves for w in ("route", "reply",
                                           "install_route")):
        return None
    return (sum(waves[WAVE + "route"].values())
            + sum(waves[WAVE + "reply"].values())
            + sum(ms for part, ms in waves[WAVE + "install_route"].items()
                  if part in ROUTING))
