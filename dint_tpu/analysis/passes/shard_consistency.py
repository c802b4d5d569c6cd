"""shard_map consistency pass: collectives must agree with the mesh.

The multi-chip paths (parallel/dense_sharded*.py) are correct only if the
ICI traffic they emit matches the mesh they run on: the CommitBck fan-out
`ppermute`s install records to devices d+1 and d+2 over the shard axis,
and the 2PC vote `psum` reduces over that same axis. A permutation built
for the wrong device count silently drops or duplicates replicas — the
backup tables diverge and recovery from a backup log reconstructs the
wrong state, with no error anywhere at runtime.

Checks, walking shard_map bodies with the eqn's mesh in scope:
  * any collective (`psum`, `ppermute`, `all_gather`, `all_to_all`,
    `reduce_scatter`, `pmin`/`pmax`, `axis_index`, ...) naming an axis not
    in the innermost mesh -> ERROR unknown-axis;
  * a collective OUTSIDE any shard_map naming a manual axis -> ERROR
    (it would only be legal under a mesh);
  * `ppermute` perm hygiene against the mesh's axis size: source or
    destination out of range -> ERROR; duplicate destination (two senders
    into one receiver lane: the backend keeps an unspecified one) or
    duplicate source -> ERROR;
  * `shard_map` with `check_vma=False` -> INFO: varying-manual-axes
    typing is off and this pass's axis checks are what remains.
"""
from __future__ import annotations

from ..core import (Finding, SEV_ERROR, SEV_INFO, TargetTrace,
                    register_pass, site_of, walk)

COLLECTIVES = {"psum", "psum2", "pmin", "pmax", "ppermute", "pbroadcast",
               "all_gather", "all_to_all", "reduce_scatter", "pgather",
               "axis_index", "pcast"}


def _axes_of(eqn) -> tuple:
    ax = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if ax is None:
        return ()
    if not isinstance(ax, (tuple, list)):
        ax = (ax,)
    return tuple(a for a in ax if isinstance(a, str))


@register_pass("shard_consistency")
def shard_consistency(trace: TargetTrace) -> list[Finding]:
    """Walks shard_map bodies for collectives whose axis names or
    permutations disagree with the mesh."""
    out: list[Finding] = []
    for ctx in walk(trace):
        eqn, site, path = ctx.eqn, site_of(ctx.eqn), "/".join(ctx.path)

        if ctx.prim == "shard_map":
            if eqn.params.get("check_vma") is False:
                out.append(Finding(
                    "shard_consistency", "check-vma-disabled", SEV_INFO,
                    trace.name,
                    "shard_map runs with check_vma=False: built-in "
                    "varying-axes typing is off, this pass's axis checks "
                    "are the standing substitute",
                    primitive=ctx.prim, site=site, path=path))
            continue

        if ctx.prim not in COLLECTIVES:
            continue
        axes = _axes_of(eqn)
        mesh = ctx.mesh
        mesh_axes = tuple(getattr(mesh, "axis_names", ()) or ())
        if mesh is None:
            if axes:
                out.append(Finding(
                    "shard_consistency", "collective-outside-mesh",
                    SEV_ERROR, trace.name,
                    f"collective `{ctx.prim}` over axis {axes} outside "
                    "any shard_map body: there is no mesh to resolve the "
                    "axis against",
                    primitive=ctx.prim, site=site, path=path))
            continue
        unknown = [a for a in axes if a not in mesh_axes]
        if unknown:
            out.append(Finding(
                "shard_consistency", "unknown-axis", SEV_ERROR, trace.name,
                f"collective `{ctx.prim}` names axis {unknown} but the "
                f"enclosing mesh only has {mesh_axes}",
                primitive=ctx.prim, site=site, path=path,
                suggestion="use parallel/sharded.SHARD_AXIS instead of a "
                           "hand-spelled axis name"))
            continue

        if ctx.prim == "ppermute" and axes:
            try:
                size = int(mesh.shape[axes[0]])
            except Exception:       # noqa: BLE001 — abstract mesh: skip
                continue
            perm = eqn.params.get("perm", ())
            srcs = [int(s) for s, _ in perm]
            dsts = [int(d) for _, d in perm]
            bad = [p for p in perm
                   if not (0 <= int(p[0]) < size and 0 <= int(p[1]) < size)]
            if bad:
                out.append(Finding(
                    "shard_consistency", "perm-out-of-range", SEV_ERROR,
                    trace.name,
                    f"ppermute perm {list(perm)} references device ids "
                    f"outside the `{axes[0]}` axis (size {size}): pairs "
                    f"{bad} never fire, so the replica fan-out silently "
                    "drops installs",
                    primitive=ctx.prim, site=site, path=path,
                    suggestion="build perms from the runner's n_shards "
                               "and assert n_shards == mesh axis size"))
            if len(set(dsts)) != len(dsts):
                out.append(Finding(
                    "shard_consistency", "perm-duplicate-dest", SEV_ERROR,
                    trace.name,
                    f"ppermute perm {list(perm)} sends two sources to one "
                    "destination: the receiver keeps an unspecified one — "
                    "a replica-divergence race",
                    primitive=ctx.prim, site=site, path=path))
            if len(set(srcs)) != len(srcs):
                out.append(Finding(
                    "shard_consistency", "perm-duplicate-src", SEV_ERROR,
                    trace.name,
                    f"ppermute perm {list(perm)} lists a source twice: "
                    "duplicate sends race on the destination buffer",
                    primitive=ctx.prim, site=site, path=path))
    return out
