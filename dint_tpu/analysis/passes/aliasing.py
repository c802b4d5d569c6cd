"""Aliasing/donation pass: in-place buffers must really be dead.

Two machineries update tables in place: Pallas kernels with
``input_output_aliases`` and jitted steps with ``donate_argnums`` (every
runner donates its carry so HBM tables update in place). Both are unchecked
promises at the JAX level on the paths we care about: read the donated
buffer after the call and you observe torn state — exactly the
use-after-free class the reference avoids by construction with its
in-kernel single-writer discipline.

Checks, per eqn:
  * pallas_call input_output_aliases:
      - the same input aliased to two outputs, or two inputs to one
        output -> ERROR double-alias (two writers, one buffer);
      - aliased input/output shape+dtype mismatch -> ERROR;
      - the aliased input var read again by a LATER eqn in the enclosing
        jaxpr (or escaping as an output) -> ERROR use-after-donate.
  * pjit with donated_invars:
      - a donated operand read again later / escaping -> ERROR
        use-after-donate;
      - the same var passed both as a donated and a second operand of the
        one call -> ERROR double-alias (the callee sees its input change
        under it when XLA reuses the buffer).
"""
from __future__ import annotations

from ..core import (Finding, SEV_ERROR, TargetTrace, register_pass,
                    site_of, used_after, walk)


def _var_positions(invars):
    pos: dict = {}
    for i, v in enumerate(invars):
        pos.setdefault(id(v), []).append(i)
    return pos


@register_pass("aliasing")
def aliasing(trace: TargetTrace) -> list[Finding]:
    """Cross-checks input_output_aliases / donate_argnums for
    use-after-donate and double-alias hazards."""
    out: list[Finding] = []
    for ctx in walk(trace):
        eqn, site, path = ctx.eqn, site_of(ctx.eqn), "/".join(ctx.path)

        if ctx.prim == "pallas_call":
            ioa = tuple(eqn.params.get("input_output_aliases") or ())
            in_seen: dict[int, int] = {}
            out_seen: dict[int, int] = {}
            for in_idx, out_idx in ioa:
                if in_idx in in_seen:
                    out.append(Finding(
                        "aliasing", "double-alias-input", SEV_ERROR,
                        trace.name,
                        f"pallas_call aliases input {in_idx} to outputs "
                        f"{in_seen[in_idx]} and {out_idx}: two in-place "
                        "writers share one buffer",
                        primitive=ctx.prim, site=site, path=path))
                if out_idx in out_seen:
                    out.append(Finding(
                        "aliasing", "double-alias-output", SEV_ERROR,
                        trace.name,
                        f"pallas_call aliases inputs {out_seen[out_idx]} "
                        f"and {in_idx} to the same output {out_idx}",
                        primitive=ctx.prim, site=site, path=path))
                in_seen.setdefault(in_idx, out_idx)
                out_seen.setdefault(out_idx, in_idx)
                if in_idx >= len(eqn.invars) or out_idx >= len(eqn.outvars):
                    continue
                iv, ov = eqn.invars[in_idx], eqn.outvars[out_idx]
                ia, oa = iv.aval, ov.aval
                if (getattr(ia, "shape", None) != getattr(oa, "shape", None)
                        or getattr(ia, "dtype", None)
                        != getattr(oa, "dtype", None)):
                    out.append(Finding(
                        "aliasing", "alias-shape-mismatch", SEV_ERROR,
                        trace.name,
                        f"pallas_call alias {in_idx}->{out_idx} pairs "
                        f"{ia.str_short()} with {oa.str_short()}: in-place "
                        "reuse needs identical shape+dtype",
                        primitive=ctx.prim, site=site, path=path))
                use = used_after(ctx.jaxpr, iv, ctx.index)
                if use:
                    out.append(Finding(
                        "aliasing", "use-after-donate", SEV_ERROR,
                        trace.name,
                        f"buffer donated to pallas_call via "
                        f"input_output_aliases ({in_idx}->{out_idx}) is "
                        f"still live: {use}; the kernel updated it in "
                        "place, so the later read observes torn state",
                        primitive=ctx.prim, site=site, path=path,
                        suggestion="thread the kernel's OUTPUT to the "
                                   "later use, or drop the alias"))

        elif ctx.prim == "jit":
            donated = eqn.params.get("donated_invars") or ()
            if not any(donated):
                continue
            pos = _var_positions(eqn.invars)
            for i, (is_don, iv) in enumerate(zip(donated, eqn.invars)):
                if not is_don:
                    continue
                use = used_after(ctx.jaxpr, iv, ctx.index)
                if use:
                    out.append(Finding(
                        "aliasing", "use-after-donate", SEV_ERROR,
                        trace.name,
                        f"operand {i} of jitted call "
                        f"`{eqn.params.get('name', '?')}` is donated "
                        f"(donate_argnums) but still live: {use}",
                        primitive=ctx.prim, site=site, path=path,
                        suggestion="use the call's returned (updated) "
                                   "value, or un-donate the argument"))
                dup = [j for j in pos.get(id(iv), []) if j != i]
                if dup:
                    out.append(Finding(
                        "aliasing", "donated-operand-duplicated", SEV_ERROR,
                        trace.name,
                        f"operand {i} of `{eqn.params.get('name', '?')}` "
                        f"is donated but the same buffer is also passed as "
                        f"operand(s) {dup}: the callee can observe its own "
                        "in-place writes through the second name",
                        primitive=ctx.prim, site=site, path=path))
    return out
