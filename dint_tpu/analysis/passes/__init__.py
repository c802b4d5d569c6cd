"""dintlint pass registry: importing this package registers every pass.

Each module encodes ONE invariant of the engine/sharded hot paths as an
eqn-level predicate over the traced jaxpr (see analysis/core.py for the
walking machinery and ANALYSIS.md for the invariant catalogue):

  scatter_race       one writer per table row, provably
  aliasing           donated / input_output_aliased buffers are dead
  purity             a step is one pure device program
  u64_overflow       packed stamps stay unsigned 32-bit
  shard_consistency  collectives agree with the mesh
  protocol           lock-dominates-write / validate-before-install /
                     abort-implies-unlock / commit-after-replication,
                     proven by the dataflow layer (analysis/dataflow.py)
  cost_budget        derived bytes/dispatches/footprint reconcile with
                     the waves.py ledger and stay under the registered
                     budgets (analysis/cost.py — the dintcost gate)
  durability         log-before-visible, replica quorum on distinct
                     fault domains, bounded rings, replay coverage,
                     in-doubt totality (analysis/dataflow.py's LOGGED/
                     TRUNCATED facts — the dintdur gate)
  plan_check         the pinned PLAN.json agrees with the knob registry,
                     the calibration ledger and the dintcost-derived
                     frontier; env flags cannot contradict it silently
                     (analysis/plan.py — the dintplan gate)
  calib_check        the pinned CALIB.json reproduces its own fit from
                     the embedded samples, its provenance hashes hold,
                     and the plan's serve rows were priced with the
                     model the resolver picks now (monitor/calib.py —
                     the dintcal gate)
  mut_check          the pinned MUTCOV.json (machine-generated engine
                     mutants vs the pass matrix) stays provenance-true,
                     clears the kill-rate floor, triages every
                     survivor, and attributes kills to every gate
                     family (analysis/mutate.py — the dintmut gate)

Adding a pass: write `passes/<name>.py`, decorate the entry point with
`@core.register_pass("<name>")`, import it here.
"""
from . import (aliasing, calib_check, cost_budget,  # noqa: F401
               durability, mut_check, plan_check, protocol, purity,
               scatter_race, shard_consistency, u64_overflow)
