"""One file per kind of deployment. A configuration names its kind
(``"deployment"`` in benchmarks/configs/<config>.json) and run.py imports
``benchmarks.deployments.<kind>`` and calls its ``build``.

``build(config, params, seed, devices, emit, rehearse)`` returns an
object with what a loop and run.py need, and nothing engine-specific:

  stat_names          names of the columns of a stats array
  txns_per_dispatch   transactions one dispatch attempts (all devices)
  steps_per_dispatch  engine steps in one dispatch
  depth               steps from a cohort's dispatch to its outcome
  n_devices, geometry (shapes for bytes_model)
  start() -> carry                 a fresh pipeline over the live state
  dispatch(carry, key) -> (carry, stats on the device)   donates carry
  drain(carry) -> (final, stats)   flushes the pipeline
  verify(final, checks, tag, totals, dispatched) -> counter snapshot
  restart(final) -> carry          a fresh pipeline over the drained state
"""
