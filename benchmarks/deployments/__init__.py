"""One file per kind of deployment. A configuration names its kind
(``"deployment"`` in benchmarks/configs/<config>.json; run.py reads no
other key of that file) and run.py imports
``benchmarks.deployments.<kind>``. The module is the whole of what the
harness knows of an engine. Its members:

  build(config, params, seed, devices, emit, rehearse) -> the object below
  compare_small(config, seed, checks)
                      the engine against independent code at a small size
                      of the configuration's choosing; the traced run calls
                      it before ``build`` (stage ``compare_small``)
  GUARANTEE_CHECKS    names, without the phase tag, of checks ``verify``
                      makes in every phase (``<phase>.<name>``), non-empty
  COMPARE_CHECKS      names of checks ``compare_small`` makes, non-empty

(tests/bench holds every cell of the manifest to those two tuples.) The
object ``build`` returns has what a loop and run.py need:

  stat_names          names of the columns of a stats array; ``attempted``
                      and ``committed`` are among them
  outcomes            the columns that are lawful answers, one to a
                      transaction (``committed`` and the protocol's aborts)
  faults              the columns that count as failed whatever else holds
  contention          the outcomes another transaction caused
                      (``contention_abort_share``)
                      a column that is none of these is carried in the
                      totals and otherwise ignored
  txns_per_dispatch   transactions one dispatch attempts (all devices)
  steps_per_dispatch  engine steps in one dispatch
  depth               steps from a cohort's dispatch to its outcome
  n_devices, geometry (shapes for bytes_model)
  start() -> carry                 a fresh pipeline over the live state
  dispatch(carry, key) -> (carry, stats on the device)   donates carry
  drain(carry) -> (final, stats)   flushes the pipeline
  verify(final, checks, tag, totals, dispatched) -> counter snapshot
  restart(final) -> carry          a fresh pipeline over the drained state

run.py reckons ``failed = attempted - sum(outcomes) + sum(faults)`` and
makes ``window.nothing_compiled``. benchmarks/checks.py has the parts any
``verify`` can use: ``check_accounting`` (attempted == dispatched, the
accounting closes over ``outcomes``, every fault is zero, some commits,
counters reconcile with stats over a list of pairs), ``check_lock_ledger``,
and the read-back of acknowledged writes from a replica ring
(``plan_readback`` over the deployment's own ``table_rows``, then
``compare_readback`` where the table carries a version word).

What a deployment of a new engine must bring itself, because nothing
here can stand in for it: a ``verify`` that holds the guarantees its
configuration states (every acknowledged write read back from each
replica, at the least), and a ``compare_small`` against code that shares
nothing with the engine under test.
"""
