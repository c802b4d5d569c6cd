"""dintmon: device-resident counter plane + host-side wave tracing.

The reference is observable by construction — every eBPF hot path bumps
per-CPU map counters (lock_kern.c's grant/reject counters, ls_kern.c's
ring heads) and every Caladan client prints the same metric block
(client_ebpf_shard.cc:368-377), which is what made its performance claims
auditable. Our engines run whole transaction pipelines inside one jitted
step, so everything between dispatch and the stats vector — lock
arbitration outcomes, validation failures, replication pushes, log-ring
occupancy — was invisible to the host.

This package is the TPU re-expression of those per-CPU counter maps:

* `counters` — a fixed registry of counter IDs and a `Counters` pytree of
  u32 device arrays threaded through engine state. Engines increment it
  IN-STEP with unique-index scatter-adds (never `io_callback`, so the
  dintlint purity pass stays clean) and the host drains it only at window
  boundaries — one ~100-byte fetch per block, zero extra dispatches.
* `trace` — the host half: wave-event JSONL emission (schema-stable),
  Chrome-trace export, and the `jax.profiler` session hook used by
  bench.py / exp.py.

* `waves` / `attrib` — dintscope, the TIMING half (round 11): the
  append-only wave-name registry behind the engines'
  `jax.named_scope("dint.<engine>.<wave>")` annotations, and the
  attribution that turns a `jax.profiler` trace (+ the JSONL stream)
  into a per-wave time breakdown. `tools/dintscope.py` is its CLI and
  `diff` its perf-regression gate.

Monitoring is OFF by default and adds nothing to the traced step when off
(the builders thread no counter state and engine outputs stay
bit-identical; the named scopes add no jaxpr equations either way).
`tools/dintmon.py` is the CLI; OBSERVABILITY.md documents the registry,
the event schema, and the dintlint interaction.
"""
from __future__ import annotations

from .counters import (ALL_NAMES, COUNTER_DOCS, COUNTER_INDEX,  # noqa: F401
                       COUNTER_KINDS, FLOW_NAMES, GAUGE_NAMES, N_COUNTERS,
                       PARITY_NAMES, Counters, bump, counters_enabled,
                       create, delta, gauge_max, snapshot, zeros_dict)
from .counters import (CTR_STEPS, CTR_TXN_ATTEMPTED,  # noqa: F401
                       CTR_TXN_COMMITTED, CTR_AB_LOCK, CTR_AB_MISSING,
                       CTR_AB_VALIDATE, CTR_AB_LOGIC, CTR_MAGIC_BAD,
                       CTR_LOCK_REQUESTS, CTR_LOCK_GRANTED,
                       CTR_LOCK_REJECTED, CTR_LOCK_REJECT_HELD,
                       CTR_LOCK_REJECT_ARB, CTR_VALIDATE_LANES,
                       CTR_VALIDATE_FAILED, CTR_INSTALL_WRITES,
                       CTR_LOG_APPENDS, CTR_REPL_PUSH_HOP1,
                       CTR_REPL_PUSH_HOP2, CTR_ROUTE_OVERFLOW,
                       CTR_RING_HWM, CTR_TRACE_DROPPED,
                       CTR_SERVE_OCC_LANES, CTR_SERVE_PAD_LANES,
                       CTR_SERVE_SHED_LANES)
from .trace import (Monitor, TraceWriter, export_chrome_trace,  # noqa: F401
                    profiler_session, read_events)
# dintscope (the timing half): wave registry + trace attribution — import
# as modules so the counter namespace above stays flat and unambiguous
from . import attrib, waves  # noqa: F401, E402
# dinttrace (the narration half): per-txn event ring + span assembler —
# module imports for the same reason
from . import txnevents, txntrace  # noqa: F401, E402
