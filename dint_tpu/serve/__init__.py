"""dintserve: the always-on serving plane (round 17).

Batch certification becomes a service: open-loop arrival schedules
(`arrivals`) fill variable-occupancy cohorts, `ServeEngine` pumps them
through the pre-compiled dense engines depth-k deep with zero
steady-state allocation, and the SLO controller (`controller`) adapts
cohort width among a registered menu and sheds — never stalls — past
saturation. `tools/dintserve.py` is the CLI; exp.py's serve sweep emits
the latency-vs-offered-load artifact with exact queue/service
attribution. Round 18 adds `MeshServeEngine` (mesh.py): the whole 2-D
(dcn x ici) mesh as one open-loop service — per-host admission feeding
one global controller, width switches coordinated mesh-wide at drain
boundaries, and the optional double-buffered (overlap) route.
"""
from __future__ import annotations

from .arrivals import (ArrivalStream, burst_schedule,  # noqa: F401
                       constant_schedule, make_schedule, poisson_schedule)
from .controller import (ControllerCfg, ServiceModel,  # noqa: F401
                         WidthController, choose_width, max_backlog,
                         simulate_widths)
from .engine import (RealClock, ServeEngine, VirtualClock,  # noqa: F401
                     cached_runner)
from .mesh import MeshServeEngine                      # noqa: F401
