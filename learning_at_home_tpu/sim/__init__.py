"""Deterministic whole-system macro-simulator (ISSUE 18).

One process, one virtual clock, thousands of virtual expert servers +
gateways + DHT nodes running the REAL scheduler / admission / routing /
placement code against simulated network latency and compute-time
models.  See docs/SIMULATION.md for the clock-seam contract, the trace
schema, and the simulated-vs-real boundary.

Modules:

- :mod:`~learning_at_home_tpu.sim.clock` — the virtual clock, the seam
  patcher, and the virtual-time asyncio event loop;
- :mod:`~learning_at_home_tpu.sim.trace` — arrival-trace segments
  (poisson / burst / diurnal) + scheduled churn events, shared with
  ``experiments/loadgen.py`` and ``experiments/dht_swarm_sim.py``;
- :mod:`~learning_at_home_tpu.sim.net` — the in-process DHT delivery
  fabric (lifted from ``experiments/dht_swarm_sim.py``);
- :mod:`~learning_at_home_tpu.sim.serving` — virtual expert servers,
  gateways wrapping the real ``SlotScheduler``/``AdmissionController``,
  and the telemetry mirror feeding the real routing cost model;
- :mod:`~learning_at_home_tpu.sim.runner` — scenario orchestration and
  the ``python -m learning_at_home_tpu.sim.runner`` CLI behind the
  collect_gate MACRO_SIM smoke.
"""

from learning_at_home_tpu.sim.clock import (  # noqa: F401
    VirtualClock,
    VirtualClockEventLoop,
    installed_clock,
)
from learning_at_home_tpu.sim.trace import ChurnEvent, Trace, TraceSegment  # noqa: F401
