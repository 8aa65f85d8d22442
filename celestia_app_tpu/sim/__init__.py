"""The scenario plane: a virtual-time, seeded, in-process simulation
engine for tens of validators plus hundreds of DASer light nodes.

- scheduler.py — the seeded discrete-event scheduler driving ONE
  VirtualClock (utils/clock.py): same seed ⇒ byte-identical event trace.
- engine.py — the world: SimTransport (a direct-call peer transport over
  the real das/server + header routes), SimValidator (an event-driven
  Tendermint round machine over chain/consensus.ValidatorNode),
  SimLightNode (a real das/daser.DASer swept on the virtual timeline),
  and Simulation, which wires them and computes verdict metrics.
- scenarios.py — the declarative adversarial scenario library (dict/JSON
  specs -> faults + topology ops) and ``run_scenario``, the entry of
  the tier-1 matrix.

docs/DESIGN.md "The scenario plane" is the normative description;
docs/FORMATS.md §19 holds the spec grammar and the verdict's schema.
"""

from celestia_app_tpu.sim.scenarios import (  # noqa: F401
    SCENARIOS,
    run_scenario,
    scenario_spec,
)
from celestia_app_tpu.sim.scheduler import Scheduler  # noqa: F401
