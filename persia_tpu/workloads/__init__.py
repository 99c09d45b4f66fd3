"""Workload zoo: production-shaped, seed-deterministic scenarios wired
as first-class bench drivers (ROADMAP item 5).

See :mod:`persia_tpu.workloads.generator` for the data layer,
:mod:`persia_tpu.workloads.models` for the dense towers, and
:mod:`persia_tpu.workloads.registry` for the scenario registry
(``dlrm``, ``seqrec``, ``multitask``).
"""

from persia_tpu.workloads.registry import (
    Scenario,
    evaluate_auc,
    get_scenario,
    register_scenario,
    scenario_names,
)

__all__ = [
    "Scenario",
    "evaluate_auc",
    "get_scenario",
    "register_scenario",
    "scenario_names",
]
