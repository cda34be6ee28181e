"""Coverage-guided adaptive exploration (the observe→steer loop).

PRs 13–18 built the observability stack — the exploration ledger's
coverage bitmaps and termination classification, the static reachable-edge
oracle, solver-hotspot attribution — but nothing acted on any of it.
This package closes the loop:

* :mod:`.plan` — the pure planner: ledger snapshots in, a
  :class:`~mythril_tpu.adaptive.plan.SteeringPlan` out (slot-budget
  weights biased at uncovered reachable edges, a requeue list for
  ``budget_exhausted`` parks, ranked concolic flip targets, per-code
  plateau verdicts).
* :mod:`.controller` — the process-wide actuation state: the throttled
  plan cache, the deterministic deficit scheduler the frontier consults
  at sync points, the ``--coverage-target`` stop verdict, and the
  ``adaptive.*`` counters.

``--no-adaptive`` disables every actuation site; the steering is a
strict scheduling optimization, so the issue set is bit-identical either
way (``tests/adaptive/test_parity.py`` asserts it).
"""

from mythril_tpu.adaptive.controller import (
    AdaptiveController,
    get_adaptive_controller,
)
from mythril_tpu.adaptive.plan import (
    SteeringPlan,
    build_plan,
    plateau_verdict,
    rank_flip_targets,
    requeue_candidates,
    steer_weights,
    uncovered_reachable,
)

__all__ = [
    "AdaptiveController",
    "SteeringPlan",
    "build_plan",
    "get_adaptive_controller",
    "plateau_verdict",
    "rank_flip_targets",
    "requeue_candidates",
    "steer_weights",
    "uncovered_reachable",
]
