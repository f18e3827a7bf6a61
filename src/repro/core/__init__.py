# The paper's primary contribution: PerFedS² — semi-synchronous
# personalized federated averaging with joint bandwidth allocation + UE
# scheduling.
from repro.core.bandwidth import lambertw, optimal_bandwidth
from repro.core.convergence import fosp_bound, step_condition
from repro.core.perfed import (
    adapt,
    perfed_grad,
    perfed_grad_exact,
    perfed_loss,
    perfed_value_and_grad,
)
from repro.core.scheduler import (
    estimate_A_K,
    greedy_schedule,
    relative_frequencies,
)

__all__ = [
    "adapt",
    "estimate_A_K",
    "fosp_bound",
    "greedy_schedule",
    "lambertw",
    "optimal_bandwidth",
    "perfed_grad",
    "perfed_grad_exact",
    "perfed_loss",
    "perfed_value_and_grad",
    "relative_frequencies",
    "step_condition",
]
