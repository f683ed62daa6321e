"""Offline schedule planning: predict before you train.

MATCHA's core claim (arXiv:1905.09435, Thm. 2) is that the spectral
contraction rate ρ of the expected mixing matrix predicts consensus — and
therefore convergence — *before* any training step runs.  This package turns
that theory into tooling, closing the loop the repo previously closed only by
burning a full training job per (topology, budget) point
(``benchmarks/budget_sweep.py``):

``spectral``
    Closed-form ρ (the quantity the MATCHA SDP minimizes) plus a Monte-Carlo
    simulator that samples the actual Bernoulli flag stream and tracks
    empirical consensus error under the realized time-varying ``W_t``
    products — including the cross-terms the expectation bound averages over.

``cost``
    Link-cost model: each matching's edges mapped onto the folded
    intra-chip/inter-chip plan (``parallel/gossip.py: build_folded_plan``)
    to predict per-iteration communication cost in hop-weighted units,
    optionally calibrated against committed wall-clock artifacts.

``autotune``
    Budget × topology sweep ranked by predicted wall-clock to a target
    consensus contraction; emits the plan artifact.

``artifact``
    The JSON plan artifact ``train_tpu.py --plan`` consumes: the chosen
    (graph, budget, seed) resolved offline, plus every candidate's
    predictions for provenance.

``verify``
    Compare predicted disagreement decay against a Recorder CSV from a real
    run — the honesty check that keeps the prediction model falsifiable.

``swap``
    The run controller's online re-solve (DESIGN.md §22): a new budget
    mapped onto a *committed* flag stream as first-moment-exact
    per-matching re-weights, executable without a recompile.
"""

from .artifact import PlanArtifact, apply_plan, load_plan, save_plan
from .autotune import plan_candidate, resolve_topology, sweep
from .cost import (
    CostModel,
    calibrate_cost_model,
    expected_comm_units,
    load_measured_comm_times,
    load_measured_link_costs,
    matching_comm_units,
    simulate_fleet_wallclock,
    straggler_step_times,
)
from .spectral import (
    ConsensusSim,
    degraded_contraction_rho,
    degraded_solver_inputs,
    empirical_contraction_rate,
    local_step_breakeven,
    masked_laplacian_expectation,
    normalize_staleness,
    parse_staleness_spec,
    simulate_consensus,
    stale_alpha_rescale,
    stale_contraction_rho,
    staleness_delay_inflation,
    steps_to_consensus,
    wire_disagreement_floor,
    wire_quantization_eps,
)
from .swap import resolve_budget_swap
from .verify import (
    load_fault_ledger,
    load_recorder_disagreement,
    verify_against_recorder,
    verify_plan_run,
)

__all__ = [
    "ConsensusSim",
    "CostModel",
    "PlanArtifact",
    "apply_plan",
    "calibrate_cost_model",
    "degraded_contraction_rho",
    "degraded_solver_inputs",
    "empirical_contraction_rate",
    "expected_comm_units",
    "masked_laplacian_expectation",
    "load_fault_ledger",
    "load_measured_comm_times",
    "load_measured_link_costs",
    "load_plan",
    "load_recorder_disagreement",
    "local_step_breakeven",
    "matching_comm_units",
    "normalize_staleness",
    "parse_staleness_spec",
    "plan_candidate",
    "resolve_budget_swap",
    "resolve_topology",
    "save_plan",
    "simulate_consensus",
    "simulate_fleet_wallclock",
    "stale_alpha_rescale",
    "stale_contraction_rho",
    "staleness_delay_inflation",
    "steps_to_consensus",
    "straggler_step_times",
    "sweep",
    "verify_against_recorder",
    "verify_plan_run",
    "wire_disagreement_floor",
    "wire_quantization_eps",
]
