"""Link-cost model: predict per-iteration communication cost offline.

The unit of account is the **ring hop**: one ``lax.ppermute`` of a chip's
``[L, ...]`` state block moving ``min(d, C−d)`` hops around the bidirectional
ICI ring.  That is exactly what the folded executor issues per (matching,
nonzero chip-offset) — the accounting comes straight from
``FoldedPlan.hop_accounting`` (``parallel/gossip.py``), so the model cannot
drift from the execution plan.

Expected per-iteration cost of a schedule is then linear in the activation
probabilities:

    E[cost] = Σ_j p_j · hops_j        (hop-weighted units / iteration)

Converting units to seconds needs two calibration constants — a fixed
per-iteration overhead ``c₀`` (dispatch, on-chip gather/FMA work, which the
single-chip measurements show dominates) and a per-hop-unit time ``c₁`` —
fit by least squares from measured ``(units, seconds)`` pairs, e.g. the
committed ``benchmarks/budget_sweep.json`` comm timings or any
``BENCH_*.json`` record.  On one chip every matching is local (``hops ≡ 0``)
and the fit collapses to ``c₀ = mean(measured)`` with ``c₁`` unidentifiable —
the honest answer for that regime (comm_time flat across budgets, which is
what the committed sweep shows); the hop term prices the folded multi-chip
plans the north star targets.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Optional, Sequence, Tuple

import numpy as np

from ..parallel.gossip import build_folded_plan
from ..topology import matchings_to_perms

__all__ = [
    "CostModel",
    "matching_comm_units",
    "expected_comm_units",
    "calibrate_cost_model",
    "load_measured_comm_times",
    "load_measured_link_costs",
    "simulate_fleet_wallclock",
    "straggler_step_times",
]


def matching_comm_units(
    decomposed: Sequence[Sequence[tuple]],
    size: int,
    num_chips: int = 1,
    perms: Optional[np.ndarray] = None,
) -> np.ndarray:
    """f64[M] hop-weighted cost of activating each matching once.

    Workers fold chip-major onto ``num_chips`` devices (the
    ``build_folded_plan`` layout); each matching costs the sum of ring hops
    of its distinct nonzero chip offsets.  ``num_chips=1`` → all zeros (every
    edge is chip-local).
    """
    if perms is None:
        perms = matchings_to_perms([list(m) for m in decomposed], size)
    plan = build_folded_plan(np.asarray(perms), num_chips)
    return plan.matching_hop_units()


def expected_comm_units(probs: np.ndarray, unit_costs: np.ndarray) -> float:
    """E[per-iteration hop units] = Σ_j p_j · hops_j (flags are Bernoulli)."""
    p = np.asarray(probs, dtype=np.float64)
    u = np.asarray(unit_costs, dtype=np.float64)
    if p.shape != u.shape:
        raise ValueError(f"probs {p.shape} vs unit costs {u.shape}")
    return float(p @ u)


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Seconds per gossip iteration as an affine function of hop units.

    ``seconds(units) = base_step_s + per_hop_s · units``.  The defaults are
    unit-free (base 1, hop 1): rankings by predicted cost are then rankings
    by ``1 + units`` — already correct ordinally — and calibration only
    sharpens the *ratio* between topology choices into wall-clock.

    ``fit`` is calibration provenance (which samples/epochs/sources fed the
    coefficients) — ``None`` on the uncalibrated default, populated by
    :func:`calibrate_cost_model` and :meth:`from_measured_link_costs` so an
    artifact carrying a fitted model can always answer "fitted from what?".
    """

    base_step_s: float = 1.0
    per_hop_s: float = 1.0
    source: str = "uncalibrated"
    fit: Optional[dict] = None

    def step_seconds(self, units: float) -> float:
        return self.base_step_s + self.per_hop_s * float(units)

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_json(d: dict) -> "CostModel":
        return CostModel(base_step_s=float(d["base_step_s"]),
                         per_hop_s=float(d["per_hop_s"]),
                         source=str(d.get("source", "uncalibrated")),
                         fit=d.get("fit"))

    @staticmethod
    def from_measured_link_costs(data, steps_per_epoch: Optional[int] = None
                                 ) -> "CostModel":
        """Bridge from a ``measured_link_costs.json`` artifact (the
        attribution plane's output, ``obs.attribution``) to the planner's
        affine model — what lets the reactive planner consume measured
        per-link truth instead of the global uncalibrated default.

        Accepts the parsed artifact dict or a path.  The identifiable
        per-matching seconds (per *activation*) are regressed against the
        plan's hop units for the artifact's topology and ``num_chips`` —
        the same degenerate-safe affine fit as :func:`calibrate_cost_model`
        (single-chip plans have every unit at 0, so the slope is honestly
        unidentifiable and the base absorbs the mean).  The per-epoch base
        overhead folds in as ``base_seconds / steps_per_epoch`` (the
        artifact records its steps_per_epoch; the argument overrides).
        Raises ``ValueError`` when the artifact has no identifiable
        matching — an unidentifiable estimate must not silently become a
        calibration.
        """
        data, label = load_measured_link_costs(data)
        per = data.get("per_matching", [])
        idx = [int(r["matching"]) for r in per if r.get("identifiable")]
        if not idx:
            raise ValueError(
                f"{label}: no identifiable matching costs "
                f"({data.get('reason') or 'estimator reported none'}) — "
                f"refusing to calibrate from noise")
        sched = data.get("schedule", {})
        from .autotune import resolve_topology

        decomposed, size, _ = resolve_topology(sched,
                                               int(sched.get("seed", 0)))
        units = matching_comm_units(decomposed, size,
                                    int(data.get("num_chips", 1)))
        theta = {int(r["matching"]): float(r["seconds"]) for r in per
                 if r.get("identifiable")}
        samples = [(float(units[j]), theta[j]) for j in idx]
        spe = int(steps_per_epoch or data.get("steps_per_epoch") or 1)
        model = calibrate_cost_model(
            samples, source=f"measured_link_costs:{label}",
            fit={"epochs_used": data.get("epochs_used"),
                 "identifiable_matchings": idx,
                 "comm_source": data.get("source"),
                 "steps_per_epoch": spe})
        base = max(float(data.get("base_seconds", 0.0)) / max(spe, 1), 0.0)
        return dataclasses.replace(
            model, base_step_s=model.base_step_s + base)


def calibrate_cost_model(
    samples: Sequence[Tuple[float, float]], source: str = "measured",
    fit: Optional[dict] = None,
) -> CostModel:
    """Least-squares fit of ``(units, seconds)`` pairs to the affine model.

    Degenerate designs are handled the way the physics demands: with a
    single distinct units value (e.g. every sample at 0 — the single-chip
    regime) the slope is unidentifiable, so ``per_hop_s = 0`` and the base
    absorbs the mean.  Negative fitted coefficients are clamped to 0: a
    negative marginal hop cost is measurement noise, and propagating it
    would rank *more* communication as *faster*.

    ``fit`` extends the recorded provenance (e.g. which epochs/sources the
    samples came from); the sample count and units range are always
    recorded, so a committed plan artifact shows what fed its model.
    """
    if not samples:
        raise ValueError("need at least one (units, seconds) sample")
    units = np.asarray([s[0] for s in samples], dtype=np.float64)
    secs = np.asarray([s[1] for s in samples], dtype=np.float64)
    provenance = {
        "samples": int(units.shape[0]),
        "units_min": float(units.min()),
        "units_max": float(units.max()),
        **(fit or {}),
    }
    if np.ptp(units) < 1e-12:
        return CostModel(base_step_s=float(secs.mean()), per_hop_s=0.0,
                         source=source + " (slope unidentifiable: "
                                         "single units level)",
                         fit=provenance)
    A = np.stack([np.ones_like(units), units], axis=1)
    (c0, c1), *_ = np.linalg.lstsq(A, secs, rcond=None)
    c0, c1 = max(float(c0), 0.0), max(float(c1), 0.0)
    return CostModel(base_step_s=c0, per_hop_s=c1, source=source,
                     fit=provenance)


def load_measured_link_costs(data) -> Tuple[dict, str]:
    """Normalize a ``measured_link_costs.json`` input: a path or the parsed
    dict; returns ``(data, label)`` and validates the format tag."""
    label = "measured_link_costs"
    if isinstance(data, str):
        label = data
        with open(data) as f:
            data = json.load(f)
    fmt = str(data.get("format", "")) if isinstance(data, dict) else ""
    if not fmt.startswith("matcha_tpu.link_costs"):
        raise ValueError(f"{label}: format {fmt!r} is not a "
                         f"matcha_tpu.link_costs artifact")
    return data, label


# ---------------------------------------------------------------------------
# Bounded-staleness fleet wall-clock model (the straggler-tax pricing)
# ---------------------------------------------------------------------------

def straggler_step_times(
    num_workers: int,
    rounds: int,
    base_s: float = 1.0,
    straggler: int = 0,
    period: int = 4,
    slowdown: float = 4.0,
    jitter: float = 0.05,
    seed: int = 0,
) -> np.ndarray:
    """f64[rounds, N] per-worker gossip-round durations with one planted
    periodic straggler: worker ``straggler`` takes ``slowdown×`` base every
    ``period``-th round (a GC pause / preemption / slow shard — the
    classic period-4 straggler the bench grid plants), everyone carries
    i.i.d. lognormal-ish jitter.  Host-side numpy; the input of
    :func:`simulate_fleet_wallclock`."""
    rng = np.random.default_rng(seed)
    t = base_s * (1.0 + jitter * np.abs(rng.standard_normal(
        (int(rounds), int(num_workers)))))
    t[np.arange(int(rounds)) % int(period) == 0, int(straggler)] *= \
        float(slowdown)
    return t


def simulate_fleet_wallclock(
    step_times: np.ndarray, staleness: int = 1, local_steps: int = 1
) -> dict:
    """Fleet wall-clock of a gossip-round schedule under three execution
    models, from per-worker round durations ``f64[rounds, N]``.

    * **barrier** — every round is a fleet-wide barrier (the committed
      synchronous executor): total = Σ_r max_i t[r, i].  This is exactly
      what ``obs.attribution.critical_path_report`` prices from heartbeats
      — the straggler tax is the gate-minus-median sum.
    * **bounded staleness** — worker i may start round r once it finished
      r−1 *and* every peer has finished round r−k_ev (its delta from that
      round is the oldest thing i is allowed to still be missing):
      ``T_i(r) = max(T_i(r−1), max_j T_j(r−k_ev)) + t[r, i]`` with
      ``k_ev = ceil(staleness / local_steps)`` outstanding exchanges.
      Conservative: the dependency is fleet-wide, not per-matching — real
      topology-aware slack is larger, so the recovered tax reported here
      is a floor.
    * **ideal** — no coupling at all (the unreachable bound):
      max_i Σ_r t[r, i].

    Returns the three totals plus ``tax_seconds`` (barrier − ideal: the
    full straggler tax the barrier pays), ``recovered_seconds`` (barrier −
    bounded: what the k-deep pipeline buys back), and
    ``recovered_fraction`` (recovered / tax, 0 when the tax is 0).
    Consistency: ``staleness=1, local_steps=1`` IS the barrier model (one
    outstanding exchange means waiting on every peer's previous round) —
    pinned by test.
    """
    t = np.asarray(step_times, np.float64)
    if t.ndim != 2:
        raise ValueError(f"step_times must be [rounds, N], got {t.shape}")
    k_ev = max(-(-int(staleness) // max(int(local_steps), 1)), 1)
    rounds, n = t.shape
    barrier = float(np.sum(t.max(axis=1)))
    ideal = float(np.max(t.sum(axis=0)))
    finish = np.zeros((rounds, n))
    for r in range(rounds):
        start = finish[r - 1] if r >= 1 else np.zeros(n)
        if r - k_ev >= 0:
            start = np.maximum(start, float(finish[r - k_ev].max()))
        finish[r] = start + t[r]
    bounded = float(finish[-1].max())
    tax = max(barrier - ideal, 0.0)
    recovered = max(barrier - bounded, 0.0)
    return {
        "rounds": int(rounds),
        "workers": int(n),
        "staleness": int(staleness),
        "local_steps": int(local_steps),
        "event_depth": int(k_ev),
        "barrier_seconds": barrier,
        "bounded_seconds": bounded,
        "ideal_seconds": ideal,
        "tax_seconds": tax,
        "recovered_seconds": recovered,
        "recovered_fraction": (recovered / tax) if tax > 0 else 0.0,
    }


def load_measured_comm_times(path: str) -> list:
    """Extract ``(budget, comm_seconds_per_epoch)`` pairs from a committed
    ``budget_sweep.json`` summary — the calibration input
    ``plan_tpu.py sweep --calibrate`` accepts.  Returns
    ``[(budget, seconds), ...]`` for the MATCHA runs (the D-PSGD row has no
    budget semantics)."""
    with open(path) as f:
        summary = json.load(f)
    out = []
    for run in summary.get("runs", []):
        if run.get("algorithm") == "matcha":
            out.append((float(run["budget"]),
                        float(run["mean_comm_time_per_epoch"])))
    if not out:
        raise ValueError(f"no MATCHA runs with comm timings in {path}")
    return out
