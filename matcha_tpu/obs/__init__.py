"""Observability: in-graph telemetry, the unified run journal, drift watch.

Three layers (DESIGN.md §14), one import surface:

* :mod:`telemetry` — a small ``Telemetry`` pytree carried through the
  compiled train step that accumulates device-side counters (per-step
  disagreement, wire bytes, matchings, alive workers, heal/quantize
  events) with **zero extra host syncs**: it is read exactly once per
  epoch, at the boundary where the loop already synchronizes.
* :mod:`journal` — the schema-versioned JSONL event stream
  (``events.jsonl``) every run writes: telemetry flushes, fault-ledger
  events, rollbacks, α re-derivations, drift trips, checkpoint writes.
  The Recorder's ``faults.json`` becomes a *view* of this stream.
* :mod:`drift` — the live planner-drift monitor: measured per-epoch
  disagreement contraction vs the plan's predicted ρ (staleness /
  bf16-floor / fault-degraded composition from ``plan.spectral``),
  journaling a ``drift`` event after K consecutive out-of-band epochs.

Plus the *performance* twin (DESIGN.md §15, ISSUE 8):

* :mod:`costs` — compiled-cost introspection (``cost_analysis`` /
  ``memory_analysis`` of every program the loop builds, journaled as v2
  ``compile`` events) and the automatic roofline / §9 capacity tables.
* :mod:`xprof` — the device-side reader: a ``jax.profiler`` capture's
  ``XLA Ops`` rows joined, by the capture's own HLO, to the ``device_span``
  (``matcha/*`` / ``comm/*``) and the pass each instruction was traced
  under; the ``device_scopes`` record (device time by program and scope,
  and the share of ``comm/*`` time under other work; loud when a capture
  has no device plane).

And the *live* half (DESIGN.md §17, ISSUE 10):

* :mod:`health` — per-host heartbeat files under ``{run}/health/``
  (step progress, step-time EWMA, comm/compute split, per-worker
  participation + disagreement) and the fleet-status digest behind
  ``obs_tpu.py watch``.
* :mod:`anomaly` — streaming MAD/robust-z detectors over those records
  (dead / straggler / disagreement-outlier / time-spike /
  deadline-missed), journaled as v3 ``anomaly`` events with an
  attributed cause.

And the *attribution plane* (DESIGN.md §18, ISSUE 11):

* :mod:`attribution` — measured per-matching/per-link costs: the flag
  stream regenerated from the journaled schedule seed, ridge-regressed
  against per-epoch comm seconds, with identifiability verdicts, the
  planlint-verifiable ``measured_link_costs.json`` artifact, v4
  ``attribution`` events, and the per-epoch critical-path analysis.
* :mod:`timeline` — the fleet timeline export: journal + heartbeat files
  merged into one Chrome-trace/Perfetto ``trace_event`` JSON (one track
  per host), schema-validated and round-trip-checked.

And the *durable-state recovery* half (DESIGN.md §23, ISSUE 18):

* :mod:`bestio` — the fs seam every observability write rides (the chaos
  harness injects ENOSPC/hung IO under it), the skew-aware ``wall_clock``,
  and ``BestEffortSink``: bounded retry + deadline + breaker, so training
  never blocks or dies on telemetry IO and degradation stays loud.
* :func:`journal.salvage_journal` — salvage-prefix-and-quarantine for a
  journal corrupted mid-stream (``read_journal(repair=True)`` forgives
  only the crash-truncated tail).

``obs_tpu.py`` renders a run's journal (summary / tail / drift / compare),
the performance artifacts (roofline / capacity / profile), the live
fleet status (watch / health), and the attribution plane (attribute /
timeline).
"""

from .costs import (
    CostLedger,
    analyze_program,
    capacity_report,
    chip_peaks,
    roofline_report,
)
from .anomaly import ANOMALY_CAUSES, AnomalyDetector, mad_zscores
from .attribution import (
    LINK_COSTS_FORMAT,
    attribute_run,
    critical_path_report,
    link_costs_artifact,
    render_attribution,
)
from .drift import DriftMonitor, compose_predicted_rho, drift_report
from .health import (
    HeartbeatEmitter,
    fleet_status,
    fleet_verdict,
    read_heartbeats,
    render_watch,
)
from .bestio import BestEffortSink, get_fs, install_fs, wall_clock
from .journal import (
    EVENT_KINDS,
    FAULT_KINDS,
    SCHEMA_VERSION,
    Journal,
    append_journal_record,
    epoch_series,
    make_event,
    count_journal_lines,
    read_journal,
    read_journal_tail,
    resolve_journal_path,
    salvage_journal,
    validate_event,
)
from .telemetry import Telemetry, TelemetrySpec, telemetry_flush, telemetry_step
from .timeline import build_timeline, timeline_for_run, validate_trace
from .xprof import TraceParseError, device_scopes, scope_map

__all__ = [
    "ANOMALY_CAUSES",
    "AnomalyDetector",
    "BestEffortSink",
    "CostLedger",
    "DriftMonitor",
    "EVENT_KINDS",
    "FAULT_KINDS",
    "HeartbeatEmitter",
    "Journal",
    "LINK_COSTS_FORMAT",
    "SCHEMA_VERSION",
    "Telemetry",
    "TelemetrySpec",
    "TraceParseError",
    "analyze_program",
    "append_journal_record",
    "attribute_run",
    "build_timeline",
    "fleet_status",
    "fleet_verdict",
    "capacity_report",
    "chip_peaks",
    "count_journal_lines",
    "compose_predicted_rho",
    "critical_path_report",
    "device_scopes",
    "drift_report",
    "epoch_series",
    "get_fs",
    "install_fs",
    "link_costs_artifact",
    "mad_zscores",
    "make_event",
    "read_heartbeats",
    "read_journal",
    "read_journal_tail",
    "render_attribution",
    "render_watch",
    "resolve_journal_path",
    "roofline_report",
    "salvage_journal",
    "scope_map",
    "telemetry_flush",
    "telemetry_step",
    "timeline_for_run",
    "validate_event",
    "validate_trace",
    "wall_clock",
]
