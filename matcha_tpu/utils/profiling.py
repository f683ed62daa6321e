"""Profiler integration (SURVEY.md §5.1).

The reference's only telemetry is ``time.time()`` brackets around the MPI
calls (train_mpi.py:114-143).  Under XLA that boundary does not exist — the
gossip is fused into the train step — so the framework offers two layers:

* the *two-program split* in the train loop (``comp_time``/``comm_time``
  series, reference-compatible CSVs), and
* real ``jax.profiler`` traces for kernel-level attribution, via
  :func:`trace` — view in TensorBoard or Perfetto to see the Pallas gossip
  kernel, the per-matching permutes, and the model's fwd/bwd separately;
* the loop's own host phases (:class:`SpanRecorder`): every statement of
  an epoch period under one name from ``SPAN_NAMES``, kept in memory for
  the journal and, under a profiler session, on the device trace's clock.
"""

from __future__ import annotations

import contextlib
import time
from pathlib import Path
from typing import List, Optional

import jax

__all__ = ["trace", "SPAN_NAMES", "SpanRecorder", "device_span"]


@contextlib.contextmanager
def trace(log_dir: str, *, create_perfetto_link: bool = False):
    """Context manager capturing a ``jax.profiler`` trace into ``log_dir``.

    Usage::

        with profiling.trace("/tmp/tb"):
            state, metrics = step(state, xb, yb)
            jax.block_until_ready(state.params)

    The block must end with a ``block_until_ready`` (or any host readback),
    otherwise asynchronously-dispatched work lands outside the trace.
    """
    Path(log_dir).mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(log_dir, create_perfetto_link=create_perfetto_link)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()


#: Every span name the train loop records, in loop order (README
#: "Profiling" says what code each covers).  ``membership_bootstrap`` and
#: an on-demand or emergency ``checkpoint`` open inside another span
#: (``prime``, ``boundary_hook``, ``divergence_check``) and are its
#: children; every other name is a leaf of the epoch period.  ``profile``
#: is recorded only by the epoch that ``trace_dir`` captures: the profiler's
#: start before the epoch and, after it, its stop (which writes the capture)
#: with the capture's reduction to the ``device_scopes`` event.
SPAN_NAMES = (
    "boundary_hook", "prime", "membership_bootstrap", "snapshot", "profile",
    "load_batches", "stack_batches", "h2d", "ledger_observe", "dispatch",
    "epoch_python", "wait_device",
    "divergence_check", "comm_split_timer", "evaluate", "record_epoch",
    "telemetry_flush", "heartbeat", "recorder_flush", "checkpoint",
)


class SpanRecorder:
    """Named host phases of one ``train()`` call, on two clocks at once.

    :meth:`span` opens ``jax.profiler.TraceAnnotation("matcha/" + name)``
    — so the phase lies on the device trace's clock whenever a profiler
    session is open (a flag test when none is) — and keeps ``{name, t0,
    t1, parent, **counts}`` in memory, ``t0``/``t1`` from
    ``time.perf_counter()`` relative to the run's start (``origin`` is
    what the run's clock read when the recorder was made).  ``parent``
    names the epoch period the span belongs to (:meth:`begin`), or
    ``"<period>/<name>"`` of the span it opened inside.

    **Host phases only.**  Inside a jitted function this bracket exists at
    *trace* time, not run time — XLA fuses the gossip into the step, so a
    wall-clock bracket around ``begin_mix`` would measure nothing (the
    round-1 lesson behind the two-program comm split).  For in-graph
    phases use :func:`device_span`.

    The profiler never sees a span inside another: a reduction that adds
    each name's cover of a device gap would count a nested parent twice.
    A span that opens inside another suspends the outer one's annotation
    and reopens it when it closes, so the outer name shows there as two
    events around the inner one.
    """

    def __init__(self, origin: float = 0.0):
        self._zero = time.perf_counter() - origin
        self._period: Optional[dict] = None
        self._open: List[list] = []  # [record, annotation] innermost last
        self.spans: List[dict] = []  # since the last end(), in start order

    def _now(self) -> float:
        return time.perf_counter() - self._zero

    def begin(self, period: str, **fields) -> None:
        """Open the epoch period that the following spans belong to."""
        self._period = {"period": period, **fields, "t0": self._now()}

    def end(self, **counts) -> Optional[dict]:
        """Close the period: ``{period, t0, t1, **counts, spans}`` with
        the spans recorded since :meth:`begin`; None where none is open."""
        if self._period is None:
            return None
        record = dict(self._period, t1=self._now(), **counts,
                      spans=self.spans)
        self._period, self.spans = None, []
        return record

    @contextlib.contextmanager
    def span(self, name: str, **counts):
        """One named phase around the ``with`` body."""
        parent = self._period["period"] if self._period else None
        if self._open:
            outer, annotation = self._open[-1]
            annotation.__exit__(None, None, None)
            parent = "/".join(filter(None, (outer["parent"], outer["name"])))
        record = {"name": name, "t0": self._now(), "t1": None,
                  "parent": parent, **counts}
        self.spans.append(record)
        self._open.append([record, _annotate(name)])
        try:
            yield
        finally:
            self._open.pop()[1].__exit__(None, None, None)
            record["t1"] = self._now()
            if self._open:
                self._open[-1][1] = _annotate(self._open[-1][0]["name"])


def _annotate(name: str):
    """An entered ``TraceAnnotation("matcha/<name>")``."""
    annotation = jax.profiler.TraceAnnotation("matcha/" + name)
    annotation.__enter__()
    return annotation


def device_span(name: str):
    """Named scope for *in-graph* phases (``jax.named_scope``).

    Ops traced under the scope carry ``name`` in the ``op_name`` of their
    HLO metadata (``matcha/fwd_bwd``, ``comm/step``, ``matcha/heal``, ...),
    and a fusion across a phase boundary keeps each fused instruction's.
    A ``jax.profiler`` capture's rows do not show it: ``obs.xprof`` joins
    them to the HLO the capture carries and gives device time by scope
    (``obs_tpu.py profile``, the journal's ``device_scopes`` event).
    Pure trace-time construct: adds zero runtime work and cannot trip the
    retrace sanitizer (tests/test_obs.py pins both properties).
    """
    return jax.named_scope(name)
