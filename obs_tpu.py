#!/usr/bin/env python
"""Observability CLI — render a run's unified journal (DESIGN.md §14).

Every saved run writes ``events.jsonl`` next to its Recorder CSVs: the
schema-versioned stream of telemetry flushes, fault-ledger events, drift
trips, checkpoint writes, and retrace detections.  This tool turns one (or
several) of those into something a human — or a session log — can read.

Commands
--------
``summary RUN [--md PATH]``
    One-screen report: config + plan header, per-epoch table (loss,
    disagreement, wire bytes, matchings, alive floor, heal counts,
    timings), fault/drift/retrace events, total bytes on wire.  ``--md``
    additionally writes the same report as a markdown artifact.

``tail RUN [-n N]``
    The last N journal events, one per line — "what just happened".

``drift RUN [--rho R] [--tolerance T] [--patience K] [--steps-per-epoch S]``
    Replay the planner-drift analysis over the journal: measured per-epoch
    disagreement contraction vs the predicted ρ band the run recorded at
    start (every flag overrides — ``--rho`` asks "would this run have
    satisfied *that* plan?").  Exit 1 when drift is detected (replayed or
    live-journaled), 0 when the run is within band.

``compare SRC... [--md PATH]``
    One table across heterogeneous sources: run dirs / journals (their
    ``bench`` events, or the final telemetry row), bare
    ``BENCH_r*.json``-style bench records, and ``MULTICHIP_r*.json``
    dryrun stamps — so driver captures and journal-emitting runs land
    side by side.

Performance observability (DESIGN.md §15):

``roofline [--workers N] [--dim D | --model M] [--chip C]
[--measured R | --source SRC] [--md PATH]``
    The automatic roofline: compile the dense per-step gossip program at
    the requested shape, extract FLOPs/HBM-bytes from the compiled cost
    analysis, and emit compute-bound / HBM-bound steps/s ceilings against
    the pinned chip peaks (CPU gets explicit provisional placeholders) —
    machine-checking benchmarks/ROOFLINE.md.  ``--measured`` (or a bench
    record via ``--source``) adds the measured-vs-ceiling ratio.  Exit 1
    when a ceiling is non-finite.

``capacity [--dim D | --model M] [--workers N,N] [--chip C] [--md PATH]``
    Re-derive the DESIGN.md §9 HBM capacity table from the compiled
    state-update program's ``memory_analysis()`` instead of hand
    multiplication: persistent state bytes and chips needed per
    (communicator, N).

``profile CAPTURE... [--md PATH] [--journal PATH]``
    Device time by scope: reduce ``jax.profiler`` captures (a
    ``--trace-dir`` run's directory, any directory above an ``*.xplane.pb``,
    or the file) with the program's one device-side reader
    (``matcha_tpu.obs.xprof``): every executed operation joined, through the
    HLO the capture itself carries, to the ``device_span`` (``matcha/*`` /
    ``comm/*``) and the pass it was traced under.  Prints, a program, the
    table (scope, ms a run, share, forward / recomputed / recomputed_inner /
    backward), the longest operations under no scope, and the share of
    ``comm/*`` device time that ran under other work (``-`` where the
    capture has no ``comm/*`` row).  ``--journal`` appends one
    ``device_scopes`` event a capture.  Exits 2 with a clear message when a
    capture has no device plane (the CPU's) instead of a table of zeros.

Live health plane (DESIGN.md §17):

``watch RUN [--once] [--interval S] [--deadline S] [--md PATH]``
    (alias: ``health``)  Live fleet status from the per-host heartbeat
    files under ``RUN/health/`` (bounded reverse-tail reads — O(tail) per
    refresh, torn-line safe against concurrent writers): one row per
    worker (alive, last-seen age, step-rate vs fleet median,
    participation, disagreement, critical-path tax, anomaly flags) plus
    every detector verdict over the tail window.  ``--once`` prints a
    single table and exits 1 when anything is flagged (the CI / scripting
    form; a healthy fleet exits 0); without it the table refreshes every
    ``--interval`` seconds until interrupted.  Exits 2 when no heartbeats
    exist.

Attribution plane (DESIGN.md §18):

``attribute RUN [--out COSTS.json] [--md PATH] [--journal PATH]``
    Measured per-matching link costs: regenerate the run's ``[T, M]``
    activation flag stream from the journaled schedule seed, fold it into
    the per-epoch design matrix, and ridge-regress the journaled per-epoch
    comm seconds against it — per-matching seconds with confidence
    intervals, an identifiability report, the per-link decomposition via
    the folded execution plan, and the per-epoch critical-path table when
    heartbeats exist.  ``--out`` writes the planlint-verifiable
    ``measured_link_costs.json`` artifact; ``--journal`` appends the
    schema-v4 ``attribution`` event.  Exits 1 when **nothing** is
    identifiable (an unidentifiable run must fail loudly, not emit noise
    as fact); exits 2 on unusable journals.

``timeline RUN [--out trace.json]``
    Fleet timeline export: merge the journal, the per-host heartbeat
    files, and the anomaly events into one Chrome-trace/Perfetto
    ``trace_event`` JSON — one track per host, compute/comm/compile/epoch
    spans, instants for anomalies and membership churn, telemetry
    counters.  The trace is schema-validated and round-trip-checked
    (every journal/heartbeat event exactly once) before writing; exits 1
    on validation failure.  Open the file at https://ui.perfetto.dev.

``RUN`` is a run directory (holding ``events.jsonl``) or a journal path.
"""

from __future__ import annotations

import argparse
import sys


def _load(source: str):
    from matcha_tpu.obs import read_journal, resolve_journal_path

    path = resolve_journal_path(source)
    return read_journal(path), path


def cmd_summary(args) -> int:
    from matcha_tpu.obs.report import render_summary, render_summary_markdown

    events, path = _load(args.run)
    print(render_summary(events, source=path))
    if args.md:
        with open(args.md, "w") as f:
            f.write(render_summary_markdown(events, source=path))
        print(f"# markdown written to {args.md}", file=sys.stderr)
    return 0


def cmd_tail(args) -> int:
    from matcha_tpu.obs import read_journal_tail, resolve_journal_path
    from matcha_tpu.obs.report import render_tail

    # bounded reverse read: "what just happened" must cost O(tail), not
    # O(run length) — a long run's journal is megabytes of history
    events = read_journal_tail(resolve_journal_path(args.run), args.n)
    print(render_tail(events, n=args.n))
    return 0


def cmd_drift(args) -> int:
    from matcha_tpu.obs import drift_report

    events, path = _load(args.run)
    report = drift_report(events, rho=args.rho, tolerance=args.tolerance,
                          patience=args.patience,
                          steps_per_epoch=args.steps_per_epoch)
    print(f"journal: {path}")
    print(f"predicted: rho={report['rho']:.6g} over "
          f"{report['steps_per_epoch']} steps/epoch -> per-epoch factor "
          f"{report['predicted_factor']:.4g} "
          f"(band <= {report['band']:.4g}, patience {report['patience']})")
    pairs = zip(report["epochs"][1:], report["measured_factors"])
    factors = "  ".join(f"e{ep}:{f:.3g}" for ep, f in pairs)
    print(f"measured factors: {factors}")
    print(f"checked epochs: {report['checked_epochs']}, "
          f"violations: {report['violations']}")
    if report.get("rebases"):
        print(f"plan re-based {report['rebases']}x mid-run (alpha "
              f"re-derivation / config-changed resume); rho above is the "
              f"final segment's")
    for trip in report["trips"]:
        print(f"DRIFT (replayed): epoch {trip['epoch']} measured "
              f"{trip['measured_factor']:.4g} > band {report['band']:.4g}")
    for e in report["journaled"]:
        print(f"DRIFT (journaled live): epoch {e.get('epoch')} measured "
              f"{e.get('measured_factor'):.4g}")
    print("verdict: " + ("within the predicted tolerance band"
                         if report["consistent"] else "PLANNER DRIFT"))
    return 0 if report["consistent"] else 1


def cmd_compare(args) -> int:
    from matcha_tpu.obs.report import compare_sources, render_compare

    rows, problems = compare_sources(args.sources)
    if not rows:
        print("nothing comparable found", file=sys.stderr)
        for p in problems:
            print(f"# {p}", file=sys.stderr)
        return 2
    print(render_compare(rows, problems))
    if args.md:
        with open(args.md, "w") as f:
            f.write(render_compare(rows, problems, markdown=True) + "\n")
        print(f"# markdown written to {args.md}", file=sys.stderr)
    return 0


def _resolve_dim(args) -> int:
    if args.dim:
        return args.dim
    from matcha_tpu.obs.costs import flat_param_dim

    return flat_param_dim(args.model, args.dataset, num_classes=args.classes)


def _resolve_measured(args):
    """Steps/s for the vs-ceiling ratio — explicit ``--measured``, or the
    first rate row a ``--source`` (bench journal / BENCH_r*.json / run
    dir) yields; ``None`` where neither gives one."""
    if args.measured is not None:
        return float(args.measured)
    if not args.source:
        return None
    from matcha_tpu.obs.report import compare_sources

    rows, problems = compare_sources([args.source])
    for p in problems:
        print(f"# {p}", file=sys.stderr)
    for row in rows:
        if row.get("value") and row.get("unit") == "gossip_steps_per_sec":
            return float(row["value"])
    # name what WAS there and what would have worked — "no record" alone
    # sends the operator diffing JSON shapes by hand
    found = sorted({str(r.get("unit")) for r in rows}) or ["nothing"]
    print(f"# no gossip_steps_per_sec record in {args.source} (found "
          f"units: {', '.join(found)}); accepted source shapes: a bench "
          f"journal / run dir with `bench` events carrying "
          f"unit=gossip_steps_per_sec, a BENCH_r*.json driver capture "
          f"(record/parsed/tail wrappers ok), or a raw bench record",
          file=sys.stderr)
    return None


def cmd_roofline(args) -> int:
    import math

    from matcha_tpu.obs.costs import render_roofline_markdown, roofline_report
    from matcha_tpu.topology import decompose, graph_size, make_graph, \
        select_graph

    if args.graphid is not None:
        decomposed = select_graph(args.graphid)
        n = graph_size(args.graphid)
    else:
        n = args.workers
        decomposed = decompose(make_graph(args.topology, n, seed=1), n, seed=1)
    dim = _resolve_dim(args)
    report = roofline_report(n, dim, decomposed,
                             wire_dtype=args.wire_dtype,
                             chip=args.chip,
                             measured_steps_per_sec=_resolve_measured(args))
    md = render_roofline_markdown(report, source=args.source or "")
    ok = all(math.isfinite(report[k]) and report[k] > 0 for k in
             ("flops_per_step", "hbm_bytes_per_step",
              "compute_bound_steps_per_sec",
              "hbm_bound_steps_per_sec"))
    journal_payload = {"roofline": report, "unit": "roofline_report"}
    print(md)
    if args.md:
        with open(args.md, "w") as f:
            f.write(md)
        print(f"# markdown written to {args.md}", file=sys.stderr)
    if args.journal and ok:
        # gated on finiteness: a failed extraction must not write NaN
        # tokens (non-strict JSON) into a session journal the compare /
        # summary renderers will read later
        from matcha_tpu.obs import append_journal_record

        append_journal_record(args.journal, "bench", record=journal_payload)
    if not ok:
        print("obs_tpu: roofline produced non-finite ceilings (nothing "
              "journaled)", file=sys.stderr)
    return 0 if ok else 1


def cmd_capacity(args) -> int:
    from matcha_tpu.obs.costs import capacity_report, render_capacity_markdown

    workers = [int(w) for w in args.workers.split(",") if w.strip()]
    report = capacity_report(_resolve_dim(args), workers=workers,
                             communicators=tuple(
                                 c for c in args.communicators.split(",")
                                 if c.strip()),
                             chip=args.chip)
    md = render_capacity_markdown(report)
    print(md)
    if args.md:
        with open(args.md, "w") as f:
            f.write(md)
        print(f"# markdown written to {args.md}", file=sys.stderr)
    return 0


def cmd_profile(args) -> int:
    from matcha_tpu.obs.xprof import device_scopes, render_device_scopes

    records = [device_scopes(src) for src in args.captures]
    text = "\n\n".join(
        "\n".join([f"# device time by scope: {r['source']}"]
                  + render_device_scopes(r)) for r in records)
    print(text)
    if args.md:
        with open(args.md, "w") as f:
            f.write("```\n" + text + "\n```\n")
        print(f"# markdown written to {args.md}", file=sys.stderr)
    if args.journal:
        from matcha_tpu.obs import append_journal_record

        for r in records:
            append_journal_record(args.journal, "device_scopes", **r)
    return 0


def cmd_attribute(args) -> int:
    import json

    from matcha_tpu.obs.attribution import (
        attribute_run,
        attribution_event_fields,
        link_costs_artifact,
        render_attribution,
    )

    events, path = _load(args.run)
    report = attribute_run(events, steps_per_epoch=args.steps_per_epoch,
                           ridge=args.ridge, num_chips=args.chips)
    print(render_attribution(report))
    if args.md:
        with open(args.md, "w") as f:
            f.write(render_attribution(report, markdown=True))
        print(f"# markdown written to {args.md}", file=sys.stderr)
    identifiable = any(report["identifiable"])
    if args.out:
        if identifiable:
            with open(args.out, "w") as f:
                json.dump(link_costs_artifact(report), f, indent=1,
                          sort_keys=True)
                f.write("\n")
            # same self-check discipline as plan_tpu sweep: never emit an
            # artifact the committed-artifact verifier would reject
            from matcha_tpu.analysis import lint_plan_file, render_plan_text

            violations, _ = lint_plan_file(args.out)
            if violations:
                print(render_plan_text(violations, [args.out]),
                      file=sys.stderr)
                print(f"# wrote {args.out}, but it FAILS planlint — do "
                      f"not commit", file=sys.stderr)
                return 1
            print(f"# wrote {args.out}", file=sys.stderr)
        else:
            print(f"# not writing {args.out}: nothing identifiable",
                  file=sys.stderr)
    if args.journal and identifiable:
        from matcha_tpu.obs import append_journal_record

        append_journal_record(args.journal, "attribution",
                              **attribution_event_fields(report))
    if not identifiable:
        print(f"obs_tpu: attribution unidentifiable — "
              f"{report['reason'] or 'no separable matching'}",
              file=sys.stderr)
        return 1
    return 0


def cmd_timeline(args) -> int:
    import json

    from matcha_tpu.obs.timeline import (
        render_timeline_summary,
        timeline_for_run,
        validate_trace,
    )

    trace = timeline_for_run(args.run)
    problems = validate_trace(trace)
    for p in problems:
        print(f"obs_tpu: timeline invalid: {p}", file=sys.stderr)
    if problems:
        print(f"obs_tpu: {len(problems)} validation problem(s) — nothing "
              f"written", file=sys.stderr)
        return 1
    with open(args.out, "w") as f:
        json.dump(trace, f, separators=(",", ":"), allow_nan=False)
    print(render_timeline_summary(trace))
    print(f"# trace written to {args.out}", file=sys.stderr)
    return 0


def cmd_watch(args) -> int:
    import time

    from matcha_tpu.obs.health import fleet_verdict, render_watch

    def once() -> int:
        # the 0/1/2 exit contract lives in fleet_verdict, shared verbatim
        # with the serve plane's /healthz endpoint (parity pinned by test)
        rc, status = fleet_verdict(args.run, deadline=args.deadline,
                                   tail=args.tail)
        if status is None:
            print(f"obs_tpu: no heartbeat evidence under {args.run}",
                  file=sys.stderr)
            return rc
        print(render_watch(status))
        if args.md:
            with open(args.md, "w") as f:
                f.write(render_watch(status, markdown=True))
            print(f"# markdown written to {args.md}", file=sys.stderr)
        return rc

    if args.once:
        return once()
    try:
        while True:  # the live dashboard loop; ^C is the exit path
            rc = once()
            print(f"# refresh in {args.interval:.0f}s (^C to stop; "
                  f"current verdict rc={rc})", file=sys.stderr)
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = p.add_subparsers(dest="cmd", required=True)

    s = sub.add_parser("summary", help="one-screen run report")
    s.add_argument("run", help="run dir (with events.jsonl) or journal path")
    s.add_argument("--md", default=None, help="also write a markdown report")
    s.set_defaults(fn=cmd_summary)

    s = sub.add_parser("tail", help="last N journal events")
    s.add_argument("run")
    s.add_argument("-n", type=int, default=20)
    s.set_defaults(fn=cmd_tail)

    s = sub.add_parser("drift", help="measured contraction vs predicted rho")
    s.add_argument("run")
    s.add_argument("--rho", type=float, default=None,
                   help="override the journal's predicted rho (what-if)")
    s.add_argument("--tolerance", type=float, default=None)
    s.add_argument("--patience", type=int, default=None)
    s.add_argument("--steps-per-epoch", type=int, default=None,
                   dest="steps_per_epoch")
    s.set_defaults(fn=cmd_drift)

    s = sub.add_parser("compare", help="table across runs / bench records")
    s.add_argument("sources", nargs="+",
                   help="run dirs, journal files, BENCH_r*.json or "
                        "MULTICHIP_r*.json records")
    s.add_argument("--md", default=None)
    s.set_defaults(fn=cmd_compare)

    def _shape_flags(s):
        s.add_argument("--dim", type=int, default=0,
                       help="flat parameter dimension D; 0 derives it from "
                            "--model via eval_shape (shapes only)")
        s.add_argument("--model", default="resnet20")
        s.add_argument("--dataset", default="synthetic_image")
        s.add_argument("--classes", type=int, default=10)
        s.add_argument("--chip", default=None,
                       help="chip table key (v5e, v4, ...); default = the "
                            "current backend, CPU falls back to explicit "
                            "provisional placeholders")

    s = sub.add_parser("roofline",
                       help="compiled-cost ceilings vs chip peaks")
    _shape_flags(s)
    s.add_argument("--workers", type=int, default=256,
                   help="virtual workers N (ignored with --graphid)")
    s.add_argument("--topology", default="geometric",
                   help="generator topology (north star: geometric)")
    s.add_argument("--graphid", type=int, default=None,
                   help="zoo topology id instead of the generator")
    s.add_argument("--wire-dtype", default="bf16", choices=["f32", "bf16"],
                   dest="wire_dtype")
    s.add_argument("--measured", type=float, default=None,
                   help="measured steps/s for the vs-ceiling ratio")
    s.add_argument("--source", default=None,
                   help="bench journal / BENCH_r*.json / run dir to read "
                        "the measured rate from instead of --measured")
    s.add_argument("--md", default=None)
    s.add_argument("--journal", default=None,
                   help="also append the report as a bench event here")
    s.set_defaults(fn=cmd_roofline)

    s = sub.add_parser("capacity",
                       help="§9 HBM capacity table from memory_analysis()")
    _shape_flags(s)
    s.add_argument("--workers", default="256,64",
                   help="comma-separated worker counts (table rows)")
    s.add_argument("--communicators", default="decen,choco",
                   help="comma-separated communicator column set")
    s.add_argument("--md", default=None)
    s.set_defaults(fn=cmd_capacity)

    for name in ("watch", "health"):  # one command, both spellings
        s = sub.add_parser(name,
                           help="live fleet status from heartbeat files")
        s.add_argument("run", help="run dir (holding health/) or a "
                                   "heartbeat directory")
        s.add_argument("--once", action="store_true",
                       help="print one table and exit (1 when any worker "
                            "is flagged — the CI form)")
        s.add_argument("--interval", type=float, default=10.0,
                       help="refresh period in seconds without --once")
        s.add_argument("--deadline", type=float, default=60.0,
                       help="seconds without a heartbeat before a host "
                            "counts as deadline-missed")
        s.add_argument("--tail", type=int, default=8,
                       help="heartbeat records per host to re-run the "
                            "detectors over (bounded reverse read)")
        s.add_argument("--md", default=None,
                       help="also write the table as a markdown artifact")
        s.set_defaults(fn=cmd_watch)

    s = sub.add_parser("attribute",
                       help="measured per-matching/per-link costs from "
                            "the journal (exit 1 when unidentifiable)")
    s.add_argument("run", help="run dir (with events.jsonl) or journal path")
    s.add_argument("--out", default=None,
                   help="write the planlint-verifiable "
                        "measured_link_costs.json here")
    s.add_argument("--ridge", type=float, default=1e-8,
                   help="ridge penalty on the per-matching coefficients")
    s.add_argument("--chips", type=int, default=1,
                   help="folded chip count for the per-link hop weighting")
    s.add_argument("--steps-per-epoch", type=int, default=None,
                   dest="steps_per_epoch",
                   help="override the journal's recorded steps/epoch")
    s.add_argument("--md", default=None,
                   help="also write the report as a markdown artifact")
    s.add_argument("--journal", default=None,
                   help="also append a schema-v4 `attribution` event here")
    s.set_defaults(fn=cmd_attribute)

    s = sub.add_parser("timeline",
                       help="export the run as a Perfetto/Chrome trace")
    s.add_argument("run", help="run dir (with events.jsonl and optionally "
                               "health/) or journal path")
    s.add_argument("--out", default="trace.json",
                   help="trace_event JSON output path (default trace.json)")
    s.set_defaults(fn=cmd_timeline)

    s = sub.add_parser("profile",
                       help="device time by scope from profiler captures")
    s.add_argument("captures", nargs="+",
                   help="capture dirs (a --trace-dir run's) or "
                        "*.xplane.pb files")
    s.add_argument("--md", default=None)
    s.add_argument("--journal", default=None,
                   help="also append one `device_scopes` event per capture "
                        "here")
    s.set_defaults(fn=cmd_profile)

    args = p.parse_args(argv)
    try:
        return args.fn(args)
    except (FileNotFoundError, ValueError) as e:
        print(f"obs_tpu: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
