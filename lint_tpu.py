#!/usr/bin/env python
"""graftlint CLI — run the repo's static-analysis rules over the tree.

The rules encode the invariants the MATCHA-class guarantees hang on — the
syntactic GL0xx family (``matcha_tpu/analysis/rules.py``: where-not-multiply
NaN masking, host purity of compiled code, the shared collective axis
constant, the single wire_dtype seam, the two-phase communicator contract,
loud failure paths), the interprocedural GL1xx SPMD-safety family
(``spmd_rules.py``: verified ppermute permutation tables, no collectives
under worker-divergent control flow, quantize-exactly-once wire lattice,
static retrace prediction), the GL2xx graftcontract family
(``contracts.py``: the sync-budget prover against the committed
``sync_budget.json`` manifest, the journal-schema call-site verifier, and
checkpoint-evolution coverage), and the GL3xx graftdur family
(``durability.py``: the atomic-publish prover — every cross-process-watched
file through the one ``utils.atomicio.atomic_publish`` seam — the
single-writer journal + torn-tolerant-reader discipline, the best-effort
IO seam inside root-marked loops, and thread-shared mutation proofs).
``tests/test_analysis.py``, ``tests/test_dataflow.py``,
``tests/test_contracts.py`` and ``tests/test_durability.py`` run the same
engine in tier-1; this CLI is the interactive/CI surface.

Examples
--------
Lint the shipped surface (the tier-1 contract)::

    python lint_tpu.py

Lint only what changed vs a ref (pre-commit speed)::

    python lint_tpu.py --changed HEAD
    python lint_tpu.py --changed origin/main

Verify committed schedule/plan artifacts numerically (planlint)::

    python lint_tpu.py lint-plan                # scans benchmarks/
    python lint_tpu.py lint-plan my_plan.json

JSON artifact (the stamp ``tests/test_docs_artifacts.py`` pins)::

    python lint_tpu.py --format json > benchmarks/lint_stamp.json

Grandfather the current violations (new ones still fail)::

    python lint_tpu.py --write-baseline

Regenerate the GL201 sync-budget manifest from the annotated tree::

    python lint_tpu.py --write-sync-budget

Exit code 0 = clean (modulo baseline), 1 = violations, 2 = usage error.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import subprocess
import sys

from matcha_tpu.analysis import (
    PLAN_CHECKS,
    SYNC_BUDGET_PATH,
    collect_sources,
    lint_paths,
    lint_plan_paths,
    load_baseline,
    render_json,
    render_plan_text,
    render_text,
    rules_by_id,
    write_baseline,
    write_sync_budget,
)

# the shipped lint surface: the package and every executable entry point.
# tests/ is deliberately excluded — fixtures *construct* violations.
DEFAULT_PATHS = ["matcha_tpu", "train_tpu.py", "plan_tpu.py", "obs_tpu.py",
                 "serve_tpu.py"]
DEFAULT_BASELINE = "graftlint_baseline.json"
DEFAULT_PLAN_PATHS = ["benchmarks"]

REPO_ROOT = pathlib.Path(__file__).resolve().parent


def changed_paths(ref: str) -> list | None:
    """The subset of the lint surface touched vs ``ref`` (tracked diffs +
    untracked files).  None = git itself failed (bad ref / not a repo)."""
    try:
        diff = subprocess.run(
            ["git", "diff", "--name-only", ref, "--", "*.py"],
            cwd=REPO_ROOT, capture_output=True, text=True, check=True,
        ).stdout.split()
        untracked = subprocess.run(
            ["git", "ls-files", "--others", "--exclude-standard", "*.py"],
            cwd=REPO_ROOT, capture_output=True, text=True, check=True,
        ).stdout.split()
    except (subprocess.CalledProcessError, OSError):
        return None
    surface = []
    for rel in dict.fromkeys(diff + untracked):  # ordered de-dup
        in_scope = any(
            rel == p or rel.startswith(p.rstrip("/") + "/")
            for p in DEFAULT_PATHS
        )
        if in_scope and (REPO_ROOT / rel).exists():
            surface.append(rel)
    return surface


def main_lint_plan(argv) -> int:
    p = argparse.ArgumentParser(
        prog="lint_tpu.py lint-plan",
        description="planlint: numeric verification of committed plan "
                    "artifacts (PL001–PL008; see "
                    "matcha_tpu/analysis/planlint.py)")
    p.add_argument("paths", nargs="*", default=None,
                   help=f"plan JSONs or directories to scan "
                        f"(default: {DEFAULT_PLAN_PATHS})")
    p.add_argument("--format", choices=["text", "json"], default="text")
    p.add_argument("--list-checks", action="store_true",
                   help="print every PL check id and what it verifies")
    args = p.parse_args(argv)

    if args.list_checks:
        for cid, what in sorted(PLAN_CHECKS.items()):
            print(f"{cid}  {what}")
        return 0

    # relative paths resolve against the cwd first, then the repo root —
    # the same anchoring the main lint surface gets via collect_sources, so
    # `lint_tpu.py lint-plan` works from any directory
    paths = []
    for q in (args.paths or DEFAULT_PLAN_PATHS):
        p = pathlib.Path(q)
        if not p.exists() and not p.is_absolute() \
                and (REPO_ROOT / p).exists():
            p = REPO_ROOT / p
        paths.append(p)
    missing = [str(q) for q in paths if not q.exists()]
    if missing:
        print(f"lint_tpu: no such path: {missing}", file=sys.stderr)
        return 2
    violations, files = lint_plan_paths(paths)
    if args.format == "json":
        print(json.dumps({
            "violations": [v.to_json() for v in violations],
            "artifacts_checked": [str(f) for f in files],
            "clean": not violations,
        }, indent=2))
    else:
        print(render_plan_text(violations, files))
    return 1 if violations else 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:]) if argv is None else list(argv)
    if argv and argv[0] == "lint-plan":
        return main_lint_plan(argv[1:])
    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("paths", nargs="*", default=None,
                   help=f"files/packages to lint (default: {DEFAULT_PATHS})")
    p.add_argument("--format", choices=["text", "json"], default="text")
    p.add_argument("--rules", default=None,
                   help="comma-separated rule ids to run (default: all)")
    p.add_argument("--baseline", default=DEFAULT_BASELINE,
                   help="baseline file of grandfathered violations "
                        "(missing file = empty baseline)")
    p.add_argument("--no-baseline", action="store_true",
                   help="ignore the baseline: report every violation")
    p.add_argument("--write-baseline", action="store_true",
                   help="record current violations into --baseline and exit 0")
    p.add_argument("--write-sync-budget", action="store_true",
                   help="regenerate sync_budget.json (GL201) from the "
                        "annotated tree; refuses while any reachable sync "
                        "lacks its `# graftcontract: sync — reason` "
                        "annotation")
    p.add_argument("--list-rules", action="store_true",
                   help="print every rule id, title, and invariant")
    p.add_argument("--changed", default=None, metavar="REF",
                   help="lint only lint-surface files touched vs this git "
                        "ref (plus untracked ones) — the fast pre-commit "
                        "path; exits 0 immediately when nothing relevant "
                        "changed")
    args = p.parse_args(argv)

    try:
        rules = rules_by_id(args.rules.split(",") if args.rules else None)
    except KeyError as e:
        print(f"lint_tpu: {e}", file=sys.stderr)
        return 2

    if args.list_rules:
        for r in rules:
            print(f"{r.id}  {r.title}")
            print(f"       {r.invariant}\n")
        return 0

    paths = args.paths or DEFAULT_PATHS
    if args.changed is not None:
        # --changed computes its own path set: combining it with explicit
        # paths would silently discard the user's argument, and combining
        # it with --write-baseline would rewrite the baseline from only the
        # touched files, dropping every other file's grandfathered entries
        if args.paths:
            print("lint_tpu: --changed and explicit paths are mutually "
                  "exclusive (the flag computes its own path set)",
                  file=sys.stderr)
            return 2
        if args.write_baseline or args.write_sync_budget:
            print("lint_tpu: refusing --changed with --write-baseline/"
                  "--write-sync-budget — a manifest written from a partial "
                  "path set drops every unchanged file's entries",
                  file=sys.stderr)
            return 2
        touched = changed_paths(args.changed)
        if touched is None:
            print(f"lint_tpu: git diff against {args.changed!r} failed "
                  f"(bad ref, or not a git checkout)", file=sys.stderr)
            return 2
        if not touched:
            print(f"lint_tpu: nothing on the lint surface changed vs "
                  f"{args.changed}")
            return 0
        paths = touched

    if args.write_sync_budget:
        # the manifest is regenerated from the FULL default surface unless
        # explicit paths narrow it deliberately — same guard philosophy as
        # --write-baseline above
        try:
            sources = collect_sources(paths, repo_root=REPO_ROOT)
        except (FileNotFoundError, SyntaxError) as e:
            print(f"lint_tpu: {e}", file=sys.stderr)
            return 2
        count, unmarked = write_sync_budget(sources)
        if unmarked:
            for line in unmarked:
                print(f"lint_tpu: {line}", file=sys.stderr)
            print("lint_tpu: refusing to write sync_budget.json — annotate "
                  "the sites above first (the reason is the manifest's "
                  "value)", file=sys.stderr)
            return 1
        print(f"lint_tpu: wrote {count} sync-budget entr(ies) to "
              f"{SYNC_BUDGET_PATH.name}")
        return 0

    baseline = set() if (args.no_baseline or args.write_baseline) \
        else load_baseline(args.baseline)
    try:
        violations, sources = lint_paths(paths, rules, baseline=baseline)
    except FileNotFoundError as e:
        print(f"lint_tpu: no such file: {e.filename}", file=sys.stderr)
        return 2
    except SyntaxError as e:
        print(f"lint_tpu: cannot parse {e.filename}:{e.lineno}: {e.msg}",
              file=sys.stderr)
        return 2

    if args.write_baseline:
        write_baseline(args.baseline, violations)
        print(f"lint_tpu: wrote {len(violations)} grandfathered "
              f"violation(s) to {args.baseline}")
        return 0

    render = render_json if args.format == "json" else render_text
    print(render(violations, sources, rules))
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
