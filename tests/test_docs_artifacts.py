"""Docs must not cite benchmark artifacts that don't exist.

Round 5 shipped README/DESIGN text describing ``benchmarks/train_step_r5.json``
and ``benchmarks/scale_probe_r5.json`` as committed measurements when neither
file existed — promissory tense laundered into evidence.  This guard scans
``README.md`` and ``docs/*.md`` for every ``benchmarks/*.json`` reference and
every round-suffixed ``*_rN.*`` cite and fails unless the artifact is
committed.  What has not been measured is written "not measured", not
pointed at a file that may appear later.
"""

import pathlib
import re

REPO = pathlib.Path(__file__).resolve().parents[1]
# jsonl? with a word-boundary: "baselines_smoke.jsonl" must match as the
# .jsonl file it names, not as a phantom .json prefix of it
REF = re.compile(r"benchmarks/[A-Za-z0-9_.\-]*\.jsonl?\b")
# round-suffixed deliverables (`lint_stamp_r6.json`, `roofline_r6.md`, …)
# were often cited bare — without the benchmarks/ prefix REF keys on — and
# in markdown as well as JSON.  The `_r<N>.` suffix is the promissory-tense
# marker: each cite must resolve on disk.
ROUND_REF = re.compile(r"\b[A-Za-z0-9_\-]+_r\d+\.(?:jsonl?|md)\b")


def _docs():
    # DESIGN.md lives in docs/ and is covered by the glob — listed
    # explicitly so a future docs/ re-layout cannot silently drop the
    # round-5 offender file from the scan (ISSUE 9 satellite)
    design = REPO / "docs" / "DESIGN.md"
    out = [REPO / "README.md", *sorted((REPO / "docs").glob("*.md"))]
    assert design in out, "docs/DESIGN.md fell off the scan surface"
    return out


def _prose_lines(doc):
    """(lineno, line) for every line outside fenced code blocks — usage
    examples legitimately name placeholder files like ``BENCH_r05.json``;
    evidence claims live in prose."""
    fenced = False
    for lineno, line in enumerate(doc.read_text().splitlines(), 1):
        if line.lstrip().startswith("```"):
            fenced = not fenced
            continue
        if not fenced:
            yield lineno, line


def test_doc_benchmark_artifact_references_exist():
    missing = []
    for doc in _docs():
        for lineno, line in enumerate(doc.read_text().splitlines(), 1):
            for ref in REF.findall(line):
                if not (REPO / ref).exists():
                    missing.append(f"{doc.name}:{lineno} -> {ref}")
    assert not missing, (
        "docs cite uncommitted benchmark artifacts (commit the artifact, "
        f"or say 'not measured'): {missing}"
    )


def test_round_artifact_cites_resolve():
    """Every ``*_rN.*`` artifact cite in prose exists under ``benchmarks/``
    (or at its stated path) — the promissory-tense laundering guard,
    extended past REF's ``benchmarks/*.json`` surface to bare and
    markdown-format cites."""
    bad = []
    for doc in _docs():
        for lineno, line in _prose_lines(doc):
            for ref in ROUND_REF.findall(line):
                if not ((REPO / "benchmarks" / ref).exists()
                        or (REPO / ref).exists()):
                    bad.append(f"{doc.name}:{lineno} -> {ref}")
    assert not bad, (
        f"docs cite round-suffixed artifacts that are not committed: {bad}")


def test_scanner_sees_the_committed_artifacts():
    """The guard is only meaningful if the reference pattern actually hits:
    the docs do cite committed artifacts, and those all resolve."""
    hits = [ref for doc in _docs() for ref in REF.findall(doc.read_text())]
    assert hits, "no benchmarks/*.json references found — pattern rotted?"
    assert any((REPO / ref).exists() for ref in hits)


# --------------------------------------------------------------- lint stamps
# `lint_tpu.py --format json` renders a stamp a measurement can be recorded
# next to, as evidence the measured tree passed graftlint.  Pin the stamp
# schema here so (a) every committed
# stamp parses as what the docs claim it is, and (b) the renderer cannot
# silently change shape between sessions — the same contract style as the
# benchmark-reference scan above.

_STAMP_KEYS = {"violations", "files_checked", "rules", "clean"}


def _assert_stamp_schema(data, where):
    assert _STAMP_KEYS <= set(data), (
        f"{where}: lint stamp missing keys {_STAMP_KEYS - set(data)}")
    assert isinstance(data["clean"], bool), where
    assert isinstance(data["files_checked"], int), where
    assert isinstance(data["violations"], list), where
    for v in data["violations"]:
        assert {"rule", "path", "line", "col", "message"} <= set(v), (
            f"{where}: malformed violation entry {v}")
    rule_ids = {r["id"] for r in data["rules"]}
    # schema v2 (ISSUE 15) added the graftcontract family; v3 (ISSUE 20)
    # adds graftdur — a full-run stamp must carry all four families; a
    # stamp without GL201 or GL301 was produced by an older tree and is
    # not evidence for this one
    assert {"GL001", "GL101", "GL201", "GL301"} <= rule_ids, (
        f"{where}: stamp rule set {sorted(rule_ids)} is missing the core, "
        f"SPMD, graftcontract, or graftdur family — it was not produced "
        f"by the full default run")
    assert data["clean"] == (not data["violations"]), where


def test_committed_lint_stamps_conform_to_schema():
    import json

    for stamp in sorted((REPO / "benchmarks").glob("lint_stamp*.json")):
        _assert_stamp_schema(json.loads(stamp.read_text()), stamp.name)


def test_lint_stamp_renderer_emits_the_pinned_schema():
    """Non-vacuous even while no stamp is committed: render a stamp
    in-process and hold it to the same schema the
    committed ones must satisfy."""
    import json

    from matcha_tpu.analysis import ALL_RULES, lint_paths, render_json

    violations, sources = lint_paths(
        ["lint_tpu.py"], ALL_RULES, baseline=set(), repo_root=REPO)
    data = json.loads(render_json(violations, sources, ALL_RULES))
    _assert_stamp_schema(data, "render_json")
    assert data["files_checked"] == 1


def test_contracts_stamp_schema():
    """The graftcontract verdict (`--rules GL201,GL202,GL203 --format
    json`) is stamped the same way: pin that shape too — committed stamps and the
    renderer both — so the sync-budget evidence cannot silently change
    schema between sessions."""
    import json

    from matcha_tpu.analysis import lint_paths, render_json, rules_by_id

    contract_rules = rules_by_id(["GL201", "GL202", "GL203"])

    def check(data, where):
        assert _STAMP_KEYS <= set(data), where
        assert {r["id"] for r in data["rules"]} == \
            {"GL201", "GL202", "GL203"}, where
        assert data["clean"] == (not data["violations"]), where

    for stamp in sorted((REPO / "benchmarks").glob("contracts_stamp*.json")):
        check(json.loads(stamp.read_text()), stamp.name)
    violations, sources = lint_paths(
        ["lint_tpu.py"], contract_rules, baseline=set(), repo_root=REPO)
    check(json.loads(render_json(violations, sources, contract_rules)),
          "render_json")
