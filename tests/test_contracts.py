"""graftcontract (GL201–GL203) tests — ISSUE 15.

Mirrors the planlint suite's structure: per-rule positive / negative /
suppressed triples on synthetic fixtures, a tamper suite that mutates
real-tree copies and asserts exactly the right rule fires (with the site
and scope named), and the acceptance gate — a zero-violation run over the
shipped surface with the committed ``sync_budget.json`` manifest.

Marker: ``contracts`` — run standalone with ``pytest -m contracts``.
"""

import pathlib
import textwrap

import pytest

from matcha_tpu.analysis import (
    CONTRACT_RULES,
    collect_sync_sites,
    lint_paths,
    lint_source,
    load_sync_budget,
    render_text,
    write_sync_budget,
)
from matcha_tpu.analysis.contracts import (
    GL201SyncBudget,
    GL202JournalSchema,
    GL203CheckpointEvolution,
    extract_registry,
)
from matcha_tpu.analysis.engine import load_source

pytestmark = pytest.mark.contracts

REPO = pathlib.Path(__file__).resolve().parents[1]
LINT_TARGETS = ["matcha_tpu", "train_tpu.py", "plan_tpu.py", "obs_tpu.py",
                "serve_tpu.py"]


def _src(tmp_path, code, filename="snippet.py"):
    f = tmp_path / filename
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(code))
    return load_source(f, REPO)


def _lint(tmp_path, code, rules, filename="snippet.py"):
    return lint_source(_src(tmp_path, code, filename), rules)


def _ids(violations):
    return sorted({v.rule for v in violations})


# ===================================================================== GL201

def test_gl201_names_the_step_scope_of_an_injected_item(tmp_path):
    """The ISSUE tamper case: a per-step ``.item()`` in a fixture train
    loop fires GL201 with the offending loop scope named."""
    vs = _lint(tmp_path, """
        import numpy as np

        # graftcontract: root
        def train(loader, epochs):
            state = init()
            while epochs:
                for batch in loader:
                    for micro in batch:
                        state, loss = step(state, micro)
                        log(loss.item())
            return state
    """, [GL201SyncBudget(manifest={"allowed": []})])
    assert _ids(vs) == ["GL201"]
    assert "`.item()` at **step** scope" in vs[0].message
    assert "root `train`" in vs[0].message


def test_gl201_classifies_batch_scope_and_interprocedural_reach(tmp_path):
    """A sync buried in a helper called from the batch loop is found
    through the call graph and classified by the *call site's* nesting."""
    vs = _lint(tmp_path, """
        import numpy as np

        def readback(m):
            return float(np.asarray(m))

        # graftcontract: root
        def train(loader, epochs):
            while epochs:
                for batch in loader:
                    readback(batch)
    """, [GL201SyncBudget(manifest={"allowed": []})])
    assert _ids(vs) == ["GL201"]
    assert "`np.asarray` at **batch** scope" in vs[0].message


def test_gl201_compiled_functions_are_step_scope(tmp_path):
    """A sync inside a jit-compiled function reachable from the root is
    per-step regardless of python loop nesting."""
    vs = _lint(tmp_path, """
        import jax

        @jax.jit
        def step(s):
            return s.mean().item()

        # graftcontract: root
        def train(epochs):
            s = 0
            while epochs:
                s = step(s)
    """, [GL201SyncBudget(manifest={"allowed": []})])
    assert _ids(vs) == ["GL201"]
    assert "**step** scope" in vs[0].message


def test_gl201_run_scope_is_exempt(tmp_path):
    """Once-per-run syncs (outside every loop) cannot hurt scaling and
    need no annotation."""
    vs = _lint(tmp_path, """
        import numpy as np

        # graftcontract: root
        def train(loader, state):
            warm = np.asarray(state)          # run scope: exempt
            jax.block_until_ready(state)      # run scope: exempt
            return warm
    """, [GL201SyncBudget(manifest={"allowed": []})])
    assert vs == []


def test_gl201_without_a_root_marker_is_silent(tmp_path):
    vs = _lint(tmp_path, """
        import numpy as np

        def train(loader, epochs):
            while epochs:
                x = np.asarray(loader)
    """, [GL201SyncBudget(manifest={"allowed": []})])
    assert vs == []


def test_gl201_annotated_and_budgeted_site_is_clean(tmp_path):
    src = _src(tmp_path, """
        import numpy as np

        # graftcontract: root
        def train(loader, epochs):
            while epochs:
                # graftcontract: sync — the one epoch-boundary readback
                tel = np.asarray(loader)
    """)
    manifest = {"allowed": [{
        "path": src.path, "root": "train", "scope": "epoch",
        "call": "np.asarray", "line": 8,
        "reason": "the one epoch-boundary readback"}]}
    assert lint_source(src, [GL201SyncBudget(manifest=manifest)]) == []


def test_gl201_annotated_but_unbudgeted_site_exceeds_the_budget(tmp_path):
    vs = _lint(tmp_path, """
        import numpy as np

        # graftcontract: root
        def train(loader, epochs):
            while epochs:
                # graftcontract: sync — not in the manifest
                tel = np.asarray(loader)
    """, [GL201SyncBudget(manifest={"allowed": []})])
    assert _ids(vs) == ["GL201"]
    assert "exceeds the committed sync budget" in vs[0].message


def test_gl201_deannotated_budgeted_site_reports_once(tmp_path):
    """Removing the annotation above a manifest-covered site yields exactly
    the 'unannotated' violation — not an extra stale-manifest diagnostic
    whose --write-sync-budget remedy would refuse to run anyway."""
    src = _src(tmp_path, """
        import numpy as np

        # graftcontract: root
        def train(loader, epochs):
            while epochs:
                tel = np.asarray(loader)
    """)
    manifest = {"allowed": [{
        "path": src.path, "root": "train", "scope": "epoch",
        "call": "np.asarray", "line": 7, "reason": "was annotated once"}]}
    vs = lint_source(src, [GL201SyncBudget(manifest=manifest)])
    assert len(vs) == 1
    assert "annotate with" in vs[0].message
    assert "stale" not in vs[0].message


def test_gl201_stale_manifest_entry_fires(tmp_path):
    src = _src(tmp_path, """
        # graftcontract: root
        def train(epochs):
            while epochs:
                pass
    """)
    manifest = {"allowed": [{
        "path": src.path, "root": "train", "scope": "epoch",
        "call": "np.asarray", "line": 99, "reason": "long gone"}]}
    vs = lint_source(src, [GL201SyncBudget(manifest=manifest)])
    assert _ids(vs) == ["GL201"]
    assert "stale" in vs[0].message


def test_gl201_suppression_with_reason(tmp_path):
    vs = _lint(tmp_path, """
        import numpy as np

        # graftcontract: root
        def train(loader, epochs):
            while epochs:
                # graftlint: disable=GL201 — fixture exercises the engine
                tel = np.asarray(loader)
    """, [GL201SyncBudget(manifest={"allowed": []})])
    assert vs == []


def test_gl201_two_syncs_on_one_line_need_two_budget_slots(tmp_path):
    """Distinct sync calls sharing a line each consume a manifest slot — a
    second readback smuggled onto an already-budgeted line still trips the
    prover."""
    src = _src(tmp_path, """
        import numpy as np

        # graftcontract: root
        def train(loader, epochs):
            while epochs:
                # graftcontract: sync — boundary readback pair
                a, b = np.asarray(loader), np.asarray(loader)
    """)
    one_slot = {"allowed": [{
        "path": src.path, "root": "train", "scope": "epoch",
        "call": "np.asarray", "line": 8, "reason": "boundary readback"}]}
    vs = lint_source(src, [GL201SyncBudget(manifest=one_slot)])
    assert _ids(vs) == ["GL201"]
    assert "exceeds the committed sync budget (1 allowed" in vs[0].message
    two_slots = {"allowed": one_slot["allowed"] * 2}
    assert lint_source(src, [GL201SyncBudget(manifest=two_slots)]) == []


def test_gl201_lambda_bodies_execute_only_when_called(tmp_path):
    """A lambda *defined* in the loop mints no site; *calling* it by name
    does — mirroring scan_body's def/class rule."""
    defined_only = collect_sync_sites(_src(tmp_path, """
        # graftcontract: root
        def train(rec, epochs):
            while epochs:
                cb = lambda v: v.item()
                rec.on_epoch(cb)
    """, "defined.py"))
    assert defined_only == []
    called = collect_sync_sites(_src(tmp_path, """
        # graftcontract: root
        def train(rec, epochs):
            while epochs:
                cb = lambda v: v.item()
                cb(rec)
    """, "called.py"))
    assert [(s, c) for _, s, c, _ in called] == [("epoch", ".item()")]


def test_gl201_dict_iteration_does_not_escalate_scope(tmp_path):
    """A metrics-dict `for k, v in d.items()` loop is bounded host
    iteration, not training granularity — a readback inside it keeps the
    call site's scope instead of minting a phantom per-'step' slot."""
    sites = collect_sync_sites(_src(tmp_path, """
        import numpy as np

        def flush(metrics, sums):
            for k, v in metrics.items():
                sums[k] = sums.get(k, 0.0) + float(np.sum(v))

        # graftcontract: root
        def train(loader, epochs, sums):
            while epochs:
                for batch in loader:
                    flush(batch, sums)
                flush(loader, sums)
    """))
    assert {(scope, call) for _, scope, call, _ in sites} == \
        {("batch", "np.sum"), ("epoch", "np.sum")}


def test_gl201_block_until_ready_label_is_receiver_shape_invariant(tmp_path):
    """`jax.block_until_ready(x)` and a method-form receiver get the SAME
    manifest label, so refactoring between them cannot break the budget."""
    sites = collect_sync_sites(_src(tmp_path, """
        import jax

        # graftcontract: root
        def train(state, epochs):
            while epochs:
                jax.block_until_ready(state)
                get_state().params.block_until_ready()
    """))
    assert {call for _, _, call, _ in sites} == {"block_until_ready"}


def test_gl201_write_sync_budget_refuses_unannotated_sites(tmp_path):
    src = _src(tmp_path, """
        import numpy as np

        # graftcontract: root
        def train(loader, epochs):
            while epochs:
                tel = np.asarray(loader)
    """)
    out = tmp_path / "budget.json"
    count, unmarked = write_sync_budget([src], out)
    assert count == 0 and len(unmarked) == 1
    assert "np.asarray" in unmarked[0] and not out.exists()


def test_gl201_write_sync_budget_roundtrip(tmp_path):
    src = _src(tmp_path, """
        import numpy as np

        # graftcontract: root
        def train(loader, epochs):
            while epochs:
                # graftcontract: sync — boundary readback, two-line
                # annotation form with a continuation
                tel = np.asarray(loader)
    """)
    out = tmp_path / "budget.json"
    count, unmarked = write_sync_budget([src], out)
    assert (count, unmarked) == (1, [])
    entries = load_sync_budget(out)
    assert entries[0]["scope"] == "epoch"
    assert entries[0]["call"] == "np.asarray"
    # continuation comment lines join into the manifest reason
    assert entries[0]["reason"] == ("boundary readback, two-line "
                                    "annotation form with a continuation")
    # the written manifest lints the fixture clean
    assert lint_source(src, [GL201SyncBudget(manifest=out)]) == []


# ===================================================================== GL202

def test_gl202_unregistered_kind_fires(tmp_path):
    vs = _lint(tmp_path, """
        def report(recorder):
            recorder.log_event("warp_core_breach", epoch=1)
    """, [GL202JournalSchema()])
    assert _ids(vs) == ["GL202"]
    assert "unregistered kind" in vs[0].message
    assert "warp_core_breach" in vs[0].message


def test_gl202_missing_required_field_fires(tmp_path):
    vs = _lint(tmp_path, """
        from matcha_tpu.obs.journal import make_event

        def emit():
            return make_event("checkpoint", 0.0, epoch=3)  # path missing
    """, [GL202JournalSchema()])
    assert _ids(vs) == ["GL202"]
    assert "missing required field(s) ['path']" in vs[0].message


def test_gl202_splat_and_compliant_sites_are_silent(tmp_path):
    vs = _lint(tmp_path, """
        def emit(recorder, tel, kind):
            recorder.log_event("checkpoint", epoch=3, path="/tmp/x")
            recorder.log_event("telemetry", epoch=3, **tel)  # open set
            recorder.log_event(kind, epoch=3)  # forwarding wrapper
            recorder.log_fault("rollback", epoch=3)
    """, [GL202JournalSchema()])
    assert vs == []


def test_gl202_keyword_kind_is_checked_too(tmp_path):
    """A literal kind passed as `kind=` must not bypass the verifier."""
    vs = _lint(tmp_path, """
        from matcha_tpu.obs.journal import make_event

        def emit():
            return make_event(kind="warp_core_breach", t=0.0)
    """, [GL202JournalSchema()])
    assert _ids(vs) == ["GL202"]
    assert "unregistered kind" in vs[0].message


def test_gl202_log_fault_of_a_non_fault_kind_fires(tmp_path):
    vs = _lint(tmp_path, """
        def emit(recorder):
            recorder.log_fault("telemetry", epoch=3)
    """, [GL202JournalSchema()])
    assert _ids(vs) == ["GL202"]
    assert "faults.json view would silently drop it" in vs[0].message


def test_gl202_suppression_with_reason(tmp_path):
    vs = _lint(tmp_path, """
        def emit(recorder):
            # graftlint: disable=GL202 — fixture constructs a bad event
            recorder.log_event("warp_core_breach", epoch=1)
    """, [GL202JournalSchema()])
    assert vs == []


def test_gl202_registry_extraction_folds_the_real_registry():
    import ast

    reg, _ = extract_registry(
        ast.parse((REPO / "matcha_tpu/obs/journal.py").read_text()))
    assert reg["SCHEMA_VERSION"] == max(reg["ACCEPTED_VERSIONS"])
    assert "backend" in reg["EVENT_KINDS"]
    assert reg["KIND_MIN_VERSION"]["backend"] == 5
    assert reg["KIND_MIN_VERSION"]["control"] == 6
    assert reg["KIND_MIN_VERSION"]["promotion"] == 6
    assert reg["KIND_MIN_VERSION"]["recovery"] == 7
    assert reg["KIND_MIN_VERSION"]["spans"] == 8
    assert reg["KIND_MIN_VERSION"]["fwd_bwd"] == reg["SCHEMA_VERSION"]
    assert set(reg["REQUIRED_FIELDS"]) <= set(reg["EVENT_KINDS"])


# ------------------------------------------------- GL202 registry tampering

def _tampered_journal(tmp_path, old, new, filename="journal.py"):
    text = (REPO / "matcha_tpu/obs/journal.py").read_text()
    assert old in text, f"tamper anchor rotted: {old!r}"
    f = tmp_path / filename
    f.write_text(text.replace(old, new))
    return load_source(f, REPO)


def test_gl202_new_kind_without_min_version_fires(tmp_path):
    src = _tampered_journal(
        tmp_path, '"retrace", "bench",', '"retrace", "bench", "sneaky",')
    vs = lint_source(src, list(CONTRACT_RULES))
    assert _ids(vs) == ["GL202"]
    assert "without a KIND_MIN_VERSION entry" in vs[0].message


def test_gl202_min_version_beyond_schema_version_fires(tmp_path):
    src = _tampered_journal(
        tmp_path, '**{k: 9 for k in V9_KINDS}}', '**{k: 10 for k in V9_KINDS}}')
    vs = lint_source(src, list(CONTRACT_RULES))
    assert any("SCHEMA_VERSION" in v.message and v.rule == "GL202"
               for v in vs)


def test_gl202_version_bump_without_a_new_kind_fires(tmp_path):
    src = _tampered_journal(
        tmp_path, "SCHEMA_VERSION = 9\nACCEPTED_VERSIONS = "
                  "frozenset({1, 2, 3, 4, 5, 6, 7, 8, 9})",
        "SCHEMA_VERSION = 10\nACCEPTED_VERSIONS = "
        "frozenset({1, 2, 3, 4, 5, 6, 7, 8, 9, 10})")
    vs = lint_source(src, list(CONTRACT_RULES))
    assert _ids(vs) == ["GL202"]
    assert "no kind is introduced at v10" in vs[0].message


# ===================================================================== GL203

_FIXTURE_CHECKPOINT = """
    import dataclasses

    class TrainState:
        params: object
        step: object
        mix_pending: object = ()
        telemetry: object = ()
        {extra_field}

    def save_checkpoint(directory, state, epoch):
        state = state.replace(telemetry=())
        write(directory, state, epoch)

    def restore_checkpoint(directory, template):
        template = template.replace(telemetry=())
        fields = dataclasses.asdict(template)
        for drop in ({ladder}):
            older = {{k: v for k, v in fields.items() if k not in drop}}
            restored = try_restore(older)
            if restored is not None:
                return restored
        raise ValueError
"""


def _checkpoint_fixture(tmp_path, extra_field="", ladder='("mix_pending",),'):
    return _src(tmp_path, _FIXTURE_CHECKPOINT.format(
        extra_field=extra_field, ladder=ladder), "checkpoint.py")


def test_gl203_compliant_fixture_is_clean(tmp_path):
    src = _checkpoint_fixture(tmp_path)
    assert lint_source(src, [GL203CheckpointEvolution()]) == []


def test_gl203_uncovered_evolution_field_fires(tmp_path):
    src = _checkpoint_fixture(tmp_path,
                              extra_field="mix_ages: object = ()")
    vs = lint_source(src, [GL203CheckpointEvolution()])
    assert _ids(vs) == ["GL203"]
    assert "`mix_ages`" in vs[0].message
    assert "no reconciliation rule" in vs[0].message


def test_gl203_ladder_dropping_a_dead_field_fires(tmp_path):
    """The ISSUE tamper case, inverse direction: a TrainState field
    deleted while the fixture restore ladder still drops it."""
    src = _checkpoint_fixture(tmp_path,
                              ladder='("mix_pending",), ("ghost",)')
    vs = lint_source(src, [GL203CheckpointEvolution()])
    assert _ids(vs) == ["GL203"]
    assert "`ghost`" in vs[0].message and "stale generation" in vs[0].message


def test_gl203_asymmetric_strip_sets_fire(tmp_path):
    src = _src(tmp_path, _FIXTURE_CHECKPOINT.format(
        extra_field="", ladder='("mix_pending",),').replace(
        "state = state.replace(telemetry=())",
        "state = state.replace(telemetry=(), mix_pending=())"),
        "checkpoint.py")
    vs = lint_source(src, [GL203CheckpointEvolution()])
    assert _ids(vs) == ["GL203"]
    assert "asymmetric strip" in vs[0].message


def test_gl203_resolves_train_state_through_the_state_sibling(tmp_path):
    (tmp_path / "state.py").write_text(textwrap.dedent("""
        class TrainState:
            params: object
            new_field: object = ()
    """))
    vs = _lint(tmp_path, """
        import dataclasses
        from .state import TrainState

        def restore_checkpoint(directory, template):
            fields = dataclasses.asdict(template)
            for drop in (("other",),):
                pass
    """, [GL203CheckpointEvolution()], filename="checkpoint.py")
    messages = " | ".join(v.message for v in vs)
    assert "`new_field`" in messages      # uncovered evolution field
    assert "`other`" in messages          # stale ladder generation


def test_gl203_suppression_with_reason(tmp_path):
    code = _FIXTURE_CHECKPOINT.format(
        extra_field="mix_ages: object = ()", ladder='("mix_pending",),')
    code = code.replace(
        "    def restore_checkpoint(directory, template):",
        "    # graftlint: disable=GL203 — fixture predates the field\n"
        "    def restore_checkpoint(directory, template):")
    f = tmp_path / "checkpoint.py"
    f.write_text(textwrap.dedent(code))
    assert lint_source(load_source(f, REPO),
                       [GL203CheckpointEvolution()]) == []


def test_gl203_tamper_real_checkpoint_ladder(tmp_path):
    """The ISSUE tamper case on the real tree: remove mix_pending's ladder
    generation from a copy of train/checkpoint.py — exactly GL203 fires,
    naming the field."""
    text = (REPO / "matcha_tpu/train/checkpoint.py").read_text()
    anchor = ('"telemetry",\n'
              '                      "mix_pending")')
    assert anchor in text, "tamper anchor rotted"
    (tmp_path / "state.py").write_text(
        (REPO / "matcha_tpu/train/state.py").read_text())
    f = tmp_path / "checkpoint.py"
    f.write_text(text.replace(anchor, '"telemetry")'))
    vs = lint_source(load_source(f, REPO), list(CONTRACT_RULES))
    assert _ids(vs) == ["GL203"]
    assert "`mix_pending`" in vs[0].message


# ============================================================ the real tree

def test_shipped_tree_is_contract_clean():
    """The acceptance gate: GL201–GL203 run green over the full shipped
    surface with the committed sync_budget.json manifest."""
    violations, sources = lint_paths(LINT_TARGETS, CONTRACT_RULES,
                                     baseline=set(), repo_root=REPO)
    assert len(sources) > 50
    assert not violations, \
        "\n" + render_text(violations, sources, CONTRACT_RULES)


def test_committed_sync_budget_matches_the_annotated_tree():
    """The manifest is FULL and fresh: regenerating it from the annotated
    tree reproduces the committed entries (line numbers are informational
    and excluded — matching is by (path, root, scope, call, reason))."""
    committed = load_sync_budget(REPO / "sync_budget.json")
    assert committed, "shipped manifest is empty — GL201 would be vacuous"
    regenerated = []
    _, sources = lint_paths(LINT_TARGETS, (), baseline=set(), repo_root=REPO)
    for src in sources:
        sites = collect_sync_sites(src)
        if sites:
            from matcha_tpu.analysis.contracts import parse_contract_markers

            _, markers = parse_contract_markers(src.lines)
            for root, scope, call, line in sites:
                regenerated.append(
                    (src.path, root, scope, call, markers.get(line)))
    as_committed = sorted((e["path"], e["root"], e["scope"], e["call"],
                           e["reason"]) for e in committed)
    assert sorted(regenerated) == as_committed, \
        "sync_budget.json is stale — run `python lint_tpu.py --write-sync-budget`"


def test_every_committed_budget_entry_has_a_real_reason():
    for e in load_sync_budget(REPO / "sync_budget.json"):
        assert e["reason"] and len(e["reason"]) > 10, e
        assert e["scope"] in ("epoch", "batch", "step"), e


def test_the_committed_budget_covers_the_one_epoch_barrier():
    """The PR-7/PR-10 pin, now a manifest fact: exactly one
    block_until_ready barrier at epoch scope in the train loop."""
    entries = [e for e in load_sync_budget(REPO / "sync_budget.json")
               if e["call"] == "block_until_ready"]
    assert len(entries) == 1
    assert entries[0]["scope"] == "epoch"
    assert entries[0]["path"] == "matcha_tpu/train/loop.py"
