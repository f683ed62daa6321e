"""graftdur (GL301–GL304) tests — ISSUE 20.

Mirrors the graftcontract suite's structure: per-rule positive /
negative / suppressed triples on synthetic fixtures, a tamper suite that
mutates real-tree copies and asserts exactly the right rule fires (with
the site named), the acceptance gate — a zero-violation run over the
shipped surface with the EMPTY committed baseline — and runtime tests
for the seam itself: ``utils.atomicio.atomic_publish`` under injected
ENOSPC, and the controller spec-publish regression (fixed-name `.tmp`
squatters) the GL301 bugfix is pinned against.

Marker: ``durability`` — run standalone with ``pytest -m durability``.
"""

import ast
import json
import os
import pathlib
import textwrap

import pytest

from matcha_tpu.analysis import (
    DURABILITY_RULES,
    WATCHED_PATH_VOCABULARY,
    lint_paths,
    lint_source,
)
from matcha_tpu.analysis.durability import (
    GL301AtomicPublish,
    GL302SingleWriterJournal,
    GL303BestEffortIO,
    GL304ThreadSharedMutation,
    parse_durability_markers,
)
from matcha_tpu.analysis.engine import load_source
from matcha_tpu.obs.bestio import FaultyFS, install_fs
from matcha_tpu.utils.atomicio import atomic_publish

pytestmark = pytest.mark.durability

REPO = pathlib.Path(__file__).resolve().parents[1]
LINT_TARGETS = ["matcha_tpu", "train_tpu.py", "plan_tpu.py", "obs_tpu.py",
                "serve_tpu.py"]


@pytest.fixture(autouse=True)
def _direct_fs():
    """Every test starts and ends on the production fs seam."""
    install_fs(None)
    yield
    install_fs(None)


def _src(tmp_path, code, filename="snippet.py"):
    f = tmp_path / filename
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(code))
    return load_source(f, REPO)


def _lint(tmp_path, code, rules, filename="snippet.py"):
    return lint_source(_src(tmp_path, code, filename), rules)


def _ids(violations):
    return sorted({v.rule for v in violations})


# ===================================================================== GL301

def test_gl301_direct_write_of_watched_path_fires(tmp_path):
    vs = _lint(tmp_path, """
        import json

        def publish(doc):
            with open("runs/control.json", "w") as f:
                json.dump(doc, f)
    """, [GL301AtomicPublish()])
    assert _ids(vs) == ["GL301"]
    assert "direct write-mode open" in vs[0].message
    assert "atomic_publish" in vs[0].message


def test_gl301_fixed_name_tmp_publish_fires(tmp_path):
    """The bugfix's shape: ``spec_path + ".tmp"`` is a shared mutable
    name — the variant message names the squatting hazard."""
    vs = _lint(tmp_path, """
        import json
        import os

        def publish(doc, spec_path):
            tmp = spec_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, spec_path)
    """, [GL301AtomicPublish()])
    assert _ids(vs) == ["GL301"]
    assert "fixed-name `.tmp` publish" in vs[0].message


def test_gl301_hand_rolled_mkstemp_seam_fires(tmp_path):
    """A second mkstemp+rename implementation is a violation even when
    it is correct — the repo keeps ONE publish protocol."""
    vs = _lint(tmp_path, """
        import json
        import os
        import tempfile

        def publish(doc, control_path):
            fd, tmp = tempfile.mkstemp(dir=".")
            with os.fdopen(fd, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, control_path)
    """, [GL301AtomicPublish()])
    assert _ids(vs) == ["GL301"]
    assert "hand-rolled tempfile+rename" in vs[0].message


def test_gl301_negative_unwatched_append_and_read(tmp_path):
    """Writes to unwatched names, appends, and reads are out of scope."""
    assert _lint(tmp_path, """
        def fine(doc):
            with open("notes.txt", "w") as f:
                f.write(str(doc))
            with open("runs/control.json") as f:
                return f.read()
    """, [GL301AtomicPublish()]) == []


def test_gl301_suppression_silences_with_reason(tmp_path):
    assert _lint(tmp_path, """
        import json

        def publish(doc):
            # graftlint: disable=GL301 — fixture: torn-state injector
            with open("runs/control.json", "w") as f:
                json.dump(doc, f)
    """, [GL301AtomicPublish()]) == []


# ===================================================================== GL302

def test_gl302_unannotated_supervisor_append_fires(tmp_path):
    vs = _lint(tmp_path, """
        from matcha_tpu.obs.journal import append_journal_record

        def note(journal_path):
            append_journal_record(journal_path, "control", action="x",
                                  applied=True, reason="r", epoch=-1)
    """, [GL302SingleWriterJournal()])
    assert _ids(vs) == ["GL302"]
    assert "single-writer annotation" in vs[0].message


def test_gl302_single_writer_annotation_silences(tmp_path):
    assert _lint(tmp_path, """
        from matcha_tpu.obs.journal import append_journal_record

        def note(journal_path):
            # graftdur: single-writer — only runs between lifetimes
            append_journal_record(journal_path, "control", action="x",
                                  applied=True, reason="r", epoch=-1)
    """, [GL302SingleWriterJournal()]) == []


def test_gl302_second_writer_fires(tmp_path):
    vs = _lint(tmp_path, """
        def stomp(journal_path):
            with open(journal_path, "wb") as f:
                f.write(b"{}")
    """, [GL302SingleWriterJournal()])
    assert _ids(vs) == ["GL302"]
    assert "second" in vs[0].message and "writer" in vs[0].message


def test_gl302_bare_read_fires_and_names_the_readers(tmp_path):
    vs = _lint(tmp_path, """
        def count(journal_path):
            with open(journal_path) as f:
                return sum(1 for line in f)
    """, [GL302SingleWriterJournal()])
    assert _ids(vs) == ["GL302"]
    assert "bare read" in vs[0].message
    assert "read_journal" in vs[0].message


def test_gl302_negative_non_journal_paths(tmp_path):
    assert _lint(tmp_path, """
        def fine(csv_path):
            with open(csv_path, "a") as f:
                f.write("1,2\\n")
            with open(csv_path) as f:
                return f.read()
    """, [GL302SingleWriterJournal()]) == []


# ===================================================================== GL303

def test_gl303_bare_write_in_root_loop_fires(tmp_path):
    vs = _lint(tmp_path, """
        # graftcontract: root
        def train(loader, epochs):
            state = init()
            for epoch in range(epochs):
                with open("hb.json", "w") as f:
                    f.write(str(epoch))
            return state
    """, [GL303BestEffortIO()])
    assert _ids(vs) == ["GL303"]
    assert "**epoch** scope" in vs[0].message
    assert "root `train`" in vs[0].message


def test_gl303_interprocedural_reach_and_rename(tmp_path):
    """An os.replace buried in a helper called per-batch is found
    through the call graph."""
    vs = _lint(tmp_path, """
        import os

        def swap(a, b):
            os.replace(a, b)

        # graftcontract: root
        def train(loader, epochs):
            for epoch in range(epochs):
                for batch in loader:
                    swap("x", "y")
    """, [GL303BestEffortIO()])
    assert _ids(vs) == ["GL303"]
    assert "os.replace" in vs[0].message


def test_gl303_negative_seam_and_setup_scope(tmp_path):
    """fs-seam IO inside the loop and bare IO at setup scope are fine."""
    assert _lint(tmp_path, """
        from matcha_tpu.obs.bestio import get_fs

        # graftcontract: root
        def train(loader, epochs):
            with open("boot.json", "w") as f:
                f.write("setup-scope: allowed")
            fs = get_fs()
            for epoch in range(epochs):
                with fs.open("hb.json", "w") as f:
                    f.write(str(epoch))
    """, [GL303BestEffortIO()]) == []


def test_gl303_suppression_silences_with_reason(tmp_path):
    assert _lint(tmp_path, """
        # graftcontract: root
        def train(loader, epochs):
            for epoch in range(epochs):
                # graftlint: disable=GL303 — fixture: local tmpfs only
                with open("hb.json", "w") as f:
                    f.write(str(epoch))
    """, [GL303BestEffortIO()]) == []


# ===================================================================== GL304

_HANDLER_FIXTURE = """
    from http.server import BaseHTTPRequestHandler

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):
            {body}
            self.wfile.write(b"ok")
"""


def test_gl304_handler_mutation_fires(tmp_path):
    vs = _lint(tmp_path, _HANDLER_FIXTURE.format(
        body="self.server.hits = getattr(self.server, 'hits', 0) + 1"),
        [GL304ThreadSharedMutation()])
    assert _ids(vs) == ["GL304"]
    assert "request-handler-reachable" in vs[0].message


def test_gl304_handler_lock_guard_silences(tmp_path):
    assert _lint(tmp_path, _HANDLER_FIXTURE.format(
        body="with self.server.lock:\n"
             "                self.server.hits = 1"),
        [GL304ThreadSharedMutation()]) == []


def test_gl304_supervisor_store_read_cross_thread_fires(tmp_path):
    vs = _lint(tmp_path, """
        class Daemon:
            def __init__(self):
                self.restarts = 0

            # graftcontract: root
            def run(self):
                while True:
                    self.restarts += 1

            def status(self):
                return {"restarts": self.restarts}
    """, [GL304ThreadSharedMutation()])
    assert _ids(vs) == ["GL304"]
    assert "`self.restarts`" in vs[0].message
    assert "status()" in vs[0].message  # the cross-thread reader, named


def test_gl304_negative_private_store_and_lock_guard(tmp_path):
    """Stores nothing outside the root reads, and lock-guarded stores,
    are both fine."""
    assert _lint(tmp_path, """
        import threading

        class Daemon:
            def __init__(self):
                self.restarts = 0
                self.sleep = 1.0
                self._lock = threading.Lock()

            # graftcontract: root
            def run(self):
                while True:
                    self.sleep = self.sleep * 2  # nobody else reads it
                    with self._lock:
                        self.restarts += 1

            def status(self):
                return {"restarts": self.restarts}
    """, [GL304ThreadSharedMutation()]) == []


def test_gl304_shared_state_annotation_silences(tmp_path):
    assert _lint(tmp_path, """
        class Daemon:
            # graftcontract: root
            def run(self):
                while True:
                    # graftdur: shared-state — GIL-atomic int store
                    self.restarts = 1

            def status(self):
                return {"restarts": self.restarts}
    """, [GL304ThreadSharedMutation()]) == []


def test_parse_durability_markers_attaches_to_next_code_line():
    single, shared = parse_durability_markers([
        "# graftdur: single-writer — between lifetimes",
        "append_journal_record(p, 'control')",
        "x = 1",
        "y = 2  # graftdur: shared-state — GIL-atomic",
    ])
    assert list(single) == [2]
    assert list(shared) == [4]
    assert "between lifetimes" in single[2]


# ============================================================ tamper suite

def _tampered(tmp_path, rel, old, new, filename=None):
    text = (REPO / rel).read_text()
    assert old in text, f"tamper anchor rotted in {rel}: {old!r}"
    f = tmp_path / (filename or pathlib.Path(rel).name)
    f.write_text(text.replace(old, new))
    return load_source(f, REPO)


def test_tamper_control_bare_open_fires_gl301(tmp_path):
    """Replace write_control's atomic_publish with a bare open('w') of
    the control document — exactly GL301 fires, at that site."""
    src = _tampered(
        tmp_path, "matcha_tpu/serve/control.py",
        '    atomic_publish(path, json.dumps(doc, indent=2, '
        'sort_keys=True) + "\\n",\n                   prefix=".control.")',
        '    control_path = path\n'
        '    with open(control_path, "w") as f:\n'
        '        f.write(json.dumps(doc, indent=2, sort_keys=True) '
        '+ "\\n")')
    vs = lint_source(src, list(DURABILITY_RULES))
    assert _ids(vs) == ["GL301"]
    assert "direct write-mode open" in vs[0].message


def test_tamper_second_journal_appender_fires_gl302(tmp_path):
    """Strip journal_control's single-writer annotation — the append
    site loses its contract and exactly GL302 fires."""
    src = _tampered(
        tmp_path, "matcha_tpu/serve/control.py",
        "    # graftdur: single-writer — supervisor-side append, by "
        "contract only\n    # between trainer lifetimes (documented "
        "above): no live Recorder races\n", "")
    vs = lint_source(src, list(DURABILITY_RULES))
    assert _ids(vs) == ["GL302"]
    assert "append_journal_record" in src.lines[vs[0].line - 1]


def test_tamper_bare_heartbeat_write_fires_gl303(tmp_path):
    """Swap the epoch-boundary heartbeat emit (BestEffortSink under the
    emitter) for a bare open('w') — exactly GL303 fires, at epoch
    scope, from the train root."""
    src = _tampered(
        tmp_path, "matcha_tpu/train/loop.py",
        '                    recorder.log_event("heartbeat", **hb)',
        '                    with open("heartbeat.json", "w") as f:\n'
        '                        f.write(str(hb))')
    vs = lint_source(src, list(DURABILITY_RULES))
    assert _ids(vs) == ["GL303"]
    assert "**epoch** scope" in vs[0].message
    assert "root `train`" in vs[0].message


def test_tamper_handler_mutation_fires_gl304(tmp_path):
    """Make the endpoint's request path mutate the endpoint — exactly
    GL304 fires: each request runs on its own thread."""
    src = _tampered(
        tmp_path, "matcha_tpu/serve/endpoint.py",
        "        run = self._select(query)",
        "        run = self._select(query)\n"
        "        self.last_query = query")
    vs = lint_source(src, list(DURABILITY_RULES))
    assert _ids(vs) == ["GL304"]
    assert "`self.last_query`" in vs[0].message


# ============================================================ the real tree

def test_shipped_tree_is_durability_clean():
    """The acceptance gate: GL301–GL304 run green over the full shipped
    surface with an EMPTY baseline — every legitimate exception carries
    an inline reason."""
    violations, sources = lint_paths(LINT_TARGETS, DURABILITY_RULES,
                                     baseline=set(), repo_root=REPO)
    assert len(sources) > 50
    assert not violations, "\n".join(
        f"{v.path}:{v.line}: {v.rule} {v.message}" for v in violations)


def test_committed_baseline_is_empty():
    data = json.loads((REPO / "graftlint_baseline.json").read_text())
    assert data["violations"] == []


def test_exactly_one_mkstemp_implementation():
    """The satellite's pin: one tempfile+rename implementation in the
    shipped tree — utils/atomicio.py — found by AST, not by grep (so
    comments and docstrings cannot mask a second seam)."""
    from matcha_tpu.analysis.engine import collect_sources

    offenders = []
    for src in collect_sources(LINT_TARGETS, repo_root=REPO):
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Call):
                fn = node.func
                leaf = fn.attr if isinstance(fn, ast.Attribute) else \
                    getattr(fn, "id", None)
                if leaf == "mkstemp":
                    offenders.append(f"{src.path}:{node.lineno}")
    assert offenders == ["matcha_tpu/utils/atomicio.py:62"] or (
        len(offenders) == 1
        and offenders[0].startswith("matcha_tpu/utils/atomicio.py")), \
        f"second mkstemp seam: {offenders}"


def test_watched_vocabulary_covers_the_published_artifacts():
    text = " ".join(WATCHED_PATH_VOCABULARY)
    for name in ("control.json", "events.jsonl", "faults.json",
                 "manifest", "spec_path", "digest-"):
        assert name in text


# ================================================= atomic_publish (runtime)

def test_atomic_publish_roundtrip_text_bytes_callable(tmp_path):
    p = tmp_path / "deep" / "doc.json"  # parent dirs are created
    atomic_publish(p, '{"a": 1}\n')
    assert json.loads(p.read_text()) == {"a": 1}
    atomic_publish(p, b'{"b": 2}\n', mode="wb")
    assert json.loads(p.read_text()) == {"b": 2}
    atomic_publish(p, lambda f: f.write('{"c": 3}\n'))
    assert json.loads(p.read_text()) == {"c": 3}
    assert [x for x in os.listdir(tmp_path / "deep")] == ["doc.json"]


def test_atomic_publish_rejects_non_write_modes(tmp_path):
    with pytest.raises(ValueError):
        atomic_publish(tmp_path / "x", "data", mode="a")


def test_atomic_publish_enospc_leaves_no_debris(tmp_path):
    """ENOSPC on the tempfile write: the publish raises, the target is
    untouched, and the tempfile is cleaned up — never a torn document,
    never a stale tmp for the prune sweep to find."""
    p = tmp_path / "control.json"
    atomic_publish(p, "old\n")
    install_fs(FaultyFS(mode="enospc", match=str(tmp_path)))
    with pytest.raises(OSError):
        atomic_publish(p, "new\n")
    install_fs(None)
    assert p.read_text() == "old\n"
    assert os.listdir(tmp_path) == ["control.json"]


def test_atomic_publish_crash_at_rename_preserves_old(tmp_path):
    """ENOSPC on the rename itself (the barrier the chaos mid_promote
    family kills at): old content survives, tmp is reaped."""
    p = tmp_path / "manifest.json"
    atomic_publish(p, "v1\n")
    install_fs(FaultyFS(mode="enospc", match="manifest.json", after=0))
    with pytest.raises(OSError):
        atomic_publish(p, "v2\n")
    install_fs(None)
    assert p.read_text() == "v1\n"
    assert os.listdir(tmp_path) == ["manifest.json"]


# ============================================= the spec-publish regression

def _controller(tmp_path):
    from matcha_tpu.serve.controller import Controller, ServeConfig

    cfg = dict(name="reg", model="mlp", savePath=str(tmp_path))
    return Controller(ServeConfig(config=cfg))


def test_write_spec_survives_tmp_squatter(tmp_path):
    """The GL301 bugfix's regression: a directory squatting on the old
    fixed name ``spec_path + ".tmp"`` wedged every relaunch
    (IsADirectoryError); the mkstemp publish sails past it."""
    ctl = _controller(tmp_path)
    squatter = ctl.spec_path + ".tmp"
    os.makedirs(os.path.dirname(squatter), exist_ok=True)
    os.mkdir(squatter)
    with pytest.raises(IsADirectoryError):
        with open(squatter, "w") as f:  # the pre-fix code's exact crash
            f.write("{}")
    ctl._write_spec()  # the fixed publish: unaffected
    with open(ctl.spec_path) as f:
        assert json.load(f)["config"]["name"] == "reg"
    assert os.path.isdir(squatter)  # inert, and nobody tripped on it


def test_write_spec_crash_between_write_and_rename(tmp_path):
    """Chaos-replay shape in-process: fault the publish's rename — the
    previously-published spec survives byte-for-byte and no tempfile
    debris is left for a later lifetime to trip on."""
    ctl = _controller(tmp_path)
    ctl._write_spec()
    before = pathlib.Path(ctl.spec_path).read_bytes()
    ctl.config["lr"] = 0.5
    install_fs(FaultyFS(mode="enospc",
                        match=os.path.basename(ctl.spec_path)))
    with pytest.raises(OSError):
        ctl._write_spec()
    install_fs(None)
    assert pathlib.Path(ctl.spec_path).read_bytes() == before
    leftovers = [x for x in os.listdir(tmp_path) if ".tmp" in x
                 or x.startswith(".spec.")]
    assert leftovers == []


def test_spec_torn_tmp_family_is_scheduled():
    """The chaos wiring: seed 13 lands on the new family, and the seed-0
    / seed-7 replays in ci/lint.sh keep their historical families."""
    from matcha_tpu.chaos.campaign import FAMILIES, schedule_for_seed
    from matcha_tpu.chaos.injectors import torn_spec_tempfile

    assert "spec_torn_tmp" in FAMILIES
    assert schedule_for_seed(13).family == "spec_torn_tmp"
    assert schedule_for_seed(0).family == "ckpt_bitflip"
    assert schedule_for_seed(7).family == "kill_mid_save"
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        spec = os.path.join(d, "serve_spec.json")
        evidence = torn_spec_tempfile(spec)
        assert os.path.isdir(spec + ".tmp")
        assert evidence["injector"] == "torn_spec_tempfile"
