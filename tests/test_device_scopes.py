"""The device-side reader (ISSUE 37): ``obs/xprof.py`` from a capture's own
HLO to device time by ``device_span``, its three callers (``train()`` under
``trace_dir``, ``obs_tpu.py profile``, the benchmark's ``chipbench/scopes.py``)
and the sixteen per-layer metrics that read its record.

Two fixtures: ``tests/fixtures/v5e_toy.xplane.pb`` is a capture from the chip
(``tests/fixtures/make_v5e_toy.py``: a toy scanned gradient step with
``device_span``s and a ``jax.checkpoint``, then a three-pass chain, twice
each; PR 37), and
``chipbench/tests/small_scopes.textproto`` is cut by hand with its answers
worked out in its header."""

import ast
import json
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from matcha_tpu.obs import make_event, read_journal, validate_event, xprof
from matcha_tpu.utils.profiling import device_span

pytestmark = pytest.mark.obs

REPO = pathlib.Path(__file__).resolve().parents[1]
V5E = REPO / "tests" / "fixtures" / "v5e_toy.xplane.pb"
SMALL = REPO / "chipbench" / "tests" / "small_scopes.textproto"
MS = 1e-3

#: the scope maps of the textproto's two programs, as its header tells them
LAYER = ("matcha/fwd_bwd", "matcha/layer")
MAPS = {
    "jit_epoch_scan(1)": {
        "while.1": ((), "forward", "jit(epoch_scan)/while"),
        "fusion.1": (LAYER, "forward", ""),
        "fusion.2": (LAYER, "recomputed", ""),
        "fusion.3": (LAYER, "backward", ""),
        "fusion.4": (("matcha/sgd",), "forward", ""),
        "fusion.5": (("comm/step",), "forward", ""),
        "all-reduce-start.1": (("comm/step",), "forward", ""),
        "copy.9": ((), "forward", "jit(epoch_scan)/while/body/copy"),
    },
    # (an instruction of matcha/heal that no row runs: fused away)
    "jit_gossip_chain(2)": {"fusion.1": (("comm/step",), "forward", ""),
                            "select.7": (("matcha/heal",), "forward", "")},
}
EPOCH, CHAIN = MAPS


# ------------------------------------------------------------ the scope map

def toy_module():
    """The compiled module of a toy gradient: two ``device_span``s, the
    first under ``jax.checkpoint``.  (The test compiles; the reader never
    does.)"""
    def loss(w, x):
        def layer(x):
            with device_span("matcha/a"):
                return jnp.tanh(x @ w)

        h = jax.checkpoint(layer)(x)
        with device_span("matcha/b"):
            return jnp.sum(jnp.sin(h) * h)

    spec = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    compiled = jax.jit(jax.grad(loss)).lower(spec, spec).compile()
    return compiled.runtime_executable().hlo_modules()[0]


def test_scope_map_names_every_pass_of_a_toy_gradient():
    module = toy_module()
    scopes = xprof.scope_map(module.as_serialized_hlo_module_proto())
    text = module.to_string()
    assert all(f"{name} = " in text for name in scopes)
    seen = {(s[-1], which) for s, which, _ in scopes.values() if s}
    assert seen == {("matcha/a", "forward"), ("matcha/a", "recomputed"),
                    ("matcha/a", "backward"), ("matcha/b", "forward"),
                    ("matcha/b", "backward")}
    # the instruction's own op_name rides along, for the rows under no scope
    assert all(xprof.scopes_of(op) == s for s, _, op in scopes.values())


@pytest.mark.parametrize("op_name, scopes, which", [
    ("jit(f)/jvp(matcha/fwd_bwd)/while/body/matcha/gdn_conv/checkpoint/mul",
     ("matcha/fwd_bwd", "matcha/gdn_conv"), "forward"),
    ("jit(f)/transpose(jvp(matcha/fwd_bwd))/checkpoint/rematted_computation/"
     "matcha/gdn_conv/mul", ("matcha/fwd_bwd", "matcha/gdn_conv"),
     "recomputed"),
    ("jit(f)/transpose(jvp(matcha/fwd_bwd))/checkpoint/matcha/gdn_conv/"
     "checkpoint/rematted_computation/mul",
     ("matcha/fwd_bwd", "matcha/gdn_conv"), "recomputed_inner"),
    ("jit(f)/transpose(jvp(matcha/fwd_bwd))/checkpoint/matcha/gdn_conv/"
     "checkpoint/mul", ("matcha/fwd_bwd", "matcha/gdn_conv"), "backward"),
    ("jit(f)/while/body/closed_call/comm/step/add", ("comm/step",),
     "forward"),
    ("jit(f)/while/body/dynamic_slice", (), "forward"),
], ids=["forward", "recomputed", "recomputed_inner", "backward", "comm",
        "no_scope"])
def test_op_names_as_jax_writes_them(op_name, scopes, which):
    """The forms are those of the linear-attention model's lowering, where a
    layer under ``remat`` holds checkpoints of its own."""
    assert xprof.scopes_of(op_name) == scopes
    assert xprof.pass_of(op_name) == which


def _varint(n):
    out = b""
    while n >= 0x80:
        out, n = out + bytes([n & 0x7F | 0x80]), n >> 7
    return out + bytes([n])


def _message(*fields):
    """A serialized protobuf message of (number, int | bytes | str)."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def _instr(ident, name, opcode, op_name, calls=None):
    """An ``HloInstructionProto``: name, opcode, metadata.op_name, id and,
    for one that runs a computation, its id."""
    fields = [(1, name), (2, opcode), (7, _message((2, op_name))),
              (35, ident)]
    return _message(*fields, *([(38, calls)] if calls is not None else []))


def test_a_fusion_is_named_by_the_op_name_the_compiler_gave_it():
    """Not by a count of what it fused: on the chip that count gave cell 1's
    weight-gradient convolutions to the update in their epilogue and the
    linear-attention cell's projections to the norm before them (PERF.md
    section 6, PR 37).  Here: a product under ``matcha/b`` with two
    elementwise instructions of ``matcha/a`` fused in."""
    under_a = "jit(f)/matcha/a/mul"
    under_b = "jit(f)/transpose(jvp(matcha/b))/dot_general"
    fused = _message(
        (1, "fused"), (5, 1), (6, 13),
        (2, _instr(10, "p", "parameter", "")),
        (2, _instr(11, "m1", "multiply", under_a)),
        (2, _instr(12, "m2", "multiply", under_a)),
        (2, _instr(13, "d1", "dot", under_b)))
    entry = _message(
        (1, "main"), (5, 3), (6, 31),
        (2, _instr(30, "fusion.1", "fusion", under_b, calls=1)),
        (2, _instr(31, "copy.2", "copy", "")))
    scopes = xprof.scope_map(_message((1, "jit_f"), (3, fused), (3, entry)))
    assert scopes["fusion.1"] == (("matcha/b",), "backward", under_b)
    assert scopes["copy.2"] == ((), "forward", "")
    # the fused computation's instructions are in the map too, by their own
    assert scopes["m1"][:2] == (("matcha/a",), "forward")
    assert set(scopes) == {"p", "m1", "m2", "d1", "fusion.1", "copy.2"}


# ------------------------------------------------------------ the reduction

@pytest.fixture(scope="module")
def small():
    return ProfileData.from_text_proto(SMALL.read_text())


@pytest.fixture(scope="module")
def record(small):
    return xprof.reduce_scopes(small, MAPS, marks=("w/start", "w/stop"))


def test_reduction_of_the_hand_cut_capture(small, record):
    assert record["window_s"] == pytest.approx(16 * MS)
    assert record["device_planes"] == 2
    assert xprof.main_program(record) == EPOCH
    epoch = record["programs"][EPOCH]
    assert (epoch["device_s"], epoch["runs"], epoch["ops_s"]) == \
        pytest.approx((10 * MS, 1.0, 9.5 * MS))
    layer = epoch["scopes"]["matcha/layer"]
    assert layer["by_pass"] == pytest.approx({
        "forward": 2.5 * MS, "recomputed": 2 * MS, "recomputed_inner": 0.0,
        "backward": 2 * MS})
    assert (layer["device_s"], layer["own_s"], layer["ops"]) == \
        pytest.approx((6.5 * MS, 6.5 * MS, 3))
    outer = epoch["scopes"]["matcha/fwd_bwd"]
    assert (outer["device_s"], outer["own_s"], outer["ops"]) == \
        pytest.approx((6.5 * MS, 0.0, 0))
    assert outer["by_pass"] == pytest.approx(layer["by_pass"])
    assert epoch["scopes"]["matcha/sgd"]["device_s"] == pytest.approx(1 * MS)
    assert sum(s["own_s"] for s in epoch["scopes"].values()) == \
        pytest.approx(epoch["matched_s"])
    # the same instruction name in two programs is two things
    assert epoch["scopes"]["comm/step"]["device_s"] == pytest.approx(1 * MS)
    chain = record["programs"][CHAIN]
    assert chain["device_s"] == pytest.approx(1.5 * MS)
    assert chain["scopes"]["comm/step"]["device_s"] == pytest.approx(1.5 * MS)
    # a scope the program has and no row joined to reads 0; one it lacks
    # is absent
    assert set(chain["scopes"]) == {"comm/step", "matcha/heal"}
    assert chain["scopes"]["matcha/heal"] == {
        "device_s": 0.0, "own_s": 0.0, "ops": 0,
        "by_pass": dict.fromkeys(xprof.PASSES, 0.0)}
    assert "matcha/heal" not in epoch["scopes"]
    # without marks: from the first device operation to the last
    assert xprof.reduce_scopes(small, MAPS)["window_s"] == \
        pytest.approx(13 * MS)


def test_an_instruction_the_map_lacks_is_unmatched_never_a_scope(record):
    epoch = record["programs"][EPOCH]
    assert epoch["matched_s"] == pytest.approx(8.5 * MS)
    assert epoch["unmatched_top"] == [
        ["copy.9 f32[16,8]", pytest.approx(0.75 * MS),
         "jit(epoch_scan)/while/body/copy"],
        ["fusion.77 f32[16,8]", pytest.approx(0.25 * MS), None]]
    assert not any("fusion.77" in json.dumps(row)
                   for row in epoch["scopes"].values())
    # a program whose HLO the capture lacks: all of it unmatched
    bare = xprof.reduce_scopes(
        ProfileData.from_text_proto(SMALL.read_text()),
        {EPOCH: MAPS[EPOCH]})["programs"][CHAIN]
    assert bare["matched_s"] == 0.0 and bare["scopes"] == {}
    assert bare["unmatched_top"] == [
        ["fusion.1 f32[16,8]", pytest.approx(1.5 * MS), None]]


def test_share_of_comm_time_under_other_work(small, record):
    assert (record["comm_s"], record["overlap_s"]) == \
        pytest.approx((4 * MS, 1.5 * MS))
    assert record["overlap_fraction"] == pytest.approx(0.375)
    no_comm = {EPOCH: {k: v for k, v in MAPS[EPOCH].items()
                       if v[0] != ("comm/step",)}}
    assert xprof.reduce_scopes(small, no_comm)["overlap_fraction"] is None


def test_a_capture_with_no_device_plane_raises(tmp_path):
    host_only = ProfileData.from_text_proto(
        'planes { id: 1 name: "/host:CPU" lines { id: 1 name: "python" } }')
    with pytest.raises(xprof.TraceParseError, match="no device plane"):
        xprof.reduce_scopes(host_only, {})
    with pytest.raises(xprof.TraceParseError, match="no \\*\\.xplane\\.pb"):
        xprof.device_scopes(str(tmp_path))
    (tmp_path / "bad.xplane.pb").write_bytes(b"\xff\xff not a capture")
    with pytest.raises(xprof.TraceParseError, match="not a readable"):
        xprof.device_scopes(str(tmp_path))


# ------------------------------------------------- a capture from the chip

@pytest.fixture(scope="module")
def v5e():
    maps = {}
    return xprof.device_scopes(str(V5E), keep_maps=maps), maps


def test_a_v5e_capture_is_joined_through_its_own_hlo(v5e):
    record, maps = v5e
    modules = xprof.hlo_modules(V5E.read_bytes())
    assert set(modules) == set(maps) == set(record["programs"])
    epoch = xprof.main_program(record)
    assert epoch.startswith("jit_epoch_scan(")
    program = record["programs"][epoch]
    assert program["runs"] == 2
    # every row joined: none "not in the capture's HLO"
    assert all(op is not None for _, _, op in program["unmatched_top"])
    assert program["matched_s"] / program["device_s"] > 0.9
    scopes = program["scopes"]
    assert set(scopes) == {"matcha/fwd_bwd", "matcha/attn_toy",
                           "matcha/mlp_toy", "matcha/sgd", "comm/step"}
    inner = [scopes["matcha/attn_toy"], scopes["matcha/mlp_toy"]]
    assert scopes["matcha/fwd_bwd"]["device_s"] == pytest.approx(
        scopes["matcha/fwd_bwd"]["own_s"] + sum(s["own_s"] for s in inner))
    for s in inner:  # each layer ran forward, again under the checkpoint,
        assert all(s["by_pass"][k] > 0 for k in  # and backward
                   ("forward", "recomputed", "backward"))
        assert s["by_pass"]["recomputed_inner"] == 0.0
    assert sum(scopes["matcha/sgd"]["by_pass"].values()) == \
        pytest.approx(scopes["matcha/sgd"]["by_pass"]["forward"])
    chain = next(m for m in record["programs"] if m != epoch)
    assert set(record["programs"][chain]["scopes"]) == {"comm/step"}


def test_the_reader_compiles_and_lowers_nothing():
    for path in ("matcha_tpu/obs/xprof.py", "chipbench/scopes.py"):
        source = (REPO / path).read_text()
        calls = {node.func.attr for node in ast.walk(ast.parse(source))
                 if isinstance(node, ast.Call)
                 and isinstance(node.func, ast.Attribute)}
        assert not calls & {"lower", "compile", "jit"}, path
        assert ".lower(" not in source and ".compile(" not in source


def test_the_cost_ledger_keeps_and_reads_nothing_for_the_reader(monkeypatch):
    """The map is made from the capture, so a start pays nothing for it:
    ``CostLedger.observe`` reads no executable's text and builds no map."""
    from jax._src import stages

    from matcha_tpu.obs.costs import CostLedger

    read = []
    monkeypatch.setattr(stages.Compiled, "as_text",
                        lambda self, *a, **k: read.append("text") or "")
    monkeypatch.setattr(xprof, "scope_map",
                        lambda *a, **k: read.append("map") or {})
    ledger = CostLedger(lambda kind, **fields: dict(fields, kind=kind))
    event = ledger.observe("toy", jax.jit(lambda x: x * 2), jnp.ones(4))
    assert event["kind"] == "compile" and read == []


# ------------------------------------------------------------ callers

def test_cli_profile_renders_the_table_and_the_overlap_line(tmp_path, capsys):
    import obs_tpu

    journal, md = tmp_path / "session.jsonl", tmp_path / "profile.md"
    assert obs_tpu.main(["profile", str(V5E), "--md", str(md),
                         "--journal", str(journal)]) == 0
    out = capsys.readouterr().out
    assert "jit_epoch_scan(" in out and "jit_gossip_chain(" in out
    assert "recomputed_inner" in out and "(no scope)" in out
    row = next(l for l in out.splitlines() if "matcha/attn_toy" in l)
    assert len(row.split()) == 8  # scope, ms, own ms, share, four passes
    assert "comm/* rows" in out and "under other work 13.0%" in out
    assert md.read_text().count("matcha/fwd_bwd") == 1
    events = read_journal(str(journal))
    assert [e["kind"] for e in events] == ["device_scopes"]
    assert validate_event(events[0]) == []
    # the CPU's capture: exit 2 and a reason, not a table of zeros
    f = jax.jit(lambda x: jnp.sum(x * x))
    jax.profiler.start_trace(str(tmp_path / "cpu"))
    jax.block_until_ready(f(jnp.ones(16)))
    jax.profiler.stop_trace()
    assert obs_tpu.main(["profile", str(tmp_path / "cpu")]) == 2
    assert "no device plane" in capsys.readouterr().err


def test_device_scopes_event_passes_the_schema(v5e):
    from matcha_tpu.obs.journal import KIND_MIN_VERSION, REQUIRED_FIELDS

    record, _ = v5e
    event = make_event("device_scopes", 1.0, epoch=1, **record)
    assert validate_event(event) == []
    json.dumps(event)
    assert KIND_MIN_VERSION["device_scopes"] == 2
    for key in REQUIRED_FIELDS["device_scopes"]:
        short = {k: v for k, v in event.items() if k != key}
        assert validate_event(short), key
    # the retired kind's journals still validate
    old = make_event("profile", 1.0, source="an old capture",
                     comm_seconds=1.0, compute_seconds=2.0,
                     overlap_seconds=0.5, overlap_fraction=0.5)
    assert validate_event(old) == []


def test_train_journals_the_record_and_writes_scopes_json(tmp_path,
                                                          monkeypatch):
    """``train()``'s caller of the reader, on a capture that has a device
    plane: one ``device_scopes`` event, ``scopes.json`` beside the capture
    with the record and the map, and ``obs_tpu.py profile <dir>`` the same
    table from the directory alone."""
    import shutil

    from matcha_tpu.train import loop

    (tmp_path / "plugins" / "profile" / "t").mkdir(parents=True)
    shutil.copy(V5E, tmp_path / "plugins" / "profile" / "t" / "vm.xplane.pb")
    logged = []

    class Recorder:
        def log_event(self, kind, **fields):
            logged.append(make_event(kind, 0.0, **fields))

    loop._journal_device_scopes(Recorder(), str(tmp_path), 3)
    assert [e["kind"] for e in logged] == ["device_scopes"]
    assert validate_event(logged[0]) == [] and logged[0]["epoch"] == 3
    kept = json.loads((tmp_path / "scopes.json").read_text())
    assert set(kept["maps"]) == set(kept["record"]["programs"])
    epoch = xprof.main_program(kept["record"])
    assert ["matcha/sgd", "forward"] in kept["maps"][epoch].values()
    assert kept["record"]["programs"][epoch]["scopes"] == json.loads(
        json.dumps(logged[0]["programs"][epoch]["scopes"]))
    assert xprof.render_device_scopes(xprof.device_scopes(str(tmp_path))) \
        == xprof.render_device_scopes(kept["record"])


# ------------------------------------------------- the benchmark's reader

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NEW = ["fwd_bwd_ms", "sgd_ms", "gossip_ms", "scope_matched_pct",
       "moe_experts_ms", "moe_route_ms", "lm_head_loss_ms", "attn_window_ms",
       "attn_full_ms", "dsa_index_ms", "dsa_select_ms", "attn_sparse_ms",
       "gdn_chunk_prep_ms", "gdn_scan_ms", "gdn_conv_ms",
       "attn_full_gated_ms", "bd_attn_ms"]
#: the model file a cell's configuration runs (the image cells: none)
MODEL_OF = {"mellum2": "models/mellum2.py", "keye-vl2": "models/keye_vl2.py",
            "qwen3-next": "models/qwen3_next.py", "sdar": "models/sdar.py"}
#: the scopes every model's step has, and the token models' shared layers
SHARED = ["train/state.py", "models/mellum2.py"]


def spans_of(path):
    """The scopes a file's ``device_span`` calls can open (a name chosen
    between two constants inside an f-string gives both)."""
    found = set()
    for node in ast.walk(ast.parse((REPO / "matcha_tpu" / path).read_text())):
        if not (isinstance(node, ast.Call) and node.args
                and getattr(node.func, "id", "") == "device_span"):
            continue
        names = [""]
        arg = node.args[0]
        for part in arg.values if isinstance(arg, ast.JoinedStr) else [arg]:
            if isinstance(part, ast.FormattedValue):
                choices = [part.value.body.value, part.value.orelse.value]
            else:
                choices = [part.value]
            names = [n + c for n in names for c in choices]
        found.update(names)
    return found


def traced_run(tmp_path, steps=16):
    """What ``harness.run_cell`` hands a metric's reader, as far as the
    scope readers look: the journal's ``run_start``, whether a trace was
    reduced, the traced steps."""
    (tmp_path / "trace").mkdir(parents=True)
    return {"events": [{"kind": "run_start",
                        "config": {"savePath": str(tmp_path / "job")}}],
            "trace": {"busy_s": 1.0}, "traced_steps": steps}


def test_scopes_finds_the_capture_where_the_harness_writes_it(tmp_path):
    """``harness.run_cell`` stages the job under ``<workdir>/job`` (the
    program's ``savePath``) and traces into ``<workdir>/trace``."""
    import inspect

    from chipbench import harness, scopes

    source = inspect.getsource(harness.run_cell)
    assert 'workdir / "job"' in source and 'workdir / "trace"' in source
    config = harness.build_train_config(
        {"train_config": {}, "chips": 1, "name": "c"}, tmp_path / "job",
        tmp_path / "job" / "data.npz")
    run = {"events": [{"kind": "run_start",
                       "config": {"savePath": config.savePath}}]}
    assert scopes.capture_dir(run) == tmp_path / "trace"
    assert scopes.capture_dir({"events": []}) is None


def test_scope_ms_reads_the_main_program_a_step(tmp_path, capsys):
    import shutil

    from chipbench import scopes

    run = traced_run(tmp_path)
    shutil.copy(V5E, tmp_path / "trace" / "vm.xplane.pb")
    record = xprof.device_scopes(str(V5E))
    program = record["programs"][xprof.main_program(record)]
    assert scopes.scope_ms(run, "matcha/fwd_bwd") == pytest.approx(
        1e3 * program["scopes"]["matcha/fwd_bwd"]["device_s"] / 16)
    assert scopes.scope_ms(run, "comm/step") == pytest.approx(
        1e3 * program["scopes"]["comm/step"]["device_s"] / 16)
    assert scopes.matched_pct(run) == pytest.approx(
        100 * program["matched_s"] / program["device_s"])
    assert scopes.scope_ms(run, "matcha/gdn_scan") is None  # not in it
    # read once a run, the epoch program's table printed once
    out = capsys.readouterr().out
    assert out.count("# scope jit_epoch_scan(") == 1
    assert "jit_gossip_chain" not in out
    assert all(l.startswith("# scope ") for l in out.splitlines())


def test_scope_ms_is_none_where_there_is_nothing_to_read(tmp_path,
                                                         monkeypatch):
    import sys

    from chipbench import scopes

    untraced = dict(traced_run(tmp_path), trace=None)
    assert scopes.scope_ms(untraced, "matcha/fwd_bwd") is None
    assert scopes.matched_pct(untraced) is None
    # a traced run whose capture has no device plane (the CPU's)
    assert scopes.scope_ms(traced_run(tmp_path / "a"), "matcha/sgd") is None
    # a program from before the reader: the import fails, no metric raises
    monkeypatch.setitem(sys.modules, "matcha_tpu.obs.xprof", None)
    assert scopes.scope_ms(traced_run(tmp_path / "b"), "matcha/sgd") is None
    assert scopes.matched_pct(traced_run(tmp_path / "c")) is None


@pytest.mark.parametrize("metric", NEW)
def test_new_metric_is_one_reader_call_on_cells_that_have_its_scope(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert {k: entry[k] for k in ("source", "layer", "moves")} == {
        "source": "device_trace", "layer": "epoch program",
        "moves": "step_ms"}
    # through the harness's own loader: nothing to read, nothing raised
    from chipbench import catalog
    assert catalog.load_reader(metric)({"events": [], "trace": None}) is None
    tree = ast.parse((REPO / "chipbench" / "metrics"
                      / f"{metric}.py").read_text())
    assert ast.get_docstring(tree)
    body = [n for n in tree.body if not isinstance(n, ast.Expr)]
    assert [type(n) for n in body] == [ast.ImportFrom, ast.FunctionDef]
    (ret,) = body[1].body
    call = ret.value
    assert isinstance(ret, ast.Return) and isinstance(call, ast.Call)
    if metric == "scope_matched_pct":
        assert (entry["unit"], entry["better"]) == ("%", "higher")
        assert call.func.id == "matched_pct" and "workloads" not in entry
        return
    assert (entry["unit"], entry["better"]) == ("ms", "lower")
    assert call.func.id == "scope_ms"
    scope = call.args[1].value
    assert scope == {"gossip_ms": "comm/step"}.get(
        metric, "matcha/" + metric[:-len("_ms")])
    cells = entry.get("workloads") or [w["name"] for w in BENCH["workloads"]]
    for cell in cells:
        files = SHARED + [f for prefix, f in MODEL_OF.items()
                          if cell.startswith(prefix)]
        assert any(scope in spans_of(f) for f in files), (cell, scope)
