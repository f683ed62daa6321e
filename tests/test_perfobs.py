"""Performance observability (ISSUE 8): cost ledger, roofline, the traced
epoch.

Two layers, mirroring the subsystem: pure cost extraction
(``obs.costs.analyze_program`` against hand-checkable programs, the
north-star roofline pin vs ROOFLINE.md's arithmetic, the §9 capacity
table) and the train-loop integration (every program the loop compiles
journals a v2 ``compile`` event; a cache-growth ``retrace`` arrives with
the added program's compile event; ``trace_dir`` captures one epoch under
the ``profile`` span).  The device-side reader of such a capture
(``obs.xprof``, ``obs_tpu.py profile``) is tested in
``tests/test_device_scopes.py``.
"""

import dataclasses
import math
import pathlib

import numpy as np
import pytest

from matcha_tpu.obs import make_event, read_journal, validate_event
from matcha_tpu.obs.costs import (
    CostLedger,
    UnknownChipError,
    analyze_program,
    capacity_report,
    chip_peaks,
    program_fingerprint,
    render_capacity_markdown,
    render_roofline_markdown,
    roofline_report,
)
from matcha_tpu.topology import decompose, make_graph
from matcha_tpu.train import TrainConfig, train

pytestmark = pytest.mark.obs

REPO = pathlib.Path(__file__).resolve().parents[1]

# the obs test recipe (tests/test_obs.py BASE), small
BASE = TrainConfig(
    name="perf", model="mlp", dataset="synthetic",
    dataset_kwargs={"num_train": 128, "num_test": 32},
    num_workers=8, graphid=5, batch_size=8, epochs=2, lr=0.0,
    warmup=False, momentum=0.0, weight_decay=0.0, matcha=True, budget=0.5,
    seed=3, save=False, sync_init=False, eval_every=1,
    measure_comm_split=True,
)


# ------------------------------------------------------------ cost extraction

def test_analyze_program_extracts_exact_matmul_costs():
    """On a single dot the extracted numbers are exactly checkable:
    2·m·n·k FLOPs, input+output boundary bytes, and a compile event that
    validates under the v2 schema."""
    import jax
    import jax.numpy as jnp

    m, k, n = 64, 128, 32
    f = jax.jit(lambda a, b: a @ b)
    a = jax.ShapeDtypeStruct((m, k), jnp.float32)
    b = jax.ShapeDtypeStruct((k, n), jnp.float32)
    costs = analyze_program(f, a, b, label="dot")
    assert costs["flops"] == 2.0 * m * n * k
    assert costs["arg_bytes"] == 4 * (m * k + k * n)
    assert costs["out_bytes"] == 4 * m * n
    assert costs["hbm_bytes"] == costs["arg_bytes"] + costs["out_bytes"]
    assert costs["peak_bytes"] >= costs["hbm_bytes"]
    assert costs["compile_seconds"] > 0
    assert costs["arg_shardings"] == ["auto"]
    event = make_event("compile", 1.0, **costs)
    assert validate_event(event) == []
    # fingerprints: stable across identical signatures, shape-sensitive
    assert costs["fingerprint"] == program_fingerprint("dot", (a, b))
    assert program_fingerprint("dot", (a, a)) != costs["fingerprint"]


def test_cost_ledger_dedups_programs_and_tracks_last_fingerprint():
    import jax
    import jax.numpy as jnp

    events = []

    def log(kind, **detail):
        events.append(make_event(kind, 0.0, **detail))
        return events[-1]

    ledger = CostLedger(log)
    f = jax.jit(lambda x: jnp.sum(x * x))
    assert ledger.observe("probe", f, jnp.ones(16)) is not None
    assert ledger.observe("probe", f, jnp.ones(16)) is None  # same program
    assert ledger.observe("probe", f, jnp.ones(8)) is not None  # new shape
    g = jax.jit(lambda x: jnp.sum(x * x))  # rebuild: a real new compile
    assert ledger.observe("probe", g, jnp.ones(16)) is not None
    assert len(events) == 3
    assert ledger.last_fingerprint("probe") == events[-1]["fingerprint"]
    assert ledger.last_fingerprint("unknown") is None


def test_roofline_reproduces_rooflinemd_ceilings_at_north_star():
    """Acceptance pin: the dense-path ceilings extracted from the compiled
    program reproduce ROOFLINE.md's hand arithmetic — 2·N²·D FLOPs and
    2·N·D·2B boundary HBM per step at (N=256, D=273258, bf16) — to within
    5%, and the v5e ceilings land on the documented ~5,500 (compute) and
    ~2,900 (HBM) steps/s."""
    n, dim = 256, 273258  # the north-star shape (ResNet-20 flat dim)
    dec = decompose(make_graph("ring", n, seed=1), n, seed=1)
    rep = roofline_report(n, dim, dec, wire_dtype="bf16", chip="v5e",
                          measured_steps_per_sec=5005.7)
    assert rep["flops_vs_model"] == pytest.approx(1.0, abs=0.05)
    assert rep["hbm_vs_model"] == pytest.approx(1.0, abs=0.05)
    assert rep["compute_bound_steps_per_sec"] == pytest.approx(5500, rel=0.05)
    assert rep["hbm_bound_steps_per_sec"] == pytest.approx(2900, rel=0.05)
    assert rep["bound"] == "hbm" and not rep["provisional"]
    # both ratios of a supplied rate: 5005.7 steps/s is ~91% of the compute
    # ceiling and above the per-step program's HBM ceiling
    assert 0.85 < rep["measured_vs_compute_bound"] < 1.0
    assert rep["measured_vs_ceiling"] > 1.0
    md = render_roofline_markdown(rep)
    assert "5,500" not in md  # numbers come from extraction, not prose
    assert f"{rep['ceiling_steps_per_sec']:.1f}" in md


def test_roofline_cpu_provisional_is_finite_and_flagged():
    dec = decompose(make_graph("ring", 4, seed=1), 4, seed=1)
    rep = roofline_report(4, 512, dec, wire_dtype="f32", chip=None)
    assert rep["provisional"] is True
    for key in ("flops_per_step", "hbm_bytes_per_step",
                "compute_bound_steps_per_sec", "hbm_bound_steps_per_sec",
                "ceiling_steps_per_sec"):
        assert math.isfinite(rep[key]) and rep[key] > 0
    assert "provisional" in render_roofline_markdown(rep)
    with pytest.raises(UnknownChipError, match="unknown chip"):
        roofline_report(4, 512, dec, chip="v99")


def test_chip_table_knows_the_chip_or_raises(monkeypatch):
    """The one chip table: known kinds resolve (the v5e reports itself as
    "TPU v5 lite"), and a device that is not in it is a named error —
    never ``(None, None)`` and never the CPU row, which only the CPU
    platform (or an explicit ``chip="cpu"``) gets."""
    import types

    import jax

    from matcha_tpu.obs.costs import CPU_PROVISIONAL, resolve_chip

    assert chip_peaks("TPU v5e") == (197.0, 819.0)
    assert chip_peaks("TPU v5 lite") == (197.0, 819.0)
    assert chip_peaks("TPU v4") == (275.0, 1228.0)
    with pytest.raises(UnknownChipError):
        chip_peaks("cpu")
    assert resolve_chip(None) == ("cpu-provisional", CPU_PROVISIONAL)
    assert resolve_chip("cpu") == ("cpu-provisional", CPU_PROVISIONAL)
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    with pytest.raises(UnknownChipError, match="TPU v99"):
        resolve_chip(None)
    fake.device_kind = "TPU v5 lite"
    assert resolve_chip(None)[0] == "v5lite"


def test_capacity_report_rederives_design9_table():
    """§9's numbers from memory_analysis(): 2 (decen) / 4 (choco) f32
    [N, D] buffers, chips = ceil(bytes / HBM) — at the ResNet-50 dim the
    committed table's 4-chip MATCHA-256 line must reproduce."""
    rep = capacity_report(1000, workers=(8, 4), chip="v5e")
    by = {(r["communicator"], r["n"]): r for r in rep["rows"]}
    assert by[("decen", 8)]["state_bytes"] == 2 * 8 * 1000 * 4
    assert by[("choco", 4)]["state_bytes"] == 4 * 4 * 1000 * 4
    assert all(r["fits_one_chip"] for r in rep["rows"])
    big = capacity_report(25_560_000, workers=(256, 64), chip="v5e")
    rows = {(r["communicator"], r["n"]): r for r in big["rows"]}
    assert rows[("decen", 256)]["chips_needed"] == 4   # 52.3 GB / 16 GB
    assert rows[("decen", 64)]["fits_one_chip"]        # 13.1 GB: the §9 line
    assert not rows[("choco", 64)]["fits_one_chip"]    # 26.2 GB: carry x2
    md = render_capacity_markdown(big)
    assert "52.35 GB" in md and "memory_analysis" in md


# ----------------------------------------------------- train-loop integration

@pytest.fixture(scope="module")
def instrumented_run(tmp_path_factory):
    """One small pipelined run exercising every ledger call site: scanned
    epoch, gossip-chain comm timer, evaluation, drain — plus a trace
    capture (host-only on CPU; the loud-failure path's fixture)."""
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    cfg = dataclasses.replace(BASE, overlap="1step", trace_dir=trace_dir)
    result = train(cfg)
    return result, trace_dir


def test_compile_events_cover_every_program(instrumented_run):
    result, _ = instrumented_run
    events = [e for e in result.recorder.events if e["kind"] == "compile"]
    labels = {e["label"] for e in events}
    assert {"epoch_scan", "gossip_chain", "evaluate", "drain"} <= labels
    for e in events:
        assert validate_event(e) == [], e
        assert e["flops"] > 0 and e["hbm_bytes"] > 0 and e["peak_bytes"] > 0
        assert e["compile_seconds"] > 0
        assert len(e["fingerprint"]) == 12
    # dedup: re-run epochs journal no duplicate (label, fingerprint) pairs
    keys = [(e["label"], e["fingerprint"]) for e in events]
    assert len(keys) == len(set(keys))
    # the comm timer's gossip-only chain is costed too (short epochs time
    # a single window length; long ones add the 2k program — both dedup)
    assert sum(1 for e in events if e["label"] == "gossip_chain") >= 1


def test_no_telemetry_compiles_no_ledger(tmp_path):
    cfg = dataclasses.replace(BASE, telemetry=False, epochs=1)
    result = train(cfg)
    assert not [e for e in result.recorder.events if e["kind"] == "compile"]


def test_retrace_event_is_accompanied_by_its_compile_event(monkeypatch):
    """Acceptance: cache growth journals WITH the program that was added.
    A data loader that drifts shape at epoch 1 (one batch fewer) is the
    silent-recompile failure mode the watch exists for — the journaled
    retrace must carry the fingerprint of a compile event that names the
    drifted program and its cost."""
    from matcha_tpu.data import WorkerBatches

    orig = WorkerBatches.epoch_into

    def drifting(self, epoch, xs_out, ys_out, first=0):
        orig(self, epoch, xs_out, ys_out, first)
        if epoch == 0:
            self.batches_per_epoch -= 1  # what the loop stages from now on

    monkeypatch.setattr(WorkerBatches, "epoch_into", drifting)
    result = train(dataclasses.replace(BASE, measure_comm_split=False,
                                       eval_every=0))
    retrace = [e for e in result.recorder.events if e["kind"] == "retrace"]
    assert retrace, "shape-drifting loader journaled no retrace event"
    compiles = {e["fingerprint"]: e for e in result.recorder.events
                if e["kind"] == "compile" and e["label"] == "epoch_scan"}
    fp = retrace[0]["fingerprint"]
    assert fp in compiles, "retrace fingerprint has no compile event"
    assert compiles[fp]["flops"] > 0
    assert len(compiles) == 2  # the original program AND the drifted one


def test_trace_dir_captures_exactly_one_window(instrumented_run):
    """One capture, of epoch 1 alone; the profiler's start and its stop are
    that epoch's two ``profile`` spans, leaves of the period around the
    epoch's own.  (The CPU's capture has no device plane: the loop warns
    and journals no ``device_scopes`` event; tests/test_device_scopes.py
    drives that event through a capture that has one.)"""
    result, trace_dir = instrumented_run
    captures = list(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    assert len(captures) == 1
    periods = {e["epoch"]: e for e in result.recorder.events
               if e["kind"] == "spans"}
    names = {k: [s["name"] for s in r["spans"]] for k, r in periods.items()}
    assert "profile" not in names[0]
    assert names[1].count("profile") == 2
    first, last = (i for i, n in enumerate(names[1]) if n == "profile")
    assert names[1][first - 1] == "snapshot"
    assert names[1][last - 1] == "wait_device"
    assert all(s["parent"] == periods[1]["period"]
               for s in periods[1]["spans"] if s["name"] == "profile")
    assert not [e for e in result.recorder.events
                if e["kind"] == "device_scopes"]


# ----------------------------------------------------------------------- CLI

def test_cli_roofline_tiny_cpu_writes_markdown(tmp_path, capsys):
    """The CI smoke contract: a tiny MLP ring-4 CPU roofline must exit 0
    with finite ceilings and write a valid markdown artifact."""
    import obs_tpu

    md = tmp_path / "roofline.md"
    rc = obs_tpu.main(["roofline", "--workers", "4", "--topology", "ring",
                       "--model", "mlp", "--dataset", "synthetic",
                       "--md", str(md)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "Automatic roofline" in out and "provisional" in out
    text = md.read_text()
    assert text.startswith("# Automatic roofline") and "| ceiling |" in text


def test_cli_roofline_reads_measured_rate_from_bench_record(tmp_path, capsys):
    import json

    import obs_tpu

    source = tmp_path / "bench_record.json"
    source.write_text(json.dumps(
        {"metric": "gossip-steps/sec", "value": 1150.0,
         "unit": "gossip_steps_per_sec", "vs_baseline": 0.23,
         "backend": "dense"}))
    rc = obs_tpu.main(["roofline", "--workers", "4", "--topology", "ring",
                       "--dim", "512", "--chip", "v5e",
                       "--source", str(source)])
    assert rc == 0
    assert "Measured" in capsys.readouterr().out


def test_cli_capacity_writes_markdown(tmp_path, capsys):
    import obs_tpu

    md = tmp_path / "capacity.md"
    rc = obs_tpu.main(["capacity", "--dim", "1000",
                       "--workers", "8,4", "--chip", "v5e",
                       "--md", str(md)])
    assert rc == 0
    assert "| decen | 8 |" in md.read_text()


def test_cli_summary_shows_cost_ledger(capsys):
    """The reference journal's compile event lands in the summary render —
    the ledger is part of the run's one-screen story, not a side channel."""
    import obs_tpu

    rc = obs_tpu.main(
        ["summary", str(REPO / "benchmarks" / "events_ring8.jsonl")])
    assert rc == 0
    out = capsys.readouterr().out
    assert "compiled programs (cost ledger): 1" in out
    assert "epoch_scan" in out
