#!/usr/bin/env python
"""Regenerate the committed reference journal ``benchmarks/events_ring8.jsonl``.

The journal is the schema pin: tier-1 validates it line by line
(``tests/test_obs.py``), so the format cannot drift silently.  It is the
exact ``events.jsonl`` of one CPU run — ring-8 MATCHA at budget 0.5, pure
gossip (lr 0) from an unsynced init, telemetry on — the same recipe the
obs test fixtures use.  Event *timings* (``t``, ``compile_seconds``) are
wall-clock and differ across regenerations by design; the schema, kind
sequence, and physics-derived payloads are deterministic (fixed seed).

The run carries a two-event membership churn (w3 leaves at epoch 2 and
rejoins at epoch 5) so the journal pins the elastic ``membership`` kind —
two events, both eagerly re-planned, bracketing the 8→7→8 live sets —
alongside the cost ledger's ``compile`` event from the v1→v2 bump.  It
also carries a fault-plan straggler (w5, period 4 over the 4-step epochs
⇒ participation pinned at exactly 0.25) so the v3 health plane has real
evidence to commit: one ``heartbeat`` per epoch and the streaming
detector's ``straggler`` ``anomaly`` verdicts naming w5.

The v4 ``attribution`` kind is pinned by a **planted heterogeneous-link
scenario**: the CPU run records no real comm split (``comm_time`` is 0),
so the estimator is fed a synthetic per-epoch comm series
``y = base + A·θ`` built from the run's own reconstructed activation
design matrix with θ = ``PLANTED_MATCHING_SECONDS`` (matching 1 priced
3× matching 0 — the link heterogeneity MATCHA exists to exploit).
Everything is seed-deterministic, so the journaled estimate recovers θ
up to the ridge bias, and the companion artifact
``benchmarks/measured_link_costs_ring8.json`` pins the PL009–011 surface.

The v6 serve plane (ISSUE 17) rides the same run through the REAL
``TrainerHarness`` boundary hook: promotion every 4 epochs (one
``promotion`` event — the consensus-mean snapshot promoted at epoch 4,
mid-churn), and one hot-swap ``control`` document (budget 0.5 → 0.35)
published at the epoch-6 boundary — after the rejoin re-fold, so the
membership pins stay untouched — applied as a value update with zero
retraces, carrying the re-based drift prediction for replay parity.

The v7 recovery plane (ISSUE 18) rides along too: the run checkpoints
every epoch (``checkpoint`` events, digest sidecars), and post-run the
newest generation is bit-flipped, convicted by its digest sidecar, and
quarantined — all through the REAL ladder helpers — with the resulting
``recovery`` event appended the way a resuming run journals it.

Regenerate after a journal schema bump (the v1→v2 bump of ISSUE 8 added
``compile`` events from the cost ledger; ISSUE 9 added ``membership``;
the v2→v3 bump of ISSUE 10 added ``heartbeat`` and ``anomaly``; the
v3→v4 bump of ISSUE 11 added ``attribution``; the v5→v6 bump of
ISSUE 17 added ``control`` and ``promotion``; the v6→v7 bump of
ISSUE 18 added ``recovery``; the v7→v8 bump of ISSUE 24 added ``spans``,
one record an epoch period, the rejoin's bootstrap among them; the v8→v9
bump of ISSUE 30 added ``fwd_bwd``, one record a run):

    JAX_PLATFORMS=cpu python benchmarks/make_reference_journal.py
"""

import json
import os
import shutil
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: the planted per-matching seconds-per-activation (θ) and per-epoch base —
#: the "heterogeneous links" the committed attribution event must recover
PLANTED_MATCHING_SECONDS = [0.02, 0.06]
PLANTED_BASE_SECONDS = 0.01

#: the v6 serve-plane pins: the hot-swap document's target budget and the
#: epoch boundary it is published at (after the epoch-5 rejoin re-fold),
#: and the promotion cadence (one promotion, at epoch 4)
SWAP_BUDGET = 0.35
SWAP_EPOCH = 6
PROMOTE_EVERY = 4


def main() -> int:
    from matcha_tpu.train import TrainConfig, train

    root = tempfile.mkdtemp(prefix="ref_journal_")
    cfg = TrainConfig(
        name="ring8", model="mlp", dataset="synthetic",
        description="reference journal: ring-8 MATCHA budget 0.5, "
                    "pure-gossip contraction (lr 0, unsynced init)",
        dataset_kwargs={"num_train": 256, "num_test": 32},
        num_workers=8, graphid=5, batch_size=8, epochs=8, lr=0.0,
        warmup=False, momentum=0.0, weight_decay=0.0, matcha=True,
        budget=0.5, seed=3, save=True, sync_init=False, eval_every=0,
        checkpoint_every=1, measure_comm_split=False,
        membership_trace={"name": "ref_churn", "events": [
            {"kind": "leave", "epoch": 2, "worker": "w3"},
            {"kind": "rejoin", "epoch": 5, "worker": "w3"},
        ]},
        # the health plane's committed evidence: a period-4 straggler on
        # w5 participates exactly 1 step in 4, so every heartbeat carries
        # participation 0.25 and every epoch convicts one `anomaly`
        fault_plan={"events": [
            {"kind": "straggler", "worker": 5, "start": 0, "period": 4},
        ]},
    )
    # v6 pin: the REAL serve plane as the boundary hook — the committed
    # `control` and `promotion` events come from TrainerHarness itself,
    # not hand-written dicts.  The control document is published at the
    # epoch-6 boundary through the atomic writer, so the journal commits
    # one applied value-scope swap (budget 0.5 → 0.35) and one promotion
    # (epoch 4, the consensus mean promoted mid-churn).
    from matcha_tpu.serve import TrainerHarness, write_control

    control_path = os.path.join(root, "control.json")
    harness = TrainerHarness({
        "control_path": control_path,
        "serving_dir": os.path.join(root, "serving"),
        "promote_every": PROMOTE_EVERY, "eval_batch": 32,
    })

    def boundary_hook(seam):
        if seam.epoch == SWAP_EPOCH:
            write_control(control_path,
                          {"version": 1, "budget": SWAP_BUDGET})
        harness.on_boundary(seam)

    # savePath stays the default relative "runs" so the journaled config
    # snapshot carries no machine-specific temp path — run from a tmp cwd
    os.chdir(root)
    train(cfg, boundary_hook=boundary_hook)
    src = os.path.join(root, "runs", "ring8_mlp", "events.jsonl")
    dst = os.path.join(REPO, "benchmarks", "events_ring8.jsonl")
    shutil.copyfile(src, dst)

    # v4 pin: attribute the planted heterogeneous-link scenario and append
    # the resulting `attribution` event (the schema evidence) plus the
    # companion measured_link_costs artifact (the planlint PL009-011 pin)
    import numpy as np

    from matcha_tpu.analysis import lint_link_costs_data
    from matcha_tpu.obs import append_journal_record, read_journal
    from matcha_tpu.obs.attribution import (
        attribute_run,
        attribution_event_fields,
        design_matrix,
        link_costs_artifact,
        reconstruct_schedule_arrays,
    )

    events = read_journal(dst)
    # the serve plane actually landed, through the real code paths: one
    # applied hot-swap at the pinned boundary (with the re-based drift
    # prediction for replay parity), one promotion, zero retraces
    [swap] = [e for e in events if e["kind"] == "control"]
    assert (swap["action"], swap["applied"], swap["epoch"]) \
        == ("apply", True, SWAP_EPOCH), swap
    assert swap["fields"]["budget"]["budget"] == SWAP_BUDGET
    assert 0.0 < swap["predicted"]["rho"] < 1.0, swap
    [promo] = [e for e in events if e["kind"] == "promotion"]
    assert (promo["action"], promo["epoch"]) == ("promote", PROMOTE_EVERY)
    assert not [e for e in events if e["kind"] == "retrace"]
    start = next(e for e in events if e["kind"] == "run_start")
    spe = int(start["predicted"]["steps_per_epoch"])
    epochs = sorted(e["epoch"] for e in events if e["kind"] == "epoch")
    flags, _, _, _ = reconstruct_schedule_arrays(
        start["config"], (max(epochs) + 1) * spe + 1)
    A = design_matrix(flags, spe, epochs)
    y = PLANTED_BASE_SECONDS + A @ np.asarray(PLANTED_MATCHING_SECONDS)
    report = attribute_run(events, comm_seconds=y,
                           source="planted:ring8-hetero")
    assert all(report["identifiable"]), report["reason"]
    recovered = np.asarray(report["per_matching_seconds"])
    assert np.allclose(recovered, PLANTED_MATCHING_SECONDS, atol=1e-4), \
        f"planted {PLANTED_MATCHING_SECONDS} vs recovered {recovered}"
    append_journal_record(dst, "attribution",
                          **attribution_event_fields(report))
    costs_path = os.path.join(REPO, "benchmarks",
                              "measured_link_costs_ring8.json")
    artifact = link_costs_artifact(report)
    violations = lint_link_costs_data(artifact, costs_path)
    assert not violations, violations
    with open(costs_path, "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)
        f.write("\n")

    # v7 pin: the recovery ladder through the REAL helpers — flip one bit
    # in the newest checkpoint generation, let the digest sidecar convict
    # it, quarantine it aside, and journal the move exactly the way a
    # resuming run does (never a hand-written dict)
    import random

    from matcha_tpu.chaos.injectors import bitflip_checkpoint
    from matcha_tpu.train.checkpoint import (
        latest_step,
        quarantine_step,
        verify_checkpoint_digest,
    )

    ckpt = os.path.join(root, "runs", "ring8_ckpt")
    step = latest_step(ckpt)
    assert step == cfg.epochs - 1, step
    assert verify_checkpoint_digest(ckpt, step) == []
    bitflip_checkpoint(ckpt, step, random.Random(0))
    problems = verify_checkpoint_digest(ckpt, step)
    assert problems, "the digest sidecar must convict the flipped bit"
    qdir = quarantine_step(ckpt, step)
    assert latest_step(ckpt) == step - 1  # the ladder's next rung
    append_journal_record(
        dst, "recovery", scope="checkpoint", action="quarantine",
        reason=f"digest verification failed: {problems[0]}", epoch=step,
        quarantined=os.path.join("runs", "ring8_ckpt",
                                 os.path.basename(qdir)))
    print(f"reference journal regenerated: {dst}")
    print(f"reference link costs regenerated: {costs_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
