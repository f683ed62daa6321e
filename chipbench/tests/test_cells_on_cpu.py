"""Each cell rehearsed tiny on the CPU through the whole of a run but the
look for a chip: the reference against the program, the control that has to
fail, the timed path broken underneath in four ways (each test names the
limits that catch it), and the fold over four devices."""

import argparse
import copy
import json
import tempfile
import time
from pathlib import Path

import pytest

from chipbench import catalog, harness

CELLS = [w["name"] for w in catalog.benchmark()["workloads"]]


def rehearse(cell, seed=7, trace=0, edit=None):
    """One rehearsal run; ``edit(job)`` changes the job before it runs."""
    import jax

    bench, job, config_file = catalog.load_cell(cell)
    job, config_file = copy.deepcopy(job), copy.deepcopy(config_file)
    harness.apply_rehearsal(job, config_file)
    if edit:
        edit(job)
    devices = jax.devices()[:job["chips"]]
    device = harness.describe_devices(devices)
    args = argparse.Namespace(workload=cell, seed=seed, seconds=0.5,
                              trace=trace, rehearse_on_cpu=True)
    with tempfile.TemporaryDirectory() as workdir:
        return harness.run_cell(args, bench, job, config_file, devices,
                                device, None, {"hits": 0, "misses": 0},
                                Path(workdir), time.perf_counter())


@pytest.mark.parametrize("cell", CELLS)
def test_program_agrees_with_reference(cell):
    line = rehearse(cell)
    assert line["correct"], line["check"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {
        m["name"] for m in catalog.metrics_of(
            catalog.benchmark(), "end_to_end", cell)}
    json.dumps(line)


def over(line):
    """The judged numbers of a result line that are over their limits."""
    return {k for k, (value, limit) in line["check"].items()
            if limit is not None and value > limit}


@pytest.mark.parametrize("cell", CELLS[:1])
def test_control_bf16_wire_is_not_correct(cell):
    """The precision below the configuration's: the program's own bf16 wire."""
    def bf16_wire(job):
        job["train_config"]["wire_dtype"] = "bf16"

    line = rehearse(cell, edit=bf16_wire)
    assert not line["correct"]
    assert "dparam_gap" in over(line), line["check"]


def test_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from matcha_tpu.train import loop

    real = loop.make_train_step

    def frozen(*args, **kwargs):
        step = real(*args, **kwargs)

        def wrapped(state, xb, yb, rng=None):
            new, metrics = step(state, xb, yb, rng)
            return state.replace(step=new.step,
                                 telemetry=new.telemetry), metrics

        return wrapped

    monkeypatch.setattr(loop, "make_train_step", frozen)
    line = rehearse(CELLS[0])
    assert not line["correct"]
    assert line["check"]["dparam_gap"][0] > line["check"]["dparam_gap"][1]


def program_only(monkeypatch, **fields):
    """Change fields of the program's ``TrainConfig`` and not the job the
    reference reads."""
    real = harness.build_train_config

    def build(job, workdir, dataset_path):
        import dataclasses

        return dataclasses.replace(real(job, workdir, dataset_path), **fields)

    monkeypatch.setattr(harness, "build_train_config", build)


@pytest.mark.parametrize("fields, fails", [
    ({"nesterov": False}, {"dparam_all_gap", "step1_dparam_all_gap"}),
    ({"weight_decay": 0.0}, {"dparam_gap"}),
], ids=["no_nesterov", "no_weight_decay"])
def test_wrong_update_rule_is_not_correct(monkeypatch, fields, fails):
    program_only(monkeypatch, **fields)
    line = rehearse(CELLS[0])
    assert not line["correct"]
    assert fails <= over(line), line["check"]


def test_half_the_batch_left_out_is_not_correct(monkeypatch):
    import jax.numpy as jnp
    from matcha_tpu.train import loop

    real = loop.make_train_step

    def half(*args, **kwargs):
        step = real(*args, **kwargs)

        def wrapped(state, xb, yb, rng=None):
            b = xb.shape[1] // 2  # [N, B, ...]: the first half, twice
            return step(state, jnp.concatenate([xb[:, :b], xb[:, :b]], 1),
                        jnp.concatenate([yb[:, :b], yb[:, :b]], 1), rng)

        return wrapped

    monkeypatch.setattr(loop, "make_train_step", half)
    line = rehearse(CELLS[0])
    assert not line["correct"]
    assert {"loss_gap", "step1_loss_gap", "step1_momentum_all_gap"} \
        <= over(line), line["check"]


def test_exchange_left_out_is_not_correct():
    def no_gossip(job):
        job["train_config"]["communicator"] = "none"

    line = rehearse(CELLS[0], edit=no_gossip)
    assert not line["correct"]
    assert line["check"]["disagree_gap"][0] > line["check"]["disagree_gap"][1]


def test_fold_over_four_virtual_devices():
    def four_chips(job):
        job["chips"] = 4

    line = rehearse(CELLS[0], edit=four_chips)
    assert line["device"]["count"] == 4
    assert line["correct"], line["check"]
