"""The block-diffusion cell's controls, rehearsed tiny on the CPU: each
fault of ``planted_faults_bd.py`` in the program alone has to read incorrect
by the cell's own limits, through ``chipbench/check.py``'s comparisons.

A whole rehearsal (``test_cells_on_cpu.rehearse``) is two ``train()`` calls
and the reference twice: a quarter of a minute alone and three times that
beside tier-1's other workers, and ten of them would be this file's whole
time budget (ISSUE 39) several times over.  So each fault goes through the
half of ``harness.run_cell``'s check that tells it, the reference is computed
once a file and not once a fault (no fault reaches it), and only the two
faults that lie in ``train()``'s own configuration pay for a ``train()``:

* ``bf16_wire`` and ``no_exchange``: the job's first epoch through the
  harness's own seam (``stage_job``, the ``OneStep`` hook) against the
  reference's epoch at the stated precision, by ``check.compare`` and the
  epoch's limits; the reference starts from the initial state the first of
  the two runs kept (no fault touches it);
* the eight faults in the model: what the one-step comparison holds the
  program to is the first gradient as the optimizer got it (weight decay
  added: ``step1_momentum_*``; ``chipbench/check.py``'s docstring) and the
  first loss, so these compute just that: ``jax.grad`` of the model's own
  ``batch_loss`` on the first step's rows with the fault planted, against
  the same of ``reference/sdar.py`` and the task's loss at ``highest``, by
  ``check.worst_norm_gap`` and the ``step1`` limits.  ``sound`` goes the same
  way and has to read correct; the sound cell's whole run, ``train()``'s
  optimizer included, is ``tests/test_chipbench_cells.py``'s."""

import copy
import functools
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from chipbench import catalog, check, harness
from chipbench.reference import sdar as reference
from chipbench.reference.layers import make_ops
from planted_faults_bd import FAULTS, planted

CELL = "sdar-30b-a3b.ep16-s4k.w2-matcha"
#: a seed whose rows hold document boundaries at the rehearsal's 64 tokens a
#: row (most hold none: a document is 256 tokens there, and
#: ``test_the_rows_hold_document_boundaries`` says so if this one stops)
SEED = 2
IN_TRAIN = {"bf16_wire": {"dparam_gap"}, "no_exchange": {"disagree_gap"}}
IN_MODEL = {
    "causal_in_block": {"step1_momentum_gap"},
    "own_clean_block_seen": {"step1_momentum_gap", "step1_loss_gap"},
    "clean_sees_noisy": {"step1_momentum_gap"},
    "positions_offset": {"step1_momentum_gap"},
    "weight_left_out": {"step1_momentum_gap", "step1_loss_gap"},
    "next_token_shift": {"step1_momentum_gap", "step1_loss_gap"},
    "docs_ignored": {"step1_momentum_gap"},
    "qk_norm_left_out": {"step1_momentum_gap"},
}


def cell_files():
    _, job, config_file = catalog.load_cell(CELL)
    job, config_file = copy.deepcopy(job), copy.deepcopy(config_file)
    harness.apply_rehearsal(job, config_file)
    return job, config_file


def over(numbers, limits):
    return {k for k, limit in limits.items() if not numbers[k] <= limit}


# --------------------------------------------- the two faults in train()

def first_epoch(fault):
    """``fault``'s own ``train()`` call of the job's first epoch: (hook,
    data, TrainConfig, the epoch's loss)."""
    from matcha_tpu.train import train

    job, config_file = cell_files()
    with tempfile.TemporaryDirectory() as workdir, planted(fault):
        data, train_config = harness.stage_job(
            job, config_file, SEED, job["data"]["steps_per_epoch"],
            Path(workdir))
        hook = harness.OneStep()
        loss = train(train_config, boundary_hook=hook).history[0]["loss"]
    return hook, data, train_config, loss


@functools.lru_cache(maxsize=None)
def reference_epoch():
    """(what the reference's first epoch at the stated precision gives, the
    run whose initial state it started from)."""
    job, config_file = cell_files()
    run = first_epoch("bf16_wire")
    return harness.run_reference(config_file, job, *run[:3], "stated"), run


@pytest.mark.parametrize("fault", sorted(IN_TRAIN))
def test_fault_in_the_exchange_is_not_correct(fault):
    job, _ = cell_files()
    want, run = reference_epoch()
    hook, _, _, loss = run if fault == "bf16_wire" else first_epoch(fault)
    numbers = check.compare(hook.after, loss, *want)
    limits = {k: v for k, v in job["limits"].items()
              if not k.startswith("step1_")}
    assert IN_TRAIN[fault] <= over(numbers, limits), numbers


# ------------------------------------------- the eight faults in the model

@functools.lru_cache(maxsize=None)
def first_batch():
    """(the model, one worker's initial parameters, the raw rows of the
    job's first step: both workers' batches as one, so that a row with a
    document boundary is among them)."""
    from matcha_tpu.models import select_model

    job, config_file = cell_files()
    tc = job["train_config"]
    data = catalog.load_task(config_file).make(
        SEED, tc["num_workers"] * tc["batch_size"], 1, config_file)
    model = select_model(tc["model"], "tokens", remat=tc["remat"],
                         **tc["model_kwargs"])
    params = model.init(jax.random.PRNGKey(tc["seed"]),
                        model.dummy_input(()), train=False)["params"]
    return model, params, jnp.asarray(data["x_train"]), jnp.asarray(
        data["y_train"])


def as_the_optimizer_got_it(loss_and_grads, params):
    """(the loss, per-leaf norms ``{leaf: [1]}`` of gradient + weight decay x
    parameter): ``step1_momentum`` after one step."""
    job, _ = cell_files()
    wd = job["train_config"]["weight_decay"]
    loss, grads = loss_and_grads
    return float(loss), {k: np.asarray(jnp.linalg.norm(
        grads[k] + wd * params[k]))[None] for k in grads}


@functools.lru_cache(maxsize=None)
def reference_step():
    _, config_file = cell_files()
    _, params, x_raw, y_raw = first_batch()
    task = catalog.load_task(config_file)
    x, targets = task.prepare(x_raw, y_raw, config_file)
    ops = make_ops(lax.Precision.HIGHEST)
    return as_the_optimizer_got_it(jax.jit(jax.value_and_grad(
        lambda p: task.loss(reference.forward(
            p, {}, x, config_file["sizes"], ops)[0], targets)))(params),
        params)


def step1_numbers(fault):
    model, params, x_raw, y_raw = first_batch()
    with planted(fault):
        loss, norms = as_the_optimizer_got_it(jax.jit(jax.value_and_grad(
            lambda p: model.apply({"params": p}, x_raw, y_raw,
                                  method="batch_loss")[0]))(params), params)
    want_loss, want = reference_step()
    whole = lambda n: {"all": np.sqrt(sum(np.square(v) for v in n.values()))}
    return {"step1_loss_gap": abs(loss - want_loss) / want_loss,
            "step1_momentum_gap": check.worst_norm_gap(norms, want),
            "step1_momentum_all_gap": check.worst_norm_gap(whole(norms),
                                                           whole(want))}


def step1_limits():
    job, _ = cell_files()
    return {k: job["limits"][k] for k in (
        "step1_loss_gap", "step1_momentum_gap", "step1_momentum_all_gap")}


def test_every_fault_has_its_control():
    assert set(IN_TRAIN) | set(IN_MODEL) == set(FAULTS)


def test_the_rows_hold_document_boundaries():
    docs = np.asarray(first_batch()[3])
    docs = docs[:, :docs.shape[1] // 2]
    assert (docs[:, 1:] != docs[:, :-1]).sum() >= 2


def test_sound_reads_correct():
    numbers = step1_numbers("sound")
    assert not over(numbers, step1_limits()), numbers


@pytest.mark.parametrize("fault", sorted(IN_MODEL))
def test_fault_in_the_model_is_not_correct(fault):
    numbers = step1_numbers(fault)
    assert IN_MODEL[fault] <= over(numbers, step1_limits()), numbers
