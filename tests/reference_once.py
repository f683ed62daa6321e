"""The benchmark's reference, computed once for the same question.

A planted fault lies in the program alone, so every fault of a cell asks
``chipbench.harness.run_reference`` the question the sound program asked:
the same configuration, hyperparameters, initial state, rows, schedule and
precision.  The fault collectors (``tests/test_chipbench_*_faults.py``) and
``tests/test_chipbench_cells.py`` used to have it answered again for every
fault, which was most of their minutes (ROADMAP D11).  :func:`install`
(called by ``chipbench_tests.load``, which they all load through) puts a
memo in front of it, keyed by a digest of **everything** it reads, so an
answer is reused only where the question is the same to the byte; a fault
that did reach the reference's inputs would get its own answer."""

import hashlib
import json

import numpy as np

from chipbench import harness

HYPER = ("lr", "momentum", "weight_decay", "nesterov")


def _question(config_file, job, hook, data, train_config, compute) -> str:
    sched, tc = hook.schedule, job["train_config"]
    h = hashlib.sha256(json.dumps(
        [compute, config_file, [tc[k] for k in HYPER], job["reference_block"],
         train_config.num_workers, train_config.batch_size, train_config.seed,
         train_config.non_iid, float(sched.alpha)],
        sort_keys=True).encode())
    first = hook.first
    arrays = [data["x_train"], data["y_train"], sched.perms, sched.flags]
    for tree in (first["params"], first["stats"]):
        for name in sorted(tree):
            h.update(name.encode())
            arrays.append(tree[name])
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str((a.shape, a.dtype)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def install():
    """Once a process: ``harness.run_reference`` behind the memo."""
    if hasattr(harness.run_reference, "answers"):
        return
    real, answers = harness.run_reference, {}

    def run_reference(config_file, job, hook, data, train_config, compute):
        key = _question(config_file, job, hook, data, train_config, compute)
        if key not in answers:
            answers[key] = real(config_file, job, hook, data, train_config,
                                compute)
        return answers[key]

    run_reference.answers = answers
    harness.run_reference = run_reference
