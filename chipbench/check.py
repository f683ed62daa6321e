"""What decides ``correct``: the program against the plain reference, number
by number, each with a limit of its own, in two comparisons.

The program's state is seen at epoch boundaries only.  So the first
comparison is of the first whole epoch of the very ``train()`` call the
window then times (its compiled epoch program, its batch stack, all N
workers), against the reference at the stated precision.  After an epoch of
steps a rounding-level difference has grown to tens of percent in single
leaves, so this one holds the exchange, its wire and the update, and cannot
tell a lower compute precision.  The second (``step1_*``) is of one step: a
second ``train()`` call through the same seam on a one-step epoch of the same
N, batch and model, against the reference at ``highest``: the first gradient
and the first update before anything has grown.  It does not tell a bfloat16
forward/backward from the stated precision either (one bfloat16 pass on the
MXU): on the chip the two read alike against ``highest``, leaf by leaf and
over all leaves.  **No number here fails under bfloat16 compute**; PERF.md
section 6 has the readings, and section 7 the number that might.  Each cell's
job file names the numbers it judges and their limits; PERF.md section 6 has
the readings each limit was set from, and which planted fault fails which.

* ``loss_gap``: the epoch's mean loss, as a share of the reference's.
* ``momentum_*``: the optimizer's momentum trace.  After one step it is the
  first gradient as the optimizer got it (weight decay added).
* ``dparam_*``: the parameters' change from their initial values.
* ``disagree_*``: each worker's distance from the workers' mean, which the
  gossip exchange shapes.

``momentum_zero_gap`` (printed, judged nowhere yet) is the largest norm the
program's trace has in a leaf whose reference norm is all but zero (under a
thousandth of the worker's median leaf: the bias of a convolution that a
batch norm follows), as a share of that median: float32 cotangents sum to
zero there, bfloat16 ones do not.

``*_gap`` is taken by the worst leaf and worker: the gap between the
program's norm and the reference's (not the norm of their difference), as a
share of the reference's norm of that leaf or of that worker's median leaf,
whichever is larger, since some gradients are all but zero.  ``*_all_gap``
is the same gap of the norm over all leaves together, by the worst worker:
steadier, and blind to a fault in a small leaf.  Both sides' norms come from
:func:`summarize`, on the device, so only ``[N]`` vectors cross to the host.
"""

import jax
import jax.numpy as jnp
import numpy as np

QUANTITIES = ("momentum", "dparam", "disagree")


def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.reshape(v.shape[0], -1)),
                                axis=1)) for k, v in tree.items()}


@jax.jit
def summarize(params, momentum, first):
    """Per-leaf, per-worker norms ``{leaf: f32[N]}`` of the momentum trace,
    of the parameters' change from ``first`` and of their distance from the
    workers' mean."""
    return {
        "momentum": _norms(momentum),
        "dparam": _norms({k: params[k] - first[k] for k in params}),
        "disagree": _norms({k: v - jnp.mean(v, axis=0, keepdims=True)
                            for k, v in params.items()}),
    }


def worst_norm_gap(program: dict, reference: dict, leaves=None) -> float:
    """Worst leaf and worker of ``|program - reference| / max(reference,
    the worker's median leaf)`` over per-leaf norm vectors."""
    want = {k: np.asarray(v, np.float64) for k, v in reference.items()}
    floor = np.median(np.stack(list(want.values())), axis=0)
    gap = 0.0
    for k in (leaves or sorted(want)):
        got = np.asarray(program[k], np.float64)
        scale = np.maximum(want[k], floor)
        gap = max(gap, float(np.max(np.abs(got - want[k]) / scale)))
    return gap


def zero_leaf_gap(program: dict, reference: dict, share=1e-3) -> float:
    """Worst worker of the program's largest norm among the leaves whose
    reference norm is under ``share`` of the worker's median leaf, over that
    median."""
    want = {k: np.asarray(v, np.float64) for k, v in reference.items()}
    floor = np.median(np.stack(list(want.values())), axis=0)
    return max((float(np.max(np.where(
        want[k] < share * floor, np.asarray(program[k], np.float64) / floor,
        0.0))) for k in want), default=0.0)


def _over_all_leaves(norms: dict):
    return {"all": np.sqrt(sum(np.square(np.asarray(v, np.float64))
                               for v in norms.values()))}


def compare(program, program_loss, reference, reference_losses, prefix=""):
    """Every number, from the two sides' :func:`summarize` outputs and
    losses, each named ``prefix`` + its name."""
    ref_loss = float(np.mean(reference_losses))
    out = {"loss_gap": abs(program_loss - ref_loss) / ref_loss}
    for name in QUANTITIES:
        out[name + "_gap"] = worst_norm_gap(program[name], reference[name])
        out[name + "_all_gap"] = worst_norm_gap(
            _over_all_leaves(program[name]), _over_all_leaves(reference[name]))
    out["momentum_zero_gap"] = zero_leaf_gap(program["momentum"],
                                             reference["momentum"])
    return {prefix + k: v for k, v in out.items()}


def verdict(numbers: dict, limits: dict):
    """(every judged number within its limit, lines that print each number
    compared beside its limit, then the numbers that are not judged)."""
    lines, ok = [], True
    for name, limit in limits.items():
        value = numbers[name]
        within = bool(np.isfinite(value) and value <= limit)
        ok = ok and within
        lines.append(f"# check {name} = {value:.6g}  limit {limit:g}  "
                     f"{'ok' if within else 'OVER'}")
    lines.append("# not judged: " + ", ".join(
        f"{k} = {v:.4g}" for k, v in numbers.items() if k not in limits))
    return ok, lines
