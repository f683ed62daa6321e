"""One step of decentralized momentum SGD over N workers, and an epoch of
them: per-worker forward, the task's loss and gradients (one block of
workers at a time), torch-style SGD (weight decay into the gradient, momentum
trace, Nesterov look-ahead), then one gossip exchange
``x <- x - alpha * sum_j flag_j * L_j x`` with ``(L_j x)_i = x_i - x_perm_j(i)``.

The configuration names the architecture (``reference``: what ``forward``
computes) and the task (``task``: what a raw row becomes before it and what
the loss is after it); neither is written here.

State is flat ``{"path": array[N, ...]}`` trees.  ``compute`` names the
precision of the forward and backward pass (:data:`COMPUTE`); parameters,
the update and the exchange are float32 in both:

* ``"highest"``: float32 operands, every matrix product at ``highest``: what
  the one-step comparison holds the program to;
* ``"stated"``: the precision the configurations state (float32 operands,
  matrix products at the MXU's default, which on a TPU is one bfloat16 pass
  with float32 accumulation): what the program runs, and a sixth of the
  time of ``highest``, so the whole-epoch comparison uses it.
"""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import catalog
from .layers import make_ops

COMPUTE = {"highest": lax.Precision.HIGHEST, "stated": lax.Precision.DEFAULT}


def make_epoch(config, job, perms, compute):
    """``epoch(params, stats, x_raw, y_raw, idx, flags, alpha)`` ->
    (params, stats, momentum, losses[T, N]) after ``len(idx)`` steps.

    ``x_raw``/``y_raw`` are the task's raw training rows, ``idx[T, N, B]``
    the rows each worker takes at each step, ``flags[T, M]`` the matchings
    that fire.  The state stays on the device: trees of ``array[N, ...]``.
    """
    arch = importlib.import_module(f"{__package__}.{config['reference']}")
    task = catalog.load_task(config)
    sizes = config["sizes"]
    ops = make_ops(COMPUTE[compute])
    lr, mu, wd = job["lr"], job["momentum"], job["weight_decay"]
    perms = np.asarray(perms)
    block = int(job["reference_block"])

    def loss_fn(p, stats, x, y):
        outputs, new_stats = arch.forward(p, stats, x, sizes, ops)
        return task.loss(outputs, y), new_stats

    def one_worker(args):
        p, stats, x_raw, y_raw = args
        x, y = task.prepare(x_raw, y_raw, config)
        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p, stats, x, y)
        return loss, grads, new_stats

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, stats, mom, x_raw, y_raw, flags_t, alpha):
        loss, grads, stats = lax.map(
            one_worker, (params, stats, x_raw, y_raw), batch_size=block)
        new_p, new_m = {}, {}
        for k, x in params.items():
            g = grads[k] + wd * x
            m = g + mu * mom[k]
            x = x - lr * ((g + mu * m) if job["nesterov"] else m)
            lap = sum(flags_t[j] * (x - jnp.take(x, perms[j], axis=0))
                      for j in range(len(perms)))
            new_p[k], new_m[k] = x - alpha * lap, m
        return new_p, stats, new_m, loss

    def epoch(params, stats, x_raw, y_raw, idx, flags, alpha):
        params = {k: jnp.array(v) for k, v in params.items()}
        stats = {k: jnp.array(v) for k, v in stats.items()}
        mom = {k: jnp.zeros_like(v) for k, v in params.items()}
        losses = []
        for t in range(len(idx)):
            params, stats, mom, loss = step(
                params, stats, mom, jnp.asarray(x_raw[idx[t]]),
                jnp.asarray(y_raw[idx[t]]),
                jnp.asarray(flags[t], jnp.float32), jnp.float32(alpha))
            losses.append(np.asarray(loss))
        return params, stats, mom, np.stack(losses)

    return epoch
