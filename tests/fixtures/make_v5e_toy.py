#!/usr/bin/env python
"""Regenerate ``v5e_toy.xplane.pb``: a ``jax.profiler`` capture of a toy
program **on the chip** (``chiprun -- python tests/fixtures/make_v5e_toy.py``;
the file comes back in ``chiprun_out/``).  On the CPU the capture has no
device plane and is of no use to ``tests/test_device_scopes.py``.

The program: a scanned gradient step over four layers, each two
``device_span``s under one ``jax.checkpoint``, with an update and an exchange
under ``matcha/sgd`` and ``comm/step``; then a three-pass chain under
``comm/step``; each run twice with the profiler open.  (PR 37's capture:
TPU v5 lite, jax 0.9.0; 177 KB.)"""

import glob
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from matcha_tpu.utils.profiling import device_span  # noqa: E402


def layer(w, x):
    with device_span("matcha/attn_toy"):
        h = jnp.tanh(x @ w)
    with device_span("matcha/mlp_toy"):
        h = jax.nn.relu(h @ w.T) + x
    return h


def loss(ws, x):
    def body(h, w):
        return jax.checkpoint(layer)(w, h), None

    with device_span("matcha/fwd_bwd"):
        h, _ = jax.lax.scan(body, x, ws)
        return jnp.mean(h * h)


@jax.jit
def epoch_scan(ws, xs):
    def step(ws, x):
        g = jax.grad(loss)(ws, x)
        with device_span("matcha/sgd"):
            ws = ws - 0.01 * g
        with device_span("comm/step"):
            ws = 0.5 * (ws + jnp.roll(ws, 1, axis=0))
        return ws, jnp.sum(g[0, 0])

    return jax.lax.scan(step, ws, xs)


@jax.jit
def gossip_chain(ws):
    with device_span("comm/step"):
        for _ in range(3):
            ws = 0.5 * (ws + jnp.roll(ws, 1, axis=0))
    return ws


def main():
    ws = jnp.ones((4, 1024, 1024), jnp.float32) * 0.01
    xs = jnp.ones((8, 512, 1024), jnp.float32)
    jax.block_until_ready((epoch_scan(ws, xs), gossip_chain(ws)))
    where = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(where, profiler_options=options)
    for _ in range(2):
        jax.block_until_ready(epoch_scan(ws, xs))
        jax.block_until_ready(gossip_chain(ws))
    jax.profiler.stop_trace()
    (capture,) = glob.glob(os.path.join(where, "**", "*.xplane.pb"),
                           recursive=True)
    out = os.path.join(os.path.dirname(__file__), "..", "..", "chiprun_out")
    os.makedirs(out, exist_ok=True)
    shutil.copy(capture, os.path.join(out, "v5e_toy.xplane.pb"))
    print(jax.devices()[0].device_kind, os.path.getsize(capture), "B")


if __name__ == "__main__":
    main()
