"""``TokenDecoder.declare`` takes a parameter set a layer since PR 35 (the
hybrid model's layers are of two kinds).  The two standing token cells'
trees are what the parent's were: names, order, shapes and dtypes at the
published sizes, and the initial values of the rehearsal sizes under a fixed
seed (a parameter's value follows its place among the ``param`` calls, so a
changed order would change it).  The sums were taken on the parent commit
(fbdcdca) by this file's own functions.

PR 39 edited ``models/mellum2.py`` again (RoPE tables by given positions, a
weight a position in ``_head_loss``, marked positions in ``_moe``, what a raw
row is on ``TokenDecoder``) for the block-diffusion model: the three standing
token cells' trees, the linear-attention cell's now among them, are still
what that PR's parent (9661a8b) gave, and the new cell's tree is pinned
beside them as that PR left it (51 leaves: a layer's ``q_norm`` and
``k_norm`` follow its ``wo``)."""

import copy
import hashlib
import json

import jax
import numpy as np
import pytest

from chipbench import catalog, harness
from matcha_tpu.models import select_model

PARENT = {
    "mellum2-12b-a2.5b.ep8-s4k.w2-matcha": (
        43, "5c2cb8dac8b2a104fd051db9bef3cb679fd75f30d4aca9125a4d563d99ecf9f8",
        "87a00c27559c2445f4bb1f53bfa73d3dbdb43e7f6a9c3398a4f61c76e1766028"),
    "keye-vl2-30b-a3b.ep16-s8k.w2-matcha": (
        55, "547e4b3df1cd29ef9041da6df9152a79c440cb2151fac43295380f41c2172293",
        "b46d163d5e02f0d3151a27760c98592e2dd5a6b4ace169ef126cb90da2f42ec2"),
    "qwen3-next-80b-a3b.ep64-s8k.w2-matcha": (
        70, "807c11b33ec2a05c0f2336c3754e75d1cf937fabcb8aa640a27a59716d7155ac",
        "145b40ea827e3b71d213aa40fdde1b6c156239f6f5550e32f57f02edc49e62a5"),
    "sdar-30b-a3b.ep16-s4k.w2-matcha": (
        51, "8c50b8b10b8d505cd36ddaeaed6424fdbd0dae7a93de3e6b5817429d40bd6643",
        "59a64628cb084eb0b5a4cb69c6dcf72a8c921e939340e51b47a8b519aab6c06e"),
}


def model_of(cell, rehearsal):
    _, job, conf = catalog.load_cell(cell)
    job, conf = copy.deepcopy(job), copy.deepcopy(conf)
    if rehearsal:
        harness.apply_rehearsal(job, conf)
    tc = job["train_config"]
    return select_model(tc["model"], "tokens", remat=True,
                        **tc["model_kwargs"])


@pytest.mark.parametrize("cell", sorted(PARENT))
def test_published_tree_is_the_parents(cell):
    leaves, shapes_sum, _ = PARENT[cell]
    model = model_of(cell, rehearsal=False)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), model.dummy_input(()), train=False))["params"]
    text = json.dumps([(k, list(v.shape), str(v.dtype))
                       for k, v in shapes.items()])
    assert len(shapes) == leaves
    assert hashlib.sha256(text.encode()).hexdigest() == shapes_sum


@pytest.mark.parametrize("cell", sorted(PARENT))
def test_initial_values_under_a_seed_are_the_parents(cell):
    model = model_of(cell, rehearsal=True)
    params = model.init(jax.random.PRNGKey(5), model.dummy_input(()),
                        train=False)["params"]
    h = hashlib.sha256()
    for k, v in params.items():
        h.update(k.encode())
        h.update(np.asarray(v).tobytes())
    assert h.hexdigest() == PARENT[cell][2]
