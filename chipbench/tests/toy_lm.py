"""A toy language model for the tests of the task seam, and for nothing
else: an embedding, one bias-free projection under a tanh, an untied head.
Both products are the module's own, at the precision ``ops`` carries."""

import jax.numpy as jnp


def forward(p, stats, x, sizes, ops):
    h = p["embed/embedding"][x["ids"]]
    z = jnp.tanh(jnp.einsum("bsh,hk->bsk", h, p["proj/kernel"],
                            precision=ops.precision))
    return jnp.einsum("bsk,kv->bsv", z, p["head/kernel"],
                      precision=ops.precision), {}


def forward_macs(sizes) -> int:
    return sizes["seq_len"] * sizes["hidden"] * (
        sizes["hidden"] + sizes["vocab_held"])
