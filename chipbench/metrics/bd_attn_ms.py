"""Device milliseconds a step in the block-diffusion attention
(``matcha/bd_attn``: the scores of every query block against the clean keys
up to its end and, for a noisy block, its own noisy keys; the mask; the
softmax; the values; forward, recomputed and backward), from the traced
window's capture joined to the epoch program's own scopes
(``chipbench/scopes.py``).  None in an untraced run and on a program with no
such scope."""

from chipbench.scopes import scope_ms


def read(run):
    return scope_ms(run, "matcha/bd_attn")
