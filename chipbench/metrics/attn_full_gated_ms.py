"""Device milliseconds a step in the gated full attention layers
(``matcha/attn_full_gated``), from the traced window's capture joined to the
epoch program's own scopes (``chipbench/scopes.py``).  None in an untraced run
and on a program with no device-side reader."""

from chipbench.scopes import scope_ms


def read(run):
    return scope_ms(run, "matcha/attn_full_gated")
