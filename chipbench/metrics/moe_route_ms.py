"""Device milliseconds a step in the router: its scores, the top-k and the
weights of the experts held (``matcha/moe_route``; the slots' gathers and
scatters are the experts'), from the traced window's capture joined to the
epoch program's own scopes (``chipbench/scopes.py``).  None in an untraced run
and on a program with no device-side reader."""

from chipbench.scopes import scope_ms


def read(run):
    return scope_ms(run, "matcha/moe_route")
