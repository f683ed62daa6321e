"""Device milliseconds a step in the routed experts held: the slots' rows
gathered, the grouped products, the scatter back (``matcha/moe_experts``;
where the compiler expands ``ragged_dot`` into products named
``ragged-dot-none`` with no scope, as in the Mellum cell, those rows are not
in it: ``scope_matched_pct`` says how much), from the traced window's capture
joined to the epoch program's own scopes (``chipbench/scopes.py``).  None in
an untraced run and on a program with no device-side reader."""

from chipbench.scopes import scope_ms


def read(run):
    return scope_ms(run, "matcha/moe_experts")
