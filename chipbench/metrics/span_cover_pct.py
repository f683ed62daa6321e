"""The share of an epoch period (one loop top of ``train()`` to the next)
that its leaf spans cover, in the worst of the window's epochs after the
profiler's stop: it falls when work is added to the loop under no name."""

from chipbench.spans import leaves, window_periods


def read(run):
    covers = [sum(s["t1"] - s["t0"] for s in leaves(r)) / (r["t1"] - r["t0"])
              for r in window_periods(run)]
    return 100.0 * min(covers) if covers else None
