"""Programs compiled, not read from the persistent cache, over the whole
process (``jax.monitoring`` cache-miss events).  0 in every run but a
checkout's first."""


def read(run):
    return run["cache"]["misses"]
