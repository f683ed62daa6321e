"""Plain references: each architecture's forward pass, and the decentralized
momentum-SGD step with one gossip exchange, in straightforward ``jax.numpy``
(``lax`` only for the convolution).  Nothing here imports ``matcha_tpu``.

One architecture is one module named by the configuration file's
``reference`` key; it defines ``forward(params, stats, x, sizes, conv, dot)``
over flat ``{"a/b/c": array}`` trees whose names are the program's own, so
mapping the program's parameters onto the reference is renaming nothing.
"""
