"""Plain references: each architecture's forward pass, and the decentralized
momentum-SGD step with one gossip exchange, in straightforward ``jax.numpy``
(``lax`` only for the convolution).  Nothing here imports ``matcha_tpu``.

One architecture is one module named by the configuration file's
``reference`` key.  It defines ``forward(params, stats, x, sizes, ops) ->
(outputs, new stats)`` over flat ``{"a/b/c": array}`` trees whose names are
the program's own, so mapping the program's parameters onto the reference is
renaming nothing, and ``forward_macs(sizes)``, one sample's forward
multiply-accumulates from the shapes alone.  ``x`` is what the
configuration's task prepared (``chipbench/tasks``) and ``outputs`` what its
loss takes; ``ops`` is ``layers.Ops``: ``conv`` and ``dot`` at the precision
the step computes at, and that ``precision`` for the products the module
writes itself.  ``stats`` is ``{}`` where nothing runs a statistic.

A later PR adds an architecture as a new module here and edits neither
``step.py`` nor ``layers.py``: the step is the optimizer's and the
exchange's, which are the program's whatever the model.
"""
