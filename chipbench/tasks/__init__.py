"""Tasks: what a configuration's rows are, and what is asked of the model
on them.  One task is one module named by the configuration file's ``task``
key (``catalog.load_task``; a file without the key has ``image_classes``).
It defines three plain functions and imports nothing of the program:

* ``make(seed, n_train, n_test, config) -> {name: array}``: the traffic
  generator.  Everything it draws comes from ``seed``; what it returns is
  written as ``data.npz`` where ``train()`` reads its data set.  ``x_train``
  holds the rows a worker is fed and ``y_train`` what the task needs beside
  them, row for row (``x_test``/``y_test`` alike): the reference gathers
  both by the row numbers of the program's own loader.
* ``prepare(x_raw, y_raw, config) -> (inputs, targets)``: one worker's
  batch of raw rows, made into what the architecture's ``forward`` takes
  and what ``loss`` takes.  Traced inside the reference's jitted step.
* ``loss(outputs, targets) -> f32 scalar``: over what ``forward`` returned.

A sample, for ``job_samples_per_s``, is one row of ``x_train``.
"""
