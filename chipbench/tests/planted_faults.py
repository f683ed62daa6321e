"""The faults that the token cell's limits were set against, planted in the
program alone (the reference never sees them), so that its controls can be
run again through ``check.compare`` at either size:

    python3 chipbench/tests/planted_faults.py <fault> --workload \\
        mellum2-12b-a2.5b.ep8-s4k.w2-matcha --seed <n> --seconds 45 --trace 0

is one whole benchmark run on the chip with ``<fault>`` planted (``correct``
has to read false; ``--rehearse-on-cpu`` walks it tiny), and
``test_token_cell_faults.py`` rehearses every one on the CPU.  ``sound``
plants nothing.  PERF.md section 6 has the readings (PR 27)."""

import contextlib
import dataclasses
import sys
import time
from pathlib import Path

FAULTS = ("bf16_wire", "no_exchange", "window_ignored", "docs_ignored",
          "no_renorm", "expert_dropped")


@contextlib.contextmanager
def planted(fault):
    """``fault`` in the program for the length of the block: a field of the
    ``TrainConfig`` the harness builds (the job file, which the reference
    reads, stays as it is), or a function of the model swapped."""
    import jax.numpy as jnp

    from chipbench import harness
    from matcha_tpu.models import mellum2

    real = harness.build_train_config, mellum2._visible, mellum2._experts

    def program_only(change):
        harness.build_train_config = lambda job, workdir, data: change(
            real[0](job, workdir, data))

    def sizes(**new):
        def change(tc):
            kwargs = dict(tc.model_kwargs)
            kwargs["sizes"] = dict(kwargs["sizes"], **new)
            return dataclasses.replace(tc, model_kwargs=kwargs)
        return change

    if fault == "bf16_wire":  # the precision below the stated float32 wire
        program_only(lambda tc: dataclasses.replace(tc, wire_dtype="bf16"))
    elif fault == "no_exchange":
        program_only(lambda tc: dataclasses.replace(tc, communicator="none"))
    elif fault == "window_ignored":  # sliding layers see the whole row
        program_only(sizes(sliding_window=10 ** 6))
    elif fault == "no_renorm":  # ``norm_topk_prob`` dropped
        program_only(sizes(norm_topk_prob=False))
    elif fault == "docs_ignored":  # attention crosses document boundaries
        mellum2._visible = lambda q, k, q_docs, k_docs, window: real[1](
            q, k, jnp.zeros_like(q_docs), jnp.zeros_like(k_docs), window)
    elif fault == "expert_dropped":  # the slots of one expert held
        def dropped(p, x, w_held, took, z):
            e = min(3, took.shape[1] - 1)
            return real[2](p, x, w_held.at[:, e].set(0.0),
                           took.at[:, e].set(False), z)
        mellum2._experts = dropped
    elif fault != "sound":
        raise SystemExit(f"unknown fault {fault!r}: sound or one of {FAULTS}")
    try:
        yield
    finally:
        (harness.build_train_config, mellum2._visible,
         mellum2._experts) = real


if __name__ == "__main__":
    t0 = time.perf_counter()
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
    from chipbench import harness

    with planted(sys.argv[1]):
        sys.exit(harness.main(sys.argv[2:], t0))
