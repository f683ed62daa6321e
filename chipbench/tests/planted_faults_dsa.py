"""The faults that the sparse-attention cell's limits were set against,
planted in the program alone (the reference never sees them), so that its
controls can be run again through ``check.compare`` at either size:

    python3 chipbench/tests/planted_faults_dsa.py <fault> --workload \\
        keye-vl2-30b-a3b.ep16-s8k.w2-matcha --seed <n> --seconds 45 --trace 0

is one whole benchmark run on the chip with ``<fault>`` planted (``correct``
has to read false; ``--rehearse-on-cpu`` walks it tiny), and
``test_dsa_cell_faults.py`` rehearses every one on the CPU.  ``sound``
plants nothing.  PERF.md section 6 has the readings (PR 31)."""

import contextlib
import dataclasses
import sys
import time
from pathlib import Path

FAULTS = ("bf16_wire", "no_exchange", "selection_left_out", "half_the_keys",
          "indexer_loss_left_out", "indexer_attached", "bf16_index_scores",
          "docs_ignored")


class _NothingDetached:
    """``jax.lax`` with a ``stop_gradient`` that stops nothing."""

    def __getattr__(self, name):
        from jax import lax

        return getattr(lax, name)

    @staticmethod
    def stop_gradient(x):
        return x


@contextlib.contextmanager
def planted(fault):
    """``fault`` in the program for the length of the block: a field of the
    ``TrainConfig`` the harness builds (the job file, which the reference
    reads, stays as it is), or a function of the model swapped."""
    import jax.numpy as jnp

    from chipbench import harness
    from matcha_tpu.models import keye_vl2

    names = ("_select", "_query_block", "_index_scores", "_visible",
             "_project", "lax")
    build = harness.build_train_config
    real = {name: getattr(keye_vl2, name) for name in names}

    def program_only(change):
        harness.build_train_config = lambda job, workdir, data: change(
            build(job, workdir, data))

    def half_the_keys(tc):
        kwargs = dict(tc.model_kwargs)
        kwargs["sizes"] = dict(kwargs["sizes"],
                               index_topk=kwargs["sizes"]["index_topk"] // 2)
        return dataclasses.replace(tc, model_kwargs=kwargs)

    if fault == "bf16_wire":  # the precision below the stated float32 wire
        program_only(lambda tc: dataclasses.replace(tc, wire_dtype="bf16"))
    elif fault == "no_exchange":
        program_only(lambda tc: dataclasses.replace(tc, communicator="none"))
    elif fault == "half_the_keys":  # top-1,024 in place of top-2,048
        program_only(half_the_keys)
    elif fault == "selection_left_out":  # dense attention over what is seen
        keye_vl2._select = lambda scores, sees, k: sees
    elif fault == "indexer_loss_left_out":  # the loss is cross-entropy alone
        def no_kl(*args, **kwargs):
            out, kl, *counts = real["_query_block"](*args, **kwargs)
            return (out, 0.0 * kl, *counts)
        keye_vl2._query_block = no_kl
    elif fault == "indexer_attached":  # its loss reaches the model through
        def attached(p, h, sizes):  # the input it shares with the attention
            keye_vl2.lax = _NothingDetached()
            try:
                return real["_project"](p, h, sizes)
            finally:
                keye_vl2.lax = real["lax"]
        keye_vl2._project = attached
    elif fault == "bf16_index_scores":  # one bfloat16 pass, not ``highest``
        def one_pass(qi, ki, w):
            import jax

            dots = jnp.einsum("bqjd,bsd->bqjs", qi.astype(jnp.bfloat16),
                              ki.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
            return jnp.sum(w[..., None] * jax.nn.relu(dots), axis=2)
        keye_vl2._index_scores = one_pass
    elif fault == "docs_ignored":  # attention crosses document boundaries
        keye_vl2._visible = lambda q, k, q_docs, k_docs, window: real[
            "_visible"](q, k, jnp.zeros_like(q_docs), jnp.zeros_like(k_docs),
                        window)
    elif fault != "sound":
        raise SystemExit(f"unknown fault {fault!r}: sound or one of {FAULTS}")
    try:
        yield
    finally:
        harness.build_train_config = build
        for name, thing in real.items():
            setattr(keye_vl2, name, thing)


if __name__ == "__main__":
    t0 = time.perf_counter()
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
    from chipbench import harness

    with planted(sys.argv[1]):
        sys.exit(harness.main(sys.argv[2:], t0))
