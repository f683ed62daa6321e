"""The faults that the block-diffusion cell's limits were set against,
planted in the program alone (the reference never sees them), so that its
controls can be run again through ``check.compare`` at either size:

    python3 chipbench/tests/planted_faults_bd.py <fault> --workload \\
        sdar-30b-a3b.ep16-s4k.w2-matcha --seed <n> --seconds 45 --trace 0

is one whole benchmark run on the chip with ``<fault>`` planted (``correct``
has to read false; ``--rehearse-on-cpu`` walks it tiny), and
``test_bd_cell_faults.py`` rehearses every one on the CPU.  ``sound`` plants
nothing.  PERF.md section 6 has the readings (PR 39)."""

import contextlib
import dataclasses
import sys
import time
from pathlib import Path

FAULTS = ("bf16_wire", "no_exchange", "causal_in_block",
          "own_clean_block_seen", "clean_sees_noisy", "positions_offset",
          "weight_left_out", "next_token_shift", "docs_ignored",
          "qk_norm_left_out")


@contextlib.contextmanager
def planted(fault):
    """``fault`` in the program for the length of the block: a field of the
    ``TrainConfig`` the harness builds (the job file, which the reference
    reads, stays as it is), or a function of the model swapped."""
    import jax.numpy as jnp

    from chipbench import harness
    from matcha_tpu.models import sdar

    names = ("_bd_visible", "_block_keys", "rope_tables", "_head_loss",
             "_rms_norm")
    build = harness.build_train_config
    real = {name: getattr(sdar, name) for name in names}

    def program_only(change):
        harness.build_train_config = lambda job, workdir, data: change(
            build(job, workdir, data))

    def mask(change):
        """``_bd_visible`` with ``change(sees, q_blk, q_noisy, k_blk,
        k_noisy, same_document)`` applied, each ``[B | 1, q, k]``."""
        def visible(q_at, q_noisy, k_at, k_noisy, q_docs, k_docs, block):
            sees = real["_bd_visible"](q_at, q_noisy, k_at, k_noisy, q_docs,
                                       k_docs, block)
            return change(
                sees, q_at=q_at[None, :, None], k_at=k_at[None, None, :],
                q_noisy=q_noisy[None, :, None], k_noisy=k_noisy[None, None],
                block=block,
                same=q_docs[:, :, None] == k_docs[:, None, :])
        sdar._bd_visible = visible

    if fault == "bf16_wire":  # the precision below the stated float32 wire
        program_only(lambda tc: dataclasses.replace(tc, wire_dtype="bf16"))
    elif fault == "no_exchange":
        program_only(lambda tc: dataclasses.replace(tc, communicator="none"))
    elif fault == "causal_in_block":  # a noisy token sees no later noisy one
        mask(lambda sees, q_at, k_at, q_noisy, k_noisy, **_: sees & ~(
            q_noisy & k_noisy & (k_at > q_at)))
    elif fault == "own_clean_block_seen":  # the answer leaks to its question
        mask(lambda sees, q_at, k_at, q_noisy, k_noisy, block, same: sees | (
            q_noisy & ~k_noisy & (k_at // block == q_at // block) & same))
    elif fault == "clean_sees_noisy":  # ... its own block's noisy copy
        sdar._block_keys = lambda start, stop, noisy: real["_block_keys"](
            start, stop, True)
        mask(lambda sees, q_at, k_at, q_noisy, k_noisy, block, same: sees | (
            ~q_noisy & k_noisy & (k_at // block == q_at // block) & same))
    elif fault == "positions_offset":  # the noisy copy at S..2S-1, not 0..S-1
        def offset(positions, head_dim, theta):
            s = positions.shape[0] // 2
            return real["rope_tables"](
                positions.at[:s].add(float(s)), head_dim, theta)
        sdar.rope_tables = offset
    elif fault == "weight_left_out":  # every masked position counts once
        sdar._head_loss = lambda h, head, targets, sizes, weights, \
            normaliser: real["_head_loss"](h, head, targets, sizes,
                                           normaliser=normaliser)
    elif fault == "next_token_shift":  # position i - 1 predicts token i
        sdar._head_loss = lambda h, head, *rest, **more: real["_head_loss"](
            jnp.roll(h, 1, axis=1), head, *rest, **more)
    elif fault == "docs_ignored":  # attention crosses document boundaries
        sdar._bd_visible = lambda q_at, q_noisy, k_at, k_noisy, q_docs, \
            k_docs, block: real["_bd_visible"](
                q_at, q_noisy, k_at, k_noisy, jnp.zeros_like(q_docs),
                jnp.zeros_like(k_docs), block)
    elif fault == "qk_norm_left_out":  # q and k go to RoPE as projected
        sdar._rms_norm = lambda x, scale, eps: x.astype(jnp.float32) \
            if x.ndim == 4 else real["_rms_norm"](x, scale, eps)
    elif fault != "sound":
        raise SystemExit(f"unknown fault {fault!r}: sound or one of {FAULTS}")
    try:
        yield
    finally:
        harness.build_train_config = build
        for name, thing in real.items():
            setattr(sdar, name, thing)


if __name__ == "__main__":
    t0 = time.perf_counter()
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
    from chipbench import harness

    with planted(sys.argv[1]):
        sys.exit(harness.main(sys.argv[2:], t0))
