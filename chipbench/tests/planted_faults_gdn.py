"""The faults that the linear-attention cell's limits were set against,
planted in the program alone (the reference never sees them), so that its
controls can be run again through ``check.compare`` at either size:

    python3 chipbench/tests/planted_faults_gdn.py <fault> --workload \\
        qwen3-next-80b-a3b.ep64-s8k.w2-matcha --seed <n> --seconds 45 \\
        --trace 0

is one whole benchmark run on the chip with ``<fault>`` planted (``correct``
has to read false; ``--rehearse-on-cpu`` walks it tiny), and
``test_gdn_cell_faults.py`` rehearses every one on the CPU.  ``sound``
plants nothing.  The job file's ``limits_why`` and PERF.md section 6 have
the readings (PR 35)."""

import contextlib
import dataclasses
import sys
import time
from pathlib import Path

FAULTS = ("bf16_wire", "no_exchange", "state_not_reset", "conv_leaks",
          "beta_left_out", "decay_left_out", "l2norm_left_out",
          "output_gate_left_out", "rope_whole_head",
          "shared_gate_left_out", "fewer_experts_a_token",
          "bf16_chunk_products")


@contextlib.contextmanager
def planted(fault):
    """``fault`` in the program for the length of the block: a field of the
    ``TrainConfig`` the harness builds (the job file, which the reference
    reads, stays as it is), or a function of the model swapped."""
    import jax.numpy as jnp

    from chipbench import harness
    from matcha_tpu.models import qwen3_next

    names = ("_gated_delta_rule", "_causal_conv", "_l2norm", "_attn_project",
             "_shared_expert", "_exact")
    build = harness.build_train_config
    real = {name: getattr(qwen3_next, name) for name in names}

    def program_only(change):
        harness.build_train_config = lambda job, workdir, data: change(
            build(job, workdir, data))

    def with_sizes(**changed):
        def change(tc):
            kwargs = dict(tc.model_kwargs)
            kwargs["sizes"] = dict(kwargs["sizes"], **{
                k: f(kwargs["sizes"]) for k, f in changed.items()})
            return dataclasses.replace(tc, model_kwargs=kwargs)
        program_only(change)

    def recurrence(change):
        """The recurrence with ``change(beta, g, docs)`` for its inputs."""
        def rule(q, k, v, beta, g, docs, chunk, again=lambda f: f):
            return real["_gated_delta_rule"](
                q, k, v, *change(beta, g, docs), chunk, again)
        qwen3_next._gated_delta_rule = rule

    if fault == "bf16_wire":  # the precision below the stated float32 wire
        program_only(lambda tc: dataclasses.replace(tc, wire_dtype="bf16"))
    elif fault == "no_exchange":
        program_only(lambda tc: dataclasses.replace(tc, communicator="none"))
    elif fault == "state_not_reset":  # the state lives through a row
        recurrence(lambda beta, g, docs: (beta, g, jnp.zeros_like(docs)))
    elif fault == "conv_leaks":  # taps reach into the document before
        qwen3_next._causal_conv = lambda x, taps, docs: real["_causal_conv"](
            x, taps, jnp.zeros_like(docs))
    elif fault == "beta_left_out":  # every write at full strength
        recurrence(lambda beta, g, docs: (jnp.ones_like(beta), g, docs))
    elif fault == "decay_left_out":  # g = 0: the state never fades
        recurrence(lambda beta, g, docs: (beta, jnp.zeros_like(g), docs))
    elif fault == "l2norm_left_out":  # q and k as the convolution left them
        qwen3_next._l2norm = lambda x: x
    elif fault == "output_gate_left_out":  # sigmoid(gate) = 1
        def ungated(p, h, sizes):
            q, k, v, gate = real["_attn_project"](p, h, sizes)
            return q, k, v, jnp.full_like(gate, 1e4)
        qwen3_next._attn_project = ungated
    elif fault == "rope_whole_head":  # all of a head's dimensions turn
        with_sizes(rotary_dim=lambda z: z["head_dim"])
    elif fault == "shared_gate_left_out":  # the shared expert at weight 1
        qwen3_next._shared_expert = lambda p, x: qwen3_next._swiglu(
            x, {"gate": p["shared_gate"], "up": p["shared_up"],
                "down": p["shared_down"]}, jnp.dot)
    elif fault == "fewer_experts_a_token":  # top-8 for top-10 (2 for 3)
        with_sizes(experts_per_token=lambda z: z["experts_per_token"] * 4 // 5)
    elif fault == "bf16_chunk_products":  # one bfloat16 pass where
        # ``highest`` is stated: the chunks' systems and the carried state
        qwen3_next._exact = lambda spec, *operands: jnp.einsum(
            spec, *(o.astype(jnp.bfloat16) for o in operands),
            preferred_element_type=jnp.float32)
    elif fault != "sound":
        raise SystemExit(f"unknown fault {fault!r}: sound or one of {FAULTS}")
    try:
        yield
    finally:
        harness.build_train_config = build
        for name, thing in real.items():
            setattr(qwen3_next, name, thing)


if __name__ == "__main__":
    t0 = time.perf_counter()
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
    from chipbench import harness

    with planted(sys.argv[1]):
        sys.exit(harness.main(sys.argv[2:], t0))
