"""The bench contract: ``python bench.py`` measures in-process, names the
device in every record, prints each refinement as a superset of the record
before it (the last JSON line is the most complete), and refuses to print
any metric when there is no TPU and the CPU was not asked for by name.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_without_tpu_exits_nonzero_and_prints_no_metric():
    """No accelerator and no explicit CPU request: non-zero exit, nothing
    on stdout — a CPU timing never appears under a device metric's name."""
    proc = subprocess.run(
        [sys.executable, "bench.py", "--steps", "8"],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout[-500:]
    assert "no TPU" in proc.stderr


@pytest.mark.slow
def test_elision_grid_cells_shape_and_byte_monotonicity():
    """The universal-elision grid (ISSUE 19) emits one cell per backend ×
    local_every with a measured rate and the ledger's per-epoch gossip
    bytes, and every backend's L=4 bytes are strictly below its L=1
    bytes — the measured A/B the elision claim ships with."""
    sys.path.insert(0, REPO)
    try:
        import jax.numpy as jnp
        import numpy as np

        from bench import elision_grid
        from matcha_tpu import topology as tp
        from matcha_tpu.schedule import matcha_schedule

        n = tp.graph_size(0)
        sched = matcha_schedule(tp.select_graph(0), n, iterations=24,
                                budget=0.5, seed=3)
        x = jnp.asarray(np.random.default_rng(0)
                        .normal(size=(n, 64)).astype(np.float32))
        cells = elision_grid(sched, x, 24, n, 64, reps=1)
    finally:
        sys.path.remove(REPO)
    assert [(c["backend"], c["local_every"]) for c in cells] == [
        ("skip", 1), ("skip", 4), ("dense", 1), ("dense", 4)]
    by_key = {(c["backend"], c["local_every"]): c for c in cells}
    for c in cells:
        assert c["unit"] == "gossip_steps_per_sec" and c["value"] > 0
        assert c["hbm_bytes_per_epoch"] > 0
    for backend in ("skip", "dense"):
        l1 = by_key[(backend, 1)]
        l4 = by_key[(backend, 4)]
        assert l4["hbm_bytes_per_epoch"] < l1["hbm_bytes_per_epoch"]
        assert l4["exec_steps"] == 6 and l1["exec_steps"] == 24


def test_bench_emits_refinements_last_line_wins():
    """The bench prints the pre-sweep record, the swept record, and the
    chunked-augmented record in order; a reader keeps the LAST complete
    line, so each refinement must be a superset-compatible record — and
    every one names the device it ran on."""
    proc = subprocess.run(
        [sys.executable, "bench.py", "--smoke", "--platform", "cpu",
         "--chunk", "4", "--steps", "50"],
        capture_output=True, text=True, timeout=420, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()
             if l.startswith("{")]
    assert len(lines) >= 2  # at least pre-sweep + final
    final = lines[-1]
    assert final["chunk"] == 1  # per-step primary is the headline
    assert "value_chunked" in final  # secondary rides the same record
    for rec in lines:  # every refinement is independently parseable
        for key in ("metric", "value", "unit", "vs_baseline"):
            assert key in rec
        assert rec["device"]["platform"] == "cpu"
        assert "mfu" not in rec  # no peaks on the CPU: no utilization


def test_compile_cache_placed_by_env_else_fixed_in_tree(monkeypatch):
    """The one compile-cache seam every entry point passes: with
    JAX_COMPILATION_CACHE_DIR set, nothing sets a cache directory in code
    (JAX reads the variable itself); unset, it is <checkout>/.jax_cache —
    the same path from any process and any working directory."""
    import jax

    from matcha_tpu.utils import compile_cache_dir, pin_platform

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.append((key, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    pin_platform(None)
    assert "jax_compilation_cache_dir" not in dict(calls)
    assert compile_cache_dir() == "/placed/from/outside"

    calls.clear()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    pin_platform(None)
    in_tree = os.path.join(REPO, ".jax_cache")
    assert dict(calls)["jax_compilation_cache_dir"] == in_tree
    assert "jax_platforms" not in dict(calls)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    other = subprocess.run(
        [sys.executable, "-c",
         "from matcha_tpu.utils import compile_cache_dir; "
         "print(compile_cache_dir())"],
        capture_output=True, text=True, timeout=120, cwd="/",
        env={**env, "PYTHONPATH": REPO})
    assert other.stdout.strip() == in_tree, other.stderr[-500:]
