"""Test harness: run JAX on 8 virtual CPU devices so shard_map/ppermute
semantics are exercised without a TPU pod (SURVEY.md §4).

Pinned through ``jax.config`` — backends initialize lazily, so this holds
whatever ``JAX_PLATFORMS`` says and however early jax was imported.

The driver runs the suite as six workers on one machine
(``-n 6 --dist loadfile``), so a worker takes its share of it: one BLAS
thread a process, one compile cache a session, the long files first
(ROADMAP D11; ``tests/test_harness.py`` holds the first and the last)."""

import os
import shutil
import tempfile

os.environ.setdefault("JAX_ENABLE_X64", "0")

#: BLAS/OpenMP threads of a test process and of every child it starts.  The
#: driver runs six workers on one machine, and an OpenBLAS pool of a thread a
#: core in each of them spins while it waits: ``test_schedule``'s solvers at
#: 256 workers took 24 s on eight threads and 1.8 s on one (ROADMAP D11).
BLAS_THREADS = 1
for _name in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS", "MKL_NUM_THREADS"):
    # read when a library is loaded: the children, and SciPy's own OpenBLAS
    os.environ[_name] = str(BLAS_THREADS)

import threadpoolctl

# NumPy is loaded already (an entry-point plugin imports it before this file)
# and its OpenBLAS has read the variables as they were: cap the pools in place
threadpoolctl.threadpool_limits(BLAS_THREADS)

import jax
import pytest

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

#: ``--dist loadfile`` hands a worker a whole file, and xdist starts the files
#: with the most cases first: the files of few and long cases ran last, and
#: the last of them alone on an idle machine (ROADMAP D11).  The files that
#: took over 70 s of case time inside the pool (junit, PR 40) start first,
#: longest first; every other file follows in collection order.
LONGEST_FIRST = (
    "test_chipbench_gdn_faults", "test_chipbench_dsa_faults",
    "test_leaf_cells", "test_train", "test_packed_fwd_bwd", "test_pallas",
    "test_expert_dispatch",
    "test_qwen3_next", "test_keye_vl2", "test_keye_vl2_tiles",
    "test_chipbench_bd_faults",
    "test_leaf_exchange", "test_chipbench_token_faults",
    "test_chipbench_cells_more", "test_leaf_step", "test_stream_exchange",
    "test_mellum2", "test_chipbench_cells", "test_models",
)


def pytest_configure(config):
    # g++ builds the native library at its first use, and six workers that
    # import ``test_native`` at once each write the file another is loading
    # (43 cases skipped, "no native lib"): built here, in the controller,
    # before it starts the workers, who then find it built
    from matcha_tpu.native.build import build_native

    build_native()
    # xdist's own order, by a file's number of cases, would undo this one
    config.option.loadscopereorder = False
    # ``train()`` jits the programs that close over its configuration (the
    # epoch scan, evaluation: the costly ones) anew at every call, so
    # ``jit``'s own cache never sees one twice, and what one worker has
    # compiled the other five compile again (a first ``train()`` in a
    # process: some sixty programs).  An entry point has ``pin_platform``'s
    # persistent compile cache for that; a test session gets one too: one
    # directory for its lifetime, which the pool's workers and the children
    # a test starts inherit through the variable JAX reads
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        cache = tempfile.mkdtemp(prefix="tier1-jax-cache-")
        config.add_cleanup(lambda: shutil.rmtree(cache, ignore_errors=True))
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
        # (this process's jax has read its environment already)
        jax.config.update("jax_compilation_cache_dir", cache)
    # every program, however quick its compile, as ``pin_platform`` has it
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def pytest_collection_modifyitems(items):
    rank = {name: at for at, name in enumerate(LONGEST_FIRST)}
    items.sort(key=lambda item: rank.get(item.path.stem, len(rank)))


@pytest.fixture
def small_leaves(monkeypatch):
    """The leaves route's shape rule with its size and padding constants
    opened up, so that the leaves of a test-sized model pass it (what a
    shape must hold of the tree, ``_LEAF_SHAPE_SHARE``, stays)."""
    from matcha_tpu.parallel import pallas_gossip

    monkeypatch.setattr(pallas_gossip, "_LEAF_MIN_ELEMENTS", 1)
    monkeypatch.setattr(pallas_gossip, "_LEAF_PAD_SHARE", 1e9)
