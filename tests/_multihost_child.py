"""Child program for the real two-process ``jax.distributed`` test.

Each of two OS processes runs this same script (SPMD, exactly how the
reference's ``mpirun -np N`` launches ``train_mpi.py`` —
/root/reference/README.md:62-65, train_mpi.py:237-241): wire the PJRT
coordination service over a localhost coordinator, build the *global* worker
mesh spanning both processes' CPU devices, run a short gossip chain through
the folded shard_map backend, and verify this process's addressable shards
against the dense ``W_t`` chain oracle computed locally in numpy.

Usage: python _multihost_child.py <coordinator> <num_procs> <process_id> \
           [devices_per_proc] [steps]

``devices_per_proc``/``steps`` default to the full-size configuration
(4 devices, 3 steps); the tier-1 bounded smoke passes 2/2 to keep the
whole two-process round under its 60 s budget on a 1-core host.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    coordinator, num_procs, proc_id = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    devices_per_proc = int(sys.argv[4]) if len(sys.argv) > 4 else 4
    steps = int(sys.argv[5]) if len(sys.argv) > 5 else 3

    import jax

    # virtual CPU devices, pinned before the backend initializes
    # (tests/conftest.py does the same for the parent suite)
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", devices_per_proc)

    from matcha_tpu.parallel import initialize_multihost

    assert initialize_multihost(coordinator, num_processes=num_procs,
                                process_id=proc_id) is True
    assert jax.process_count() == num_procs, jax.process_count()
    # global view on every process
    assert len(jax.devices()) == num_procs * devices_per_proc

    import numpy as np

    from matcha_tpu import topology as tp
    from matcha_tpu.communicator import make_decen
    from matcha_tpu.parallel import global_worker_mesh
    from matcha_tpu.schedule import matcha_schedule

    n, d = 8, 37
    sched = matcha_schedule(tp.select_graph(5), n, iterations=steps,
                            budget=0.5, seed=4)
    x0 = np.random.default_rng(0).normal(size=(n, d)).astype(np.float32)

    mesh = global_worker_mesh()
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P("workers", None))
    x = jax.make_array_from_callback(x0.shape, sharding, lambda idx: x0[idx])

    comm = make_decen(sched, mesh=mesh, backend="shard_map")
    flags = np.asarray(sched.flags, np.float32)
    out, _ = jax.jit(comm.run)(x, flags)

    # single-process oracle: the dense mixing chain, identical on every host
    want = x0.copy()
    for t in range(steps):
        want = (sched.mixing_matrix_at(t) @ want).astype(np.float32)

    for shard in out.addressable_shards:
        np.testing.assert_allclose(
            np.asarray(shard.data), want[shard.index], rtol=1e-5, atol=1e-6)
    print(f"proc {proc_id}: {len(out.addressable_shards)} shards verified")
    return 0


if __name__ == "__main__":
    sys.exit(main())
