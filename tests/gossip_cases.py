"""The backend columns that the overlap and the staleness matrices share
(``tests/test_overlap.py``, ``tests/test_staleness.py``).

``dense`` on the eight workers of graph 0 is the streamed form of the
one-chip exchange; ``dense-mxu`` is the same backend on a ring wide enough
that ``gossip_mix_dense`` takes the MXU product (the form cell 2 trains
on)."""

import numpy as np

from matcha_tpu import topology as tp
from matcha_tpu.communicator import make_centralized, make_choco, make_decen
from matcha_tpu.parallel import STREAM_MAX_WORKERS
from matcha_tpu.schedule import matcha_schedule

MXU_SIZE = STREAM_MAX_WORKERS + 8
MXU_SCHED = matcha_schedule(
    tp.decompose(tp.ring_graph(MXU_SIZE), MXU_SIZE, seed=0), MXU_SIZE,
    iterations=12, budget=0.5, seed=3)


def sched_of(backend, small):
    """The schedule ``backend`` runs on: ``small`` but for ``dense-mxu``."""
    return MXU_SCHED if backend == "dense-mxu" else small


def make_comm(backend, small, wire=None):
    if backend == "choco":
        return make_choco(small, ratio=0.5, consensus_lr=0.3, wire_dtype=wire)
    if backend == "centralized":
        return make_centralized(wire_dtype=wire)
    return make_decen(sched_of(backend, small), backend=backend.split("-")[0],
                      wire_dtype=wire)


def alive_of(sched):
    """Worker 2 dead, at the schedule's own worker count."""
    alive = np.ones(sched.num_workers, np.float32)
    alive[2] = 0.0
    return alive
