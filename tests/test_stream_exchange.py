"""The one-chip dense exchange chooses its form from the worker count (PR 28).

* **Parity** — at N in {2, 3, 8, 16} the streamed form (one vector-unit pass,
  ``pallas_gossip.stream_mix``) reads what the float64 oracle ``W @ x`` and
  the MXU product read, masked or not, at float32 and bf16 wire, through
  ``step``, ``begin_mix``/``apply_mix`` and ``Communicator.run``; one N above
  the crossover takes the product and reads the same.
* **What the exchange keeps, on both sides of the crossover** (N = 16
  streamed, N = ``ABOVE`` the product; PR 29) — the worker mean, a doubly
  stochastic realized ``W_t`` over survivors under any ``alive`` mask, the
  state bitwise through an empty chain or an all-zero flag row, and one
  compiled step for every ``alive`` value and flag row.
* **The decision record** — ``resolve_gossip_backend`` answers by itself
  (explicit: as asked; ``auto``: ``shard_map`` on several devices, ``dense``
  on one chip), the three cells' job files resolve to the form ISSUE 28
  names (2 and 16 streamed, 128 the product), and the ``backend`` event
  that ``train()`` journals carries it.
* **Lowering** — the streamed exchange lowers with no ``dot_general`` over
  the state; above the crossover ``dense`` lowers to the text of the product
  it was before.  (That the kernel compiles for a described v5e at the
  cells' shapes, in place, is a case of ``tests/test_pallas.py``, in its
  child process: libtpu loaded here would hang a TPU plane on every later
  profiler trace of this worker.)
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from matcha_tpu import topology as tp
from matcha_tpu.analysis import check_single_trace, retrace_guard
from matcha_tpu.communicator import make_decen
from matcha_tpu.communicator.decen import resolve_gossip_backend
from matcha_tpu.parallel import (STREAM_MAX_WORKERS, dense_exchange_form,
                                 dense_gossip_fn, masked_laplacians,
                                 stream_mix)
from matcha_tpu.schedule import matcha_schedule
from matcha_tpu.train import TrainConfig, train
from matcha_tpu.train.loop import build_schedule

ABOVE = STREAM_MAX_WORKERS + 8
SIZES = (2, 3, 8, 16, ABOVE)
STEPS = 6
DIM = 20000  # N = 2 walks two 8,192-column chunks and a ragged third


def _schedule(n):
    topology = "chain" if n < 4 else "ring"
    decomposed = tp.decompose(tp.make_graph(topology, n, seed=0), n, seed=0)
    return matcha_schedule(decomposed, n, iterations=STEPS, budget=0.7, seed=5)


def _alive(n):
    alive = np.ones(n, np.float32)
    alive[n // 2] = 0.0
    return alive


def _oracle(sched, x, alive, wire, steps):
    """The float64 chain ``x <- W_t x`` with the wire's rounding of both
    operands, as the exchange states it."""
    L = np.asarray(sched.laplacians(), np.float64)
    if alive is not None:
        L = np.asarray(masked_laplacians(jnp.asarray(L, jnp.float32),
                                         jnp.asarray(alive)), np.float64)
    x = np.asarray(x, np.float64)
    n = x.shape[0]

    def rounded(a):
        if wire is None:
            return a
        return np.asarray(jnp.asarray(a, jnp.float32).astype(jnp.bfloat16)
                          .astype(jnp.float32), np.float64)

    for t in range(steps):
        w = float(sched.alpha) * np.asarray(sched.flags[t], np.float64)
        W = np.eye(n) - np.tensordot(w, L, axes=1)
        x = rounded(np.asarray(W, np.float32)) @ rounded(x)
    return x


def _through(comm, path, x, flags, alive):
    """The exchange through one of its three entry points, jitted."""
    a = None if alive is None else jnp.asarray(alive)
    if path == "step":
        fn = jax.jit(lambda x: comm.step(x, (), flags[0], a)[0])
    elif path == "begin_apply":
        def fn(x):
            delta, _ = comm.begin_mix(x, (), flags[0], a)
            return comm.apply_mix(x, delta)
        fn = jax.jit(fn)
    else:
        fn = jax.jit(lambda x: comm.run(x, flags, alive=a)[0])
    return np.asarray(fn(x)), (1 if path != "run" else STEPS)


@pytest.mark.parametrize("path", ["step", "begin_apply", "run"])
@pytest.mark.parametrize("wire", [None, "bf16"], ids=["f32", "bf16"])
@pytest.mark.parametrize("masked", [False, True], ids=["full", "alive"])
@pytest.mark.parametrize("n", SIZES)
def test_form_reads_what_the_oracle_and_the_product_read(n, masked, wire, path):
    sched = _schedule(n)
    form = dense_exchange_form(n)["form"]
    assert form == ("streamed" if n <= STREAM_MAX_WORKERS else "mxu")
    x = jnp.asarray(np.random.default_rng(n).normal(size=(n, DIM)),
                    jnp.float32)
    flags = jnp.asarray(sched.flags[:STEPS], jnp.float32)
    alive = _alive(n) if masked else None

    comm = make_decen(sched, backend="dense", wire_dtype=wire)
    got, steps = _through(comm, path, x, flags, alive)
    want = _oracle(sched, x, alive, wire, steps)
    # the product, at every N: the form a mesh keeps (single_chip=False)
    compute = jnp.float32 if wire is None else jnp.bfloat16
    product = dense_gossip_fn(sched.laplacians(), compute_dtype=compute,
                              single_chip=False)
    mxu = jax.jit(lambda x: product(
        x, float(sched.alpha) * flags[0],
        None if alive is None else jnp.asarray(alive)))(x)

    # float32 sums of N terms; under a bf16 wire a chain re-rounds a state
    # that differs in its last float32 bit, so a rounding boundary can flip
    tol = 2e-6 if wire is None else (2e-6 if steps == 1 else 2e-2)
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * (1 + np.abs(want).max()))
    if steps == 1:
        np.testing.assert_allclose(got, np.asarray(mxu), rtol=0, atol=2e-6)
    if masked:
        # a dead worker's row is a self-loop: the exchange leaves it alone
        dead = n // 2
        kept = x[dead] if wire is None else \
            x[dead].astype(jnp.bfloat16).astype(jnp.float32)
        np.testing.assert_allclose(got[dead], np.asarray(kept), rtol=0,
                                   atol=1e-6 if steps == 1 else 1e-1)


@pytest.mark.parametrize("n", [2, 16])
def test_streamed_blocks_ragged_or_not_never_mix_columns(n):
    """One chunk, a whole number of chunks, a ragged last chunk, and a D
    under one chunk: columns never mix, whatever the block holds past D."""
    rng = np.random.default_rng(0)
    w = rng.normal(size=(n, n)).astype(np.float32)
    for d in (7, 2048, 8192, 20000):
        x = rng.normal(size=(n, d)).astype(np.float32)
        got = jax.jit(lambda w, x: stream_mix(x, w, interpret=True))(w, x)
        np.testing.assert_allclose(
            np.asarray(got), w.astype(np.float64) @ x.astype(np.float64),
            rtol=0, atol=1e-5)
    with pytest.raises(ValueError, match="mixing matrix"):
        stream_mix(jnp.zeros((n, 8)), jnp.zeros((n + 1, n + 1)),
                   interpret=True)


# ------------------------------------- what the exchange keeps, at both forms

BOTH_FORMS = [16, ABOVE]


@pytest.mark.parametrize("wire", [None, "bf16"], ids=["f32", "bf16"])
@pytest.mark.parametrize("n", BOTH_FORMS)
def test_realized_mixing_is_doubly_stochastic_over_survivors(n, wire):
    """Under three drawn ``alive`` masks every step's realized ``W_t`` (the
    exchange of the identity) has unit row and column sums and is
    symmetric, a dead worker's row and column are a self-loop, and the
    worker mean of a random state is kept: mass moves between survivors,
    never appears or disappears."""
    sched = _schedule(n)
    comm = make_decen(sched, backend="dense", wire_dtype=wire)
    step = jax.jit(lambda x, f, a: comm.step(x, (), f, a)[0])
    rng = np.random.default_rng(7)
    eye = jnp.eye(n, dtype=jnp.float32)
    # a bf16 wire rounds W_t and the state once each: 2^-9 an entry
    tol = 2e-6 if wire is None else n * 2.0 ** -9
    for trial in range(3):
        alive = (rng.random(n) > rng.uniform(0.1, 0.6)).astype(np.float32)
        dead = np.flatnonzero(alive == 0)
        x = jnp.asarray(rng.normal(size=(n, 40)), jnp.float32)
        for t in range(STEPS):
            flags_t = jnp.asarray(sched.flags[t], jnp.float32)
            W = np.asarray(step(eye, flags_t, jnp.asarray(alive)))
            np.testing.assert_allclose(W.sum(0), 1.0, rtol=0, atol=tol)
            np.testing.assert_allclose(W.sum(1), 1.0, rtol=0, atol=tol)
            np.testing.assert_allclose(W, W.T, rtol=0, atol=1e-7)
            np.testing.assert_array_equal(W[dead], np.eye(n)[dead])
            out = np.asarray(step(x, flags_t, jnp.asarray(alive)))
            np.testing.assert_allclose(
                out.mean(0), np.asarray(x).mean(0), rtol=0,
                atol=tol * float(jnp.abs(x).max()))
            if wire is None:  # a self-loop leaves the row alone, bitwise
                np.testing.assert_array_equal(out[dead], np.asarray(x)[dead])


@pytest.mark.parametrize("path", ["step", "run"])
@pytest.mark.parametrize("n", BOTH_FORMS)
def test_empty_chain_and_zero_flags_return_the_state_bitwise(n, path):
    """No flag fired: ``W_t`` is the identity and the float32 state comes
    back bitwise (float32 wire: nothing is rounded on the way) — through
    ``step`` on an all-zero row, through ``run`` on a ``T = 0`` chain and on
    a chain of all-zero rows, masked or not."""
    sched = _schedule(n)
    comm = make_decen(sched, backend="dense")
    x = jnp.asarray(np.random.default_rng(n).normal(size=(n, 300)),
                    jnp.float32)
    m = sched.num_matchings
    if path == "step":
        out = jax.jit(lambda x, f: comm.step(x, (), f)[0])(
            x, jnp.zeros((m,), jnp.float32))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
        return
    for steps in (0, 3):
        flags = np.zeros((steps, m), np.float32)
        out, _ = jax.jit(lambda x: comm.run(x, flags))(x)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
        out, _ = jax.jit(lambda x, a: comm.run(x, flags, alive=a))(
            x, jnp.asarray(_alive(n)))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


@pytest.mark.parametrize("n", BOTH_FORMS)
def test_one_compiled_step_serves_every_alive_mask_and_flag_row(n):
    """``alive`` and the flag row are traced inputs of the exchange:
    membership churn and the schedule's next row never compile a second
    program."""
    sched = _schedule(n)
    comm = make_decen(sched, backend="dense")
    guarded, counter = retrace_guard(
        jax.jit(lambda x, f, a: comm.step(x, (), f, a)[0]))
    x = jnp.asarray(np.random.default_rng(1).normal(size=(n, 64)),
                    jnp.float32)
    other = np.ones(n, np.float32)
    other[[0, n - 1]] = 0.0
    every = np.ones(sched.num_matchings, np.float32)
    first = np.zeros_like(every)
    first[0] = 1.0
    outs = [np.asarray(guarded(x, jnp.asarray(flags_t), jnp.asarray(alive)))
            for flags_t, alive in ((every, _alive(n)), (every, other),
                                   (first, other))]
    check_single_trace(counter, label=f"dense_step_n{n}")
    assert counter.count == 1
    # each call mixed with its own mask and row, not the first call's
    assert not np.array_equal(outs[0], outs[1])
    assert not np.array_equal(outs[1], outs[2])


# --------------------------------------------------------- the decision record

class _Mesh:
    def __init__(self, size):
        self.size = size


@pytest.mark.parametrize("requested", ["auto", "dense"])
@pytest.mark.parametrize("devices", [None, 1, 4],
                         ids=["no-mesh", "one-device", "four-devices"])
def test_resolve_gossip_backend_table(devices, requested):
    """Explicit: as asked.  ``auto``: ``shard_map`` on several devices,
    ``dense`` on one chip.  The record is the journal's pinned triple and,
    where the backend is ``dense``, asked for by name or not, the form it
    compiles to; nothing else."""
    sched = _schedule(16)
    single = devices in (None, 1)
    record = resolve_gossip_backend(
        sched, None if devices is None else _Mesh(devices),
        requested=requested)
    chosen = requested if requested != "auto" else \
        ("dense" if single else "shard_map")
    assert (record["requested"], record["chosen"]) == (requested, chosen)
    assert record["reason"]
    if chosen == "shard_map":
        assert set(record) == {"requested", "chosen", "reason"}
    else:
        assert set(record) == {"requested", "chosen", "reason", "exchange"}
        assert record["exchange"] == dense_exchange_form(16, single)
        assert record["exchange"]["form"] == \
            ("streamed" if single else "mxu")


CELLS = {"wrn28-10-c100.w16-matcha": (16, "streamed"),
         "mellum2-12b-a2.5b.ep8-s4k.w2-matcha": (2, "streamed"),
         "resnet20-c10.w128-matcha": (128, "mxu")}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_job_files_resolve_to_the_form_the_issue_names(cell):
    root = Path(__file__).resolve().parents[1] / "chipbench" / "workloads"
    fields = json.loads((root / f"{cell}.json").read_text())["train_config"]
    n, form = CELLS[cell]
    assert fields["num_workers"] == n and fields["gossip_backend"] == "auto"

    class Sched:  # what the resolver reads of a schedule
        num_workers, num_matchings, probs, name = n, 3, [0.5] * 3, None

    record = resolve_gossip_backend(Sched, None, requested="auto")
    assert record["chosen"] == "dense"
    assert record["exchange"] == {"form": form, "n": n, "single_chip": True,
                                  "crossover": STREAM_MAX_WORKERS}


@pytest.mark.parametrize("backend,form", [("auto", "streamed"),
                                          ("dense", "streamed"),
                                          ("gather", None)])
def test_backend_event_names_the_form_that_compiled(tmp_path, backend, form):
    config = TrainConfig(
        name="form", model="mlp", dataset="synthetic", num_workers=8,
        graphid=0, batch_size=8, epochs=1, lr=0.05, warmup=False,
        communicator="decen", gossip_backend=backend, devices=1, save=False,
        savePath=str(tmp_path), measure_comm_split=False, eval_every=0)
    result = train(config)
    events = [e for e in result.recorder.events if e["kind"] == "backend"]
    assert len(events) == 1
    if form is None:
        assert "exchange" not in events[0]
    else:
        # the form, and since PR 34 where the step runs it: this model's
        # leaves are under the shape rule's size, so on the flat state, which
        # holds the streamed pass's one kernel
        assert events[0]["exchange"] == {
            "form": form, "n": 8, "single_chip": True,
            "crossover": STREAM_MAX_WORKERS, "layout": "flat",
            "kernel_sites": 1, "leaves_in_place": 0,
            "small_buffer_elements": 0,
            "reason": "no leaf passes the shape rule"}
    # a mesh keeps the product at every N
    sched = build_schedule(config, 4)

    class Mesh:
        size = 4

    assert resolve_gossip_backend(sched, Mesh, requested="dense")[
        "exchange"]["form"] == "mxu"


def test_train_journal_carries_backend_decision(tmp_path):
    """An auto run journals its backend choice as a `backend` event that
    the journal's schema validates, read back from the file."""
    from matcha_tpu.obs.journal import read_journal, validate_event

    train(TrainConfig(
        name="auto", model="mlp", dataset="synthetic",
        dataset_kwargs={"num_train": 64, "num_test": 32},
        num_workers=4, devices=1, graphid=None, topology="ring", batch_size=8,
        epochs=1, lr=0.05, warmup=False, eval_every=1,
        measure_comm_split=False, save=True, savePath=str(tmp_path),
        health=False))
    events = read_journal(str(tmp_path / "auto_mlp" / "events.jsonl"))
    backend_events = [e for e in events if e["kind"] == "backend"]
    assert len(backend_events) == 1
    e = backend_events[0]
    assert validate_event(e) == []
    assert e["requested"] == "auto" and e["chosen"] == "dense"
    assert "reason" in e and e["exchange"]["form"] == "streamed"


def _step_text(comm, sched, masked=False):
    n = sched.num_workers
    x = jax.ShapeDtypeStruct((n, 4096), jnp.float32)
    flags = jax.ShapeDtypeStruct((sched.num_matchings,), jnp.float32)
    if masked:
        return jax.jit(lambda x, f, a: comm.step(x, (), f, a)[0]).lower(
            x, flags, jax.ShapeDtypeStruct((n,), jnp.float32)).as_text()
    return jax.jit(lambda x, f: comm.step(x, (), f)[0]).lower(
        x, flags).as_text()


@pytest.mark.parametrize("masked", [False, True], ids=["full", "alive"])
@pytest.mark.parametrize("n", [2, 16, ABOVE])
def test_streamed_exchange_lowers_with_no_product_over_the_state(n, masked):
    """W_t is still built with contractions over ``[M, N, N]``; the state's
    4,096 columns meet a ``dot_general`` only above the crossover."""
    sched = _schedule(n)
    text = _step_text(make_decen(sched, backend="dense"), sched, masked)
    over_state = [line for line in text.splitlines()
                  if "dot_general" in line and "x4096x" in line]
    assert bool(over_state) == (n > STREAM_MAX_WORKERS)


def test_above_the_crossover_dense_lowers_to_the_product_it_was():
    """Cell 2's N: ``dense`` is the ``[N, N] x [N, D]`` product at
    ``highest``, to the text (the expression as it stood before PR 28)."""
    n = 128
    decomposed = tp.decompose(tp.make_graph("geometric", n, seed=9001), n,
                              seed=9001)
    sched = matcha_schedule(decomposed, n, iterations=4, budget=0.5, seed=9001)
    L = jnp.asarray(np.asarray(sched.laplacians()), jnp.float32)
    alpha = float(sched.alpha)

    def before(x, flags_t):
        weights = alpha * flags_t
        W = jnp.eye(n, dtype=jnp.float32) - jnp.tensordot(weights, L, axes=1)
        out = lax.dot(W.astype(jnp.float32), x.astype(jnp.float32),
                      precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
        return out.astype(x.dtype)

    x = jax.ShapeDtypeStruct((n, 4096), jnp.float32)
    flags = jax.ShapeDtypeStruct((sched.num_matchings,), jnp.float32)
    want = jax.jit(before).lower(x, flags).as_text()
    comm = make_decen(sched, backend="dense")
    got = jax.jit(lambda x, f: comm.step(x, (), f)[0]).lower(x, flags).as_text()
    assert "dot_general" in got
    # (the first line names the jitted function)
    assert got.split("\n", 1)[1] == want.split("\n", 1)[1]
