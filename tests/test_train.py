"""End-to-end training tests (SURVEY.md §4 'End-to-end'): tiny MLP on
synthetic data, 8 virtual workers — loss decreases AND replicas converge."""

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from matcha_tpu.train import (
    TrainConfig,
    build_schedule,
    make_lr_schedule,
    train,
)


BASE = TrainConfig(
    name="t",
    model="mlp",
    dataset="synthetic",
    num_workers=8,
    graphid=5,  # 8-node ring
    batch_size=16,
    epochs=3,
    lr=0.1,
    warmup=False,
    momentum=0.9,
    matcha=True,
    budget=0.5,
    seed=3,
    save=False,
    eval_every=1,
    # the comp/comm split costs one extra jit per train() call — measured in
    # its own dedicated test below, off everywhere else to keep CI fast
    measure_comm_split=False,
)


# --------------------------------------------------------------- lr schedule

def test_lr_schedule_warmup_and_decay():
    s = make_lr_schedule(0.8, batches_per_epoch=10, base_lr=0.1, warmup=True,
                         warmup_epochs=5, decay_epochs=(100, 150))
    assert float(s(0)) == pytest.approx(0.1)
    assert float(s(25)) == pytest.approx(0.1 + (0.8 - 0.1) * 25 / 50)
    assert float(s(50)) == pytest.approx(0.8)
    assert float(s(999)) == pytest.approx(0.8)
    assert float(s(100 * 10)) == pytest.approx(0.08)
    assert float(s(150 * 10)) == pytest.approx(0.008)


def test_lr_schedule_no_warmup_when_target_below_base():
    # reference: warmup only applies if target > base (train_mpi.py:184-191)
    s = make_lr_schedule(0.05, batches_per_epoch=10, base_lr=0.1, warmup=True)
    assert float(s(0)) == pytest.approx(0.05)
    assert float(s(100)) == pytest.approx(0.05)


# --------------------------------------------------------------- e2e training

def test_train_matcha_mlp_loss_decreases_and_consensus():
    result = train(BASE)
    hist = result.history
    assert len(hist) == 3
    assert hist[-1]["loss"] < hist[0]["loss"] * 0.7
    assert hist[-1]["test_acc_mean"] > 0.5  # synthetic clusters are separable
    # replicas stay in consensus under gossip
    assert hist[-1]["disagreement"] < 0.5


def test_train_python_loop_matches_scan():
    cfg_scan = dataclasses.replace(BASE, epochs=1, scan_epoch=True)
    cfg_loop = dataclasses.replace(BASE, epochs=1, scan_epoch=False)
    a = train(cfg_scan).history[-1]
    b = train(cfg_loop).history[-1]
    assert a["loss"] == pytest.approx(b["loss"], rel=1e-4)
    assert a["test_acc_mean"] == pytest.approx(b["test_acc_mean"], abs=1e-6)


def test_train_chunked_scan_matches_whole_epoch_scan():
    """scan_chunk pipelines bounded segments instead of staging the whole
    epoch (loop.py _run_epoch_scanned); same steps in the same order, so
    params and weighted-mean metrics must match the one-scan epoch exactly.
    Chunk 3 against 8 workers x batch 16 gives a tail segment (the second
    compiled shape) as well."""
    a = train(dataclasses.replace(BASE, epochs=2)).history[-1]
    b = train(dataclasses.replace(BASE, epochs=2, scan_chunk=3)).history[-1]
    assert a["loss"] == pytest.approx(b["loss"], rel=1e-5)
    assert a["accuracy"] == pytest.approx(b["accuracy"], abs=1e-6)
    assert a["test_acc_mean"] == pytest.approx(b["test_acc_mean"], abs=1e-6)
    assert a["disagreement"] == pytest.approx(b["disagreement"], rel=1e-4, abs=1e-8)


# ------------------------------------------------ kept host stacks (ISSUE 25)

#: five steps an epoch: whole, and in segments of 2, 2 and a tail of 1
STAGING_PATHS = {"whole_epoch": None, "chunked": 2}
STAGING_STEPS, STAGING_EPOCHS = 5, 3


def _stack_afresh(self, epoch, xs_out, ys_out, first=0):
    """The staging before the stacks were kept, as the oracle: drain
    ``epoch``, ``np.stack`` (the copy into ``*_out`` is the seam's)."""
    batches = self.epoch(epoch)
    loaded = list(itertools.islice(batches, first, first + len(xs_out)))
    xs, ys = (np.stack(arrays) for arrays in zip(*loaded))
    xs_out[...], ys_out[...] = xs, ys


@pytest.fixture(scope="module", params=list(STAGING_PATHS))
def kept_and_fresh(request):
    """Three epochs of ``train()`` twice on one staging path: as shipped,
    and with every segment staged the old way into arrays allocated for it.
    (path, kept result, fresh result, the ``(address, steps)`` of every x
    stack the kept run put on the device, in order)."""
    from matcha_tpu.data import WorkerBatches
    from matcha_tpu.train import loop

    config = dataclasses.replace(
        BASE, epochs=STAGING_EPOCHS, eval_every=0,
        dataset_kwargs={"num_train": 8 * 16 * STAGING_STEPS, "num_test": 16},
        scan_chunk=STAGING_PATHS[request.param])
    put, pair, seen = loop._put_batches, loop._HostStacks.pair, []

    def watching(stack, mesh):
        if stack.ndim > 3:  # x: [steps, N, B, ...]; y is [steps, N, B]
            seen.append((stack.ctypes.data, len(stack)))
        return put(stack, mesh)

    def forgetful(self, turn, steps):
        self._pairs.clear()  # what was handed out lives on with its holder
        return pair(self, turn, steps)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(loop, "_put_batches", watching)
        kept = train(config)
        kept_seen = list(seen)
        patch.setattr(loop._HostStacks, "pair", forgetful)
        patch.setattr(WorkerBatches, "epoch_into", _stack_afresh)
        fresh = train(config)
    return request.param, kept, fresh, kept_seen


def test_train_on_kept_stacks_matches_fresh_staging_bitwise(kept_and_fresh):
    """Stacks that every epoch writes over give the very parameters that
    fresh arrays give: the same rows reach the device in every epoch, and
    no stack is written while a copy (on the CPU, an alias) of it is still
    to be read."""
    _, kept, fresh, _ = kept_and_fresh
    assert int(kept.state.step) == STAGING_STEPS * STAGING_EPOCHS
    leaves = jax.tree_util.tree_leaves
    for a, b in zip(leaves(kept.state.params), leaves(fresh.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [h["loss"] for h in kept.history] == \
        [h["loss"] for h in fresh.history]


def test_train_stages_every_epoch_into_the_same_stacks(kept_and_fresh):
    """One stack for the whole-epoch path, epoch 0 to epoch 2; exactly two
    for the chunked path, taken in turn inside an epoch (the tail is the
    head of the first)."""
    path, _, _, seen = kept_and_fresh
    addresses = [address for address, _ in seen]
    if path == "whole_epoch":
        assert [steps for _, steps in seen] == [STAGING_STEPS] * STAGING_EPOCHS
        assert len(set(addresses)) == 1
    else:
        assert [steps for _, steps in seen] == [2, 2, 1] * STAGING_EPOCHS
        a, b = addresses[:2]
        assert a != b and addresses == [a, b, a] * STAGING_EPOCHS


def test_fresh_staging_oracle_allocates_every_segment(kept_and_fresh):
    """The oracle is one: its run ends where the kept run does only
    because the rows are the same, not because it took the same path."""
    path, kept, fresh, _ = kept_and_fresh
    reused = [[s["reused"] for e in result.recorder.events
               if e["kind"] == "spans" for s in e["spans"]
               if s["name"] == "stack_batches"] for result in (kept, fresh)]
    segments = 1 if path == "whole_epoch" else 3
    assert reused[1] == [0] * segments * STAGING_EPOCHS
    assert sum(reused[0]) == segments * STAGING_EPOCHS - min(segments, 2)


@pytest.mark.parametrize("communicator", ["decen", "choco", "centralized", "none"])
def test_train_all_communicators(communicator):
    cfg = dataclasses.replace(BASE, communicator=communicator, epochs=2)
    hist = train(cfg).history
    assert hist[-1]["loss"] < hist[0]["loss"]
    if communicator == "centralized":
        assert hist[-1]["disagreement"] < 1e-4


@pytest.mark.slow  # two full CHOCO trains + 3 stage programs ≈ 2 min on the
# CPU mesh — tier-1's largest line item at a budget already at its ceiling
# (ISSUE 6 audit); the warmup *validation* stays in tier-1 below, and the
# unfiltered lane runs this e2e in full
def test_train_choco_compression_warmup():
    """Warmup ramps the drop-ratio 0→0.9 across its stage programs; the
    {x̂, s} carry crosses stage boundaries unchanged, and the dense-rate
    early consensus must leave replicas at least as tight after epoch 0 as
    the cold top-k-10% start does."""
    # 3 epochs / 2 warmup stages prove the same ramp shape as the original
    # 4/3 (dense epoch 0, intermediate stage, full-ratio final epoch) for
    # one fewer stage program + two fewer scanned epochs — this test was
    # tier-1's largest line item (ISSUE 6 wall-clock audit)
    base = dataclasses.replace(BASE, communicator="choco", compress_ratio=0.9,
                               consensus_lr=0.2, epochs=3)
    cold = train(base).history
    warm = train(dataclasses.replace(base, compress_warmup_epochs=2)).history
    assert warm[-1]["loss"] < warm[0]["loss"]
    # epoch 0 runs at ratio 0.0 (keep-all): consensus cannot be looser than
    # the compressed cold start's (generous 1.5x slack: different top-k
    # trajectories make the exact values incomparable)
    assert warm[0]["disagreement"] <= cold[0]["disagreement"] * 1.5
    # the final epoch runs at the full ratio in both runs
    assert warm[-1]["active_matchings"] == cold[-1]["active_matchings"]


def test_compress_warmup_validation():
    with pytest.raises(ValueError, match="compress_warmup_epochs"):
        TrainConfig(compress_warmup_epochs=2)  # decen: not compressed
    with pytest.raises(ValueError, match="compress_warmup_epochs"):
        TrainConfig(communicator="choco", compress_warmup_epochs=-1)


#: ResNet-8, 4 workers on a generator ring, two epochs of separable synthetic
#: images.  Sized for ~35 s of single-core XLA-CPU compile; the full-size
#: conv configs run on TPU via benchmarks/run_baselines.py.
CONV = TrainConfig(
    name="conv-smoke", model="resnet8", dataset="synthetic_image",
    dataset_kwargs={"num_train": 64, "num_test": 32, "separation": 40.0},
    num_workers=4, devices=1, graphid=None, topology="ring", batch_size=4, epochs=2,
    lr=0.05, warmup=False, matcha=False, fixed_mode="all", seed=0,
    save=False, eval_every=1, measure_comm_split=False,
)


@pytest.fixture(scope="module")
def conv_history():
    """``train(CONV)`` once a file: the smoke's run is the reference the
    memory knobs are held to."""
    return train(CONV).history


def test_train_conv_model_smoke(conv_history):
    """A conv model through the vmapped train step (not just a forward pass —
    test_models stops there): deterministic loss decrease."""
    hist = conv_history
    assert len(hist) == 2
    assert np.isfinite(hist[-1]["loss"])
    assert hist[1]["loss"] < hist[0]["loss"]  # measured: 2.369 -> 2.079
    assert np.isfinite(hist[-1]["disagreement"])


def test_train_remat_and_grad_chunk_exact(conv_history):
    """remat (block-level rematerialization) and grad_chunk (worker-slab
    fwd/bwd) are pure memory/FLOPs trades — both must reproduce the default
    step bit-for-bit-ish (state.py make_train_step, models _remat_block).
    The conv smoke config under each knob."""
    ref = conv_history[-1]
    # grad_chunk=2 in the combined knob: with 4 workers, grad_chunk=4 would
    # short-circuit to plain vmap and never test remat inside the lax.map
    # slab path (the matcha-resnet50-imagenet-256w production combination)
    for knob in ({"remat": True}, {"grad_chunk": 2},
                 {"remat": True, "grad_chunk": 2}):
        got = train(dataclasses.replace(CONV, **knob)).history[-1]
        assert got["loss"] == pytest.approx(ref["loss"], rel=1e-5), knob
        assert got["test_acc_mean"] == pytest.approx(
            ref["test_acc_mean"], abs=1e-6), knob
        assert got["disagreement"] == pytest.approx(
            ref["disagreement"], rel=1e-4, abs=1e-8), knob


def test_grad_chunk_validation():
    with pytest.raises(ValueError, match="grad_chunk"):
        TrainConfig(name="t", num_workers=8, grad_chunk=3)
    with pytest.raises(ValueError, match="grad_chunk"):
        TrainConfig(name="t", num_workers=8, grad_chunk=0)


def test_train_fixed_dpsgd_and_generator_topology():
    cfg = dataclasses.replace(
        BASE, matcha=False, fixed_mode="all", graphid=None, topology="ring",
        num_workers=8, epochs=2,
    )
    hist = train(cfg).history
    assert hist[-1]["loss"] < hist[0]["loss"]


def test_train_non_iid_partition():
    cfg = dataclasses.replace(BASE, non_iid=True, epochs=2)
    hist = train(cfg).history
    assert hist[-1]["loss"] < hist[0]["loss"]


def test_build_schedule_size_mismatch_raises():
    cfg = dataclasses.replace(BASE, graphid=0, num_workers=16)
    with pytest.raises(ValueError, match="8-worker topology"):
        build_schedule(cfg, 10)


def test_checkpoint_resume(tmp_path):
    cfg = dataclasses.replace(
        BASE, epochs=2, checkpoint_every=1, savePath=str(tmp_path),
        communicator="choco",  # carry must survive the roundtrip
    )
    r1 = train(cfg)
    # resume for one more epoch
    cfg2 = dataclasses.replace(cfg, epochs=3, checkpoint_every=0)
    r2 = train(cfg2, resume_dir=f"{cfg.savePath}/{cfg.name}_ckpt")
    assert r2.history[0]["epoch"] == 2
    # 2048 synthetic examples / 8 workers / bs 16 = 16 batches per epoch
    assert int(r2.state.step) == 3 * 16
    # choco carry survived: x_hat is nonzero after training
    assert float(jnp.abs(r2.state.comm_carry["x_hat"]).max()) > 0


def test_recorder_writes_reference_compatible_logs(tmp_path):
    cfg = dataclasses.replace(BASE, epochs=1, save=True, savePath=str(tmp_path))
    train(cfg)
    folder = tmp_path / f"{cfg.name}_{cfg.model}"
    assert folder.is_dir()
    for kind in ("time", "acc", "losses", "tacc", "disagreement"):
        f = folder / f"dsgd-lr{cfg.lr}-budget{cfg.budget}-r0-{kind}.log"
        assert f.exists(), f
    assert (folder / "ExpDescription").exists()
    # one line per epoch
    lines = (folder / f"dsgd-lr{cfg.lr}-budget{cfg.budget}-r3-losses.log").read_text().strip().splitlines()
    assert len(lines) == 1


def test_comm_split_measured():
    # two-program comp/comm split (SURVEY.md §5.1): comm_time is measured by
    # re-running the epoch's gossip chain in isolation; it must be positive,
    # bounded by the epoch, and comp+comm must reassemble the epoch time
    cfg = dataclasses.replace(BASE, epochs=1, measure_comm_split=True)
    r = train(cfg)
    comm = r.history[0]["comm_time"]
    assert 0 < comm <= r.history[0]["epoch_time"]
    rec = r.recorder
    assert rec.data["comptime"][0] + rec.data["commtime"][0] == pytest.approx(
        rec.data["time"][0]
    )


def test_checkpoint_resume_sharded_choco(tmp_path):
    """Multichip resume: 16 workers folded on the 8-device mesh with the
    shard_map CHOCO backend — the orbax roundtrip must restore the sharded
    params, carry {x_hat, s}, and step cursor."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    base = dict(
        name="shres", model="mlp", dataset="synthetic", batch_size=16,
        epochs=1, num_workers=16, graphid=None, topology="ring",
        matcha=True, budget=0.5, communicator="choco", compress_ratio=0.9,
        consensus_lr=0.3, lr=0.05, warmup=False, save=False, eval_every=0,
        measure_comm_split=False, devices=8, gossip_backend="shard_map",
        savePath=str(tmp_path),
    )
    r1 = train(TrainConfig(checkpoint_every=1, **base))
    steps_per_epoch = int(r1.state.step)
    r2 = train(TrainConfig(checkpoint_every=0, **{**base, "epochs": 2}),
               resume_dir=f"{tmp_path}/shres_ckpt")
    assert r2.history[0]["epoch"] == 1
    assert int(r2.state.step) == 2 * steps_per_epoch
    assert float(jnp.abs(r2.state.comm_carry["x_hat"]).max()) > 0


def test_checkpoint_resume_schedule_mismatch_raises(tmp_path):
    """The cursor's meaning is the flag stream it indexes: resuming against a
    schedule built with a different seed (different Bernoulli draws) or a
    shorter horizon must raise, not silently de-synchronize gossip from the
    solver's α (VERDICT r2 item 8; the invariant the reference leaves to
    identical global numpy seeding, graph_manager.py:298-309)."""
    cfg = dataclasses.replace(
        BASE, epochs=2, checkpoint_every=1, savePath=str(tmp_path))
    train(cfg)
    ckpt = f"{cfg.savePath}/{cfg.name}_ckpt"
    # different seed => different flag stream => fingerprint mismatch
    cfg_bad = dataclasses.replace(cfg, epochs=3, checkpoint_every=0, seed=99)
    with pytest.raises(ValueError, match="flag stream|fingerprint"):
        train(cfg_bad, resume_dir=ckpt)
    # shorter horizon than the checkpointed stream => unverifiable => raises
    cfg_short = dataclasses.replace(cfg, epochs=1, checkpoint_every=0)
    with pytest.raises(ValueError, match="exceeds|shorter"):
        train(cfg_short, resume_dir=ckpt)
    # different budget => different probs/alpha => static fingerprint mismatch
    cfg_budget = dataclasses.replace(cfg, epochs=3, checkpoint_every=0,
                                     budget=0.9)
    with pytest.raises(ValueError, match="fingerprint|matchings"):
        train(cfg_budget, resume_dir=ckpt)


def test_checkpoint_resume_legacy_pre_mix_pending(tmp_path):
    """Regression (ROADMAP PR-5 finding): a checkpoint written *before*
    ``TrainState.mix_pending`` existed must still restore.  orbax's
    ``StandardRestore`` raises ``Dict key mismatch`` against any template
    carrying the slot (both the array and ``()`` forms), so
    ``restore_checkpoint`` detects the legacy tree shape and restores
    through a mix_pending-free template, re-attaching the empty slot —
    which ``_reconcile_mix_pending`` then primes if the resuming run is
    pipelined."""
    import os
    import shutil

    import orbax.checkpoint as ocp

    cfg = dataclasses.replace(BASE, epochs=1, checkpoint_every=1,
                              savePath=str(tmp_path), eval_every=0)
    r1 = train(cfg)
    ckpt = f"{cfg.savePath}/{cfg.name}_ckpt"

    # rewrite epoch 0's tree in the pre-PR4 shape: same leaves, no
    # mix_pending entry — exactly what a pre-overlap run saved
    legacy_dir = str(tmp_path / "legacy_ckpt")
    s = r1.state
    legacy_tree = {"params": s.params, "batch_stats": s.batch_stats,
                   "opt_state": s.opt_state, "comm_carry": s.comm_carry,
                   "step": s.step}
    mgr = ocp.CheckpointManager(
        legacy_dir, options=ocp.CheckpointManagerOptions(create=True))
    mgr.save(0, args=ocp.args.StandardSave(legacy_tree))
    mgr.wait_until_finished()
    mgr.close()
    # the schedule fingerprint sidecar is format-independent: reuse it
    shutil.copy(os.path.join(ckpt, "schedule-0.json"),
                os.path.join(legacy_dir, "schedule-0.json"))

    # the old-format checkpoint resumes through the full train loop (eager
    # keeps the empty slot the whole way)
    r2 = train(dataclasses.replace(cfg, epochs=2, checkpoint_every=0),
               resume_dir=legacy_dir)
    assert r2.history[0]["epoch"] == 1
    assert int(r2.state.step) == 2 * 16  # 2048 ex / 8 workers / bs 16
    assert np.isfinite(r2.history[0]["loss"])

    # pipelined resume needs only the restore seam, not a second full train:
    # the array-probe template triggers the same legacy fallback, and the
    # re-attached empty slot is exactly what _reconcile_mix_pending primes
    # a zero delta from under --overlap 1step
    from matcha_tpu.train.checkpoint import restore_checkpoint

    probe = r1.state.replace(
        mix_pending=jnp.zeros((8, int(np.sum([np.prod(p.shape) for p in
                              jax.tree_util.tree_leaves(r1.state.params)])
                              // 8)), jnp.float32))
    st, ep = restore_checkpoint(legacy_dir, probe)
    assert ep == 0 and st.mix_pending == ()
