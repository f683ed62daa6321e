"""A token job through the same ``train()`` as the image models: the loader
against ``chipbench/tasks/next_token.make``'s file, and a toy sparse decoder
on two workers of a ``chain`` on each epoch path: the loss falls, nothing
retraces, the spans cover the period, the model's counters ride the period's
record, and evaluation gives the held-out loss and token accuracy."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench.tasks import next_token
from matcha_tpu.data import (WorkerBatches, judged_positions, load_tokens,
                             partition_indices)
from matcha_tpu.obs.journal import validate_event
from matcha_tpu.train import TrainConfig, train
from matcha_tpu.train.loop import _HostStacks
from matcha_tpu.utils.profiling import SPAN_NAMES

SEQ, VOCAB, WORKERS, BATCH, STEPS, EPOCHS = 32, 48, 2, 2, 3, 4
SIZES = {
    "hidden": 32, "head_dim": 8, "q_heads_held": 4, "kv_heads_held": 1,
    "layer_types": ["sliding", "full"], "sliding_window": 8,
    "rope_theta": 500000,
    "yarn": {"factor": 16, "original_max_position_embeddings": 8192,
             "beta_fast": 32, "beta_slow": 1,
             "attention_factor": 1.2772588722239782},
    "num_experts": 8, "experts_per_token": 2, "experts_held": [0, 1],
    "expert_width": 24, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
    "vocab_held": VOCAB, "seq_len": SEQ, "attn_block": 16, "loss_chunk": 16,
}
PATHS = {"whole_epoch": {}, "chunked": {"scan_chunk": 2},
         "per_batch": {"scan_epoch": False}}


@pytest.fixture(scope="module")
def npz(tmp_path_factory):
    """(path, arrays) of ``next_token.make``'s data set for the toy sizes."""
    data = next_token.make(11, WORKERS * BATCH * STEPS, 6, {"sizes": SIZES})
    path = tmp_path_factory.mktemp("tokens") / "data.npz"
    np.savez(path, **data)
    return str(path), data


def test_loader_keeps_ids_and_document_numbers_as_written(npz):
    path, data = npz
    ds = load_tokens(path)
    assert ds.name == "tokens" and ds.num_classes == data["x_train"].max() + 1
    assert ds.token_rows  # what the loop reads; an image data set says False
    for split in ("train", "test"):
        for half in "xy":
            got = getattr(ds, f"{half}_{split}")
            assert got.dtype == np.int32 and got.flags.c_contiguous
            np.testing.assert_array_equal(got, data[f"{half}_{split}"])
    assert ds.x_train.shape == (WORKERS * BATCH * STEPS, SEQ + 1)
    assert judged_positions(ds.y_test) == int(np.sum(
        data["y_test"][:, 1:] == data["y_test"][:, :-1]))


def test_loader_refuses_a_file_of_another_layout(tmp_path):
    np.savez(tmp_path / "images.npz", x_train=np.zeros((4, 8, 8, 3), np.uint8),
             y_train=np.zeros(4, np.int32), x_test=np.zeros((2, 8, 8, 3)),
             y_test=np.zeros(2, np.int32))
    with pytest.raises(ValueError, match="ids and document numbers"):
        load_tokens(str(tmp_path / "images.npz"))


def test_stacks_are_int32_row_for_row_disjoint_and_reused(npz):
    ds = load_tokens(npz[0])
    parts = partition_indices(len(ds.x_train), WORKERS, seed=5)
    loader = WorkerBatches(ds.x_train, ds.y_train, parts, BATCH, seed=5)
    stacks = _HostStacks(loader, None)
    xs, ys, reused = stacks.pair(0, STEPS)
    assert (xs.dtype, ys.dtype, reused) == (np.int32, np.int32, 0)
    assert xs.shape == ys.shape == (STEPS, WORKERS, BATCH, SEQ + 1)
    loader.epoch_into(0, xs, ys)
    # a row's ids and its document numbers stay together, each row is fed
    # once, and no row reaches two workers
    number = {ds.x_train[i].tobytes() + ds.y_train[i].tobytes(): i
              for i in range(len(ds.x_train))}
    fed = np.array([[[number[xs[t, w, b].tobytes() + ys[t, w, b].tobytes()]
                      for b in range(BATCH)] for w in range(WORKERS)]
                    for t in range(STEPS)])
    assert sorted(fed.reshape(-1)) == list(range(len(ds.x_train)))
    for w in range(WORKERS):
        assert set(fed[:, w].reshape(-1)) <= set(parts[w].tolist())
    for (x, y), t in zip(loader.epoch(0), range(STEPS)):
        np.testing.assert_array_equal(x, xs[t])
        np.testing.assert_array_equal(y, ys[t])
    again, _, reused = stacks.pair(0, STEPS)
    assert reused == 1 and np.shares_memory(again, xs)


@pytest.fixture(scope="module", params=list(PATHS))
def run(request, npz, tmp_path_factory):
    tmp = tmp_path_factory.mktemp(request.param)
    config = TrainConfig(
        name="tokens", model="mellum2", dataset="tokens", datasetRoot=npz[0],
        model_kwargs={"sizes": SIZES}, num_workers=WORKERS, graphid=None,
        topology="chain", batch_size=BATCH, epochs=EPOCHS, lr=0.05,
        warmup=False, matcha=True, budget=0.5, seed=3, eval_every=2,
        remat=True, devices=1, save=True, savePath=str(tmp),
        **PATHS[request.param])
    result = train(config, boundary_hook=lambda seam: None)
    return request.param, result, npz[1]


def test_a_grad_chunk_that_the_step_would_not_read_is_refused(npz, tmp_path):
    """Workers of a model that supplies its loss run one after another:
    ``grad_chunk`` 1 says that (the cell's job file does), 2 is an error."""
    config = TrainConfig(
        name="tokens", model="mellum2", dataset="tokens", datasetRoot=npz[0],
        model_kwargs={"sizes": SIZES}, num_workers=WORKERS, graphid=None,
        topology="chain", batch_size=BATCH, epochs=1, lr=0.05, warmup=False,
        devices=1, save=False, savePath=str(tmp_path), grad_chunk=2)
    with pytest.raises(ValueError, match="supplies its loss"):
        train(config)


def test_loss_falls_and_nothing_retraces(run):
    _, result, _ = run
    losses = [h["loss"] for h in result.history]
    assert len(losses) == EPOCHS and np.all(np.isfinite(losses))
    assert losses[-1] < losses[0] < 1.1 * np.log(VOCAB)
    kinds = [e["kind"] for e in result.recorder.events]
    assert "retrace" not in kinds
    assert all(not validate_event(e) for e in result.recorder.events)
    json.dumps(result.history)
    assert result.state.batch_stats == {}


def test_backend_event_says_what_runs_the_grouped_products(run):
    """Off the TPU the expert layer keeps ``lax.ragged_dot``, and the run's
    ``backend`` event says so, a record a form and shape with the reason
    (PR 38; on the chip it names the kernels and their tiles)."""
    _, result, _ = run
    (event,) = [e for e in result.recorder.events if e["kind"] == "backend"]
    record = event["expert_products"]
    # 2 layers x (gate, up, down) x (forward, recomputed, two gradients) x
    # 2 workers
    assert record["products_per_step"] == 48
    assert record["on_kernel"] == record["kernel_sites"] == 0
    assert record["empty_rows"] == "in no group"
    assert len(record["products"]) == 6
    for product in record["products"]:
        assert product["kernel"] == "lax.ragged_dot"
        assert "off the TPU" in product["reason"]
        # an even router's slots twice over: 64 tokens x 2 of 8 x 2 held x 2
        assert product["rows"] == 64
    json.dumps(event)


def test_spans_cover_the_period_and_dispatch_counts_tokens(run):
    path, result, _ = run
    records = [e for e in result.recorder.events if e["kind"] == "spans"]
    assert len(records) == EPOCHS
    gaps = []
    for r in records:
        leaves = [s for s in r["spans"] if s["parent"] == r["period"]]
        # the leaves tile the period: known names, in start order, none
        # over another, none outside the period.  What lies between two of
        # them is the few statements that close one span and open the next,
        # so the gaps are judged by their median over the run and not by a
        # share of a millisecond-sized epoch's wall clock, which one
        # descheduled moment under the test workers breaks
        assert {s["name"] for s in leaves} <= set(SPAN_NAMES)
        assert {"record_epoch", "epoch_python" if path == "per_batch"
                else "dispatch"} <= {s["name"] for s in leaves}
        edges = [r["t0"]] + [t for s in leaves for t in (s["t0"], s["t1"])] \
            + [r["t1"]]
        assert edges == sorted(edges)
        gaps += [b - a for a, b in zip(edges[0::2], edges[1::2])]
        tokens = sum(s.get("tokens", 0) for s in leaves
                     if s["name"] == "dispatch")
        assert tokens == (0 if path == "per_batch"
                          else STEPS * WORKERS * BATCH * SEQ)
    assert float(np.median(gaps)) < 2e-3


def test_counters_ride_the_period_and_the_history(run):
    _, result, data = run
    records = [e for e in result.recorder.events if e["kind"] == "spans"]
    docs = data["y_train"]
    for r, h in zip(records, result.history):
        c = r["counters"]
        assert c == h["counters"]
        assert set(c) == {"loss_positions", "moe_slots_held",
                          "moe_rows_computed", "moe_rows_multiplied",
                          "moe_load"}
        # every row is fed once an epoch, so the epoch judges what the
        # data set has to judge
        assert c["loss_positions"] == judged_positions(docs)
        load = np.asarray(c["moe_load"])
        assert load.shape == (2, 2) and load.sum() == c["moe_slots_held"]
        assert 0 < c["moe_slots_held"] <= c["moe_rows_computed"]
        # off the TPU ``lax.ragged_dot`` multiplies every row laid out
        assert c["moe_rows_multiplied"] == c["moe_rows_computed"]
        assert c["moe_slots_held"] <= 2 * 2 * STEPS * WORKERS * BATCH * SEQ


def test_evaluation_gives_held_out_loss_and_token_accuracy(run):
    _, result, _ = run
    for h in result.history:
        ran = (h["epoch"] + 1) % 2 == 0
        assert (h["test_loss_mean"] > 0) == ran
        if ran:
            assert 0 < h["test_loss_mean"] < 2 * np.log(VOCAB)
            assert 0 <= h["test_acc_mean"] <= 1
    assert any(e["kind"] == "spans" and any(
        s["name"] == "evaluate" for s in e["spans"])
        for e in result.recorder.events)


@pytest.mark.parametrize("conf_name, cell", [
    ("mellum2-12b-a2.5b.ep8-s4k", "mellum2-12b-a2.5b.ep8-s4k.w2-matcha"),
    ("keye-vl2-30b-a3b.ep16-s8k", "keye-vl2-30b-a3b.ep16-s8k.w2-matcha"),
    ("qwen3-next-80b-a3b.ep64-s8k", "qwen3-next-80b-a3b.ep64-s8k.w2-matcha"),
    ("sdar-30b-a3b.ep16-s4k", "sdar-30b-a3b.ep16-s4k.w2-matcha")])
def test_job_file_hands_the_program_the_configurations_sizes(conf_name, cell):
    """The harness passes ``TrainConfig`` fields only, so a token cell's job
    file repeats the configuration's sizes: they must not drift apart."""
    root = Path(__file__).resolve().parents[1] / "chipbench"
    conf = json.loads((root / "configs" / f"{conf_name}.json").read_text())
    job = json.loads((root / "workloads" / f"{cell}.json").read_text())
    assert job["train_config"]["model_kwargs"]["sizes"] == conf["sizes"]
    small = dict(conf["sizes"], **job["rehearsal"]["sizes"])
    assert job["rehearsal"]["train_config"]["model_kwargs"]["sizes"] == small
    sizes = conf["sizes"]
    assert (conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["vocab_size"], conf["num_experts"],
            conf["num_experts_per_tok"], conf["moe_intermediate_size"],
            conf["hidden_size"], conf["head_dim"],
            conf["num_experts_held"]) == (
        sizes["q_heads_held"], sizes["kv_heads_held"], sizes["vocab_held"],
        sizes["num_experts"], sizes["experts_per_token"],
        sizes["expert_width"], sizes["hidden"], sizes["head_dim"],
        len(sizes["experts_held"]))
    if "layer_types" in sizes:
        assert (conf["num_hidden_layers"], conf["sliding_window"]) == (
            len(sizes["layer_types"]), sizes["sliding_window"])
    elif "gdn_chunk" in sizes:
        assert (conf["num_hidden_layers"], conf["full_attention_interval"],
                conf["rope_theta"], conf["linear_num_key_heads"],
                conf["linear_num_value_heads"], conf["linear_key_head_dim"],
                conf["linear_value_head_dim"], conf["linear_conv_kernel_dim"],
                conf["shared_expert_intermediate_size"],
                conf["partial_rotary_factor"] * conf["head_dim"]) == (
            sizes["num_layers"], sizes["full_attention_interval"],
            sizes["rope_theta"], sizes["linear_key_heads_held"],
            sizes["linear_value_heads_held"], sizes["linear_key_dim"],
            sizes["linear_value_dim"], sizes["conv_kernel"],
            sizes["shared_expert_width"], sizes["rotary_dim"])
    elif "block_length" in sizes:
        assert (conf["num_hidden_layers"], conf["rope_theta"],
                conf["rms_norm_eps"], conf["norm_topk_prob"]) == (
            sizes["num_layers"], sizes["rope_theta"], sizes["rms_norm_eps"],
            sizes["norm_topk_prob"])
        assert sizes["mask_id"] == sizes["vocab_held"] - 1
        assert sizes["seq_len"] % sizes["block_length"] == 0
    else:
        indexer = conf["sa_config"]
        assert (conf["num_hidden_layers"], conf["rope_theta"],
                indexer["indexer_num_heads"], indexer["indexer_head_dim"],
                indexer["indexer_num_kv_heads"], indexer["topk"]) == (
            sizes["num_layers"], sizes["rope_theta"], sizes["indexer_heads"],
            sizes["indexer_head_dim"], 1, sizes["index_topk"])
