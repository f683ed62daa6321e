"""Run one cell once: set-up, a timed window of whole epochs through
``train()`` itself, the check of its first epoch and of one step against the
reference, one JSON line.

``train(config, boundary_hook=hook)`` calls the hook before every epoch; the
hook is the harness's clock.  It lets the warm-up epochs pass (until one adds
no ``compile`` or ``retrace`` event to the journal), stamps the window's start
at a boundary, and asks for the stop at the first boundary at or after
``--seconds``.  Nothing of the program is edited or side-stepped.
"""

import argparse
import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from . import catalog, check, tracered
from .peaks import peaks_of

PROGRAM_EVENTS = ("compile", "retrace")


def flat_tree(tree, to_host=False) -> dict:
    """A pytree of ``[N, ...]`` leaves as a flat ``{"a/b/c": leaf}``."""
    import jax

    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(p, "key", getattr(p, "name", p)))
                     for p in path): np.asarray(leaf) if to_host else leaf
            for path, leaf in leaves}


def momentum_of(opt_state) -> dict:
    """The SGD momentum trace inside an optax state, as a flat tree."""
    import jax

    holds = lambda n: "trace" in getattr(n, "_fields", ())
    holders = [n for n in jax.tree_util.tree_leaves(opt_state, is_leaf=holds)
               if holds(n)]
    if len(holders) != 1:
        raise RuntimeError(f"expected one momentum trace in the optimizer "
                           f"state, found {len(holders)}")
    return flat_tree(holders[0].trace)


class FirstEpoch:
    """What every hook keeps of a ``train()`` call for the check: the
    initial state (on the host: the program donates it) and, on the device,
    the norms of the state after the first epoch."""

    def __init__(self):
        self.first = self.after = self.schedule = None

    def keep(self, seam):
        import jax

        if seam.epoch == 0:
            self.schedule = seam.schedule
            self.first = {"params": flat_tree(seam.state.params, True),
                          "stats": flat_tree(seam.state.batch_stats, True)}
        elif seam.epoch == 1:
            self.after = jax.device_get(check.summarize(
                flat_tree(seam.state.params),
                momentum_of(seam.state.opt_state), self.first["params"]))


class OneStep(FirstEpoch):
    """The hook of the one-step call: stop after the first epoch."""

    def __call__(self, seam):
        self.keep(seam)
        if seam.epoch == 1:
            seam.request_stop()


class Window(FirstEpoch):
    """The boundary hook: warm-up, the window's two stamps, the stop."""

    def __init__(self, seconds: float, trace_dir=None,
                 clock=time.perf_counter):
        super().__init__()
        self.seconds, self.trace_dir, self.clock = seconds, trace_dir, clock
        self.boundaries = []  # one {"epoch", "t", "programs"} per hook call
        self.start = self.stop = None  # epochs [start, stop) are the window
        self.traced = None  # (first epoch, epoch after the last) traced
        self.events = []

    def _programs(self, seam) -> int:
        return sum(e["kind"] in PROGRAM_EVENTS for e in seam.recorder.events)

    def __call__(self, seam):
        import jax

        with jax.profiler.TraceAnnotation("chipbench/hook"):
            self._at_boundary(seam, jax)

    def _mark(self, jax, name):
        with jax.profiler.TraceAnnotation(name):
            pass

    def _at_boundary(self, seam, jax):
        now = self.clock()
        k = seam.epoch
        self.events = seam.recorder.events
        self.boundaries.append(
            {"epoch": k, "t": now, "programs": self._programs(seam)})
        self.keep(seam)
        self.boundaries[-1]["hook_s"] = self.clock() - now
        if self.start is None:
            quiet = k >= 2 and (self.boundaries[-1]["programs"]
                                == self.boundaries[-2]["programs"])
            if quiet:
                self.start = k
                if self.trace_dir:
                    options = jax.profiler.ProfileOptions()
                    options.python_tracer_level = 0
                    jax.profiler.start_trace(self.trace_dir,
                                             profiler_options=options)
                    self._mark(jax, tracered.WINDOW_MARKS[0])
                    self.traced = (k, k + 2)
                self.boundaries[-1]["t"] = self.clock()
            return
        if self.traced and k == self.traced[1]:
            self._mark(jax, tracered.WINDOW_MARKS[1])
            jax.profiler.stop_trace()  # writes the file: many seconds
            self.boundaries[-1]["t"] = self.clock()
        if now - self.boundaries[self.start]["t"] >= self.seconds \
                and not (self.traced and k < self.traced[1]):
            self.stop = k
            seam.request_stop()


def describe_devices(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices):
    """The peak on the fullest chip, as far as the device's counters tell:
    the larger of ``peak_bytes_in_use`` and ``peak_bytes_reserved``.

    The TPU runtime counts a running program's scratch under the second and
    its buffers under the first, and which of the two a program's temporaries
    land in differs from program to program, so this is a peak the chip
    certainly held and can be short of the true one by the program's
    arguments (PERF.md section 7): it fills the result line's ``device``, and
    no metric reads it."""
    peaks = [max((d.memory_stats() or {}).get(key) or 0
                 for key in ("peak_bytes_in_use", "peak_bytes_reserved"))
             for d in devices]
    return max(peaks) or None


def build_train_config(job, workdir, dataset_path):
    from matcha_tpu.train import TrainConfig

    fields = dict(job["train_config"])
    fields.update(name="chipbench", savePath=str(workdir),
                  datasetRoot=str(dataset_path), devices=job["chips"])
    unknown = set(fields) - {f.name for f in dataclasses.fields(TrainConfig)}
    if unknown:
        raise ValueError(f"{job['name']}: not TrainConfig fields: {unknown}")
    return TrainConfig(**fields)


def first_epoch_rows(config, n_train):
    """``idx[T, N, B]``: the rows of the data set that each worker takes at
    each step of epoch 0, from the program's own partition and loader."""
    from matcha_tpu.data import WorkerBatches, partition_indices

    rows = np.arange(n_train)
    parts = partition_indices(n_train, config.num_workers, seed=config.seed,
                              non_iid=config.non_iid)
    loader = WorkerBatches(rows, rows, parts, config.batch_size,
                           seed=config.seed, augment=False)
    idx = np.stack([xb for xb, _ in loader.epoch(0)])
    flat = idx.reshape(-1)
    if len(np.unique(flat)) != len(flat):
        raise RuntimeError("the first epoch feeds a row twice")
    return idx


def run_reference(config_file, job, hook, data, train_config, compute):
    """The reference's first epoch from the initial state ``hook`` kept of
    the program's: (the norms after it, its losses ``[T, N]``)."""
    from .reference.step import make_epoch

    sched = hook.schedule
    idx = first_epoch_rows(train_config, len(data["x_train"]))
    hyper = {k: job["train_config"][k]
             for k in ("lr", "momentum", "weight_decay", "nesterov")}
    hyper["reference_block"] = job["reference_block"]
    epoch = make_epoch(config_file, hyper, np.asarray(sched.perms), compute)
    params, _, momentum, losses = epoch(
        hook.first["params"], hook.first["stats"], data["x_train"],
        data["y_train"], idx, np.asarray(sched.flags[:len(idx)], np.float32),
        float(sched.alpha))
    import jax

    return jax.device_get(check.summarize(
        params, momentum, hook.first["params"])), losses


def stage_job(job, config_file, seed, steps, workdir):
    """The data set of ``steps`` steps an epoch drawn from the seed, written
    where ``train()`` reads it, and the ``TrainConfig`` that names it."""
    tc = job["train_config"]
    n_train = tc["num_workers"] * tc["batch_size"] * steps
    data = catalog.load_task(config_file).make(
        seed, n_train, int(n_train * job["data"]["test_fraction"]),
        config_file)
    workdir.mkdir(parents=True, exist_ok=True)
    np.savez(workdir / "data.npz", **data)
    return data, build_train_config(job, workdir, workdir / "data.npz")


def one_step(job, config_file, seed, workdir):
    """``train()`` through the same seam on a one-step epoch of the same N,
    batch and model: (the hook with the state before and the norms after
    the step, the data, the ``TrainConfig``, the step's loss)."""
    from matcha_tpu.train import train

    data, train_config = stage_job(job, config_file, seed, 1, workdir)
    hook = OneStep()
    result = train(train_config, boundary_hook=hook)
    loss = result.history[0]["loss"]
    del result
    gc.collect()
    return hook, data, train_config, loss


def apply_rehearsal(job, config_file):
    """Tiny sizes for a CPU rehearsal of the cell's control flow."""
    small = job["rehearsal"]
    job["limits"] = small["limits"]
    job["train_config"].update(small.get("train_config", {}))
    job["data"].update(small.get("data", {}))
    config_file["sizes"].update(small.get("sizes", {}))


def open_cell(workload, rehearse_on_cpu):
    """The cell's files and the chips it runs on: (BENCHMARK.json, job,
    configuration, devices, their description, their peaks); None, with a
    line on stderr, where JAX finds no TPU or fewer chips than it asks."""
    bench, job, config_file = catalog.load_cell(workload)
    if rehearse_on_cpu:
        apply_rehearsal(job, config_file)

    from matcha_tpu.utils import pin_platform

    pin_platform("cpu" if rehearse_on_cpu else None)
    import jax

    devices = jax.devices()
    device = describe_devices(devices)
    peaks = None
    if not rehearse_on_cpu:
        if device["platform"] != "tpu" or device["count"] < job["chips"]:
            print(f"chipbench: {workload} needs {job['chips']} TPU "
                  f"chip(s); JAX found {device}", file=sys.stderr)
            return None
        peaks = peaks_of(device["kind"])
    devices = devices[:job["chips"]]
    device["count"] = len(devices)
    return bench, job, config_file, devices, device, peaks


def main(argv=None, t0=None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(prog="python -m chipbench")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="tiny sizes on the CPU, to debug the control flow; "
                         "the line names cpu and the exit code is 2")
    args = ap.parse_args(argv)

    cell = open_cell(args.workload, args.rehearse_on_cpu)
    if cell is None:
        return 1
    bench, job, config_file, devices, device, peaks = cell
    import jax

    cache = {"hits": 0, "misses": 0}

    def count_cache(event, **_):
        if event.endswith("/cache_hits"):
            cache["hits"] += 1
        elif event.endswith("/cache_misses"):
            cache["misses"] += 1

    jax.monitoring.register_event_listener(count_cache)

    workdir = Path(tempfile.mkdtemp(prefix="chipbench_"))
    try:
        line = run_cell(args, bench, job, config_file, devices, device,
                        peaks, cache, workdir, t0)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    if line is None:
        return 1
    if args.rehearse_on_cpu:
        line["correct"] = False
        line["rehearsal"] = "cpu: proves nothing about the chip"
    print(json.dumps(line))
    return 2 if args.rehearse_on_cpu else 0


def run_cell(args, bench, job, config_file, devices, device, peaks, cache,
             workdir, t0):
    """One run of one cell; the result line as a dict, or None where the
    run ended before its window closed."""
    from matcha_tpu.train import TrainingDiverged, train

    tc = job["train_config"]
    steps = job["data"]["steps_per_epoch"]
    data, train_config = stage_job(job, config_file, args.seed, steps,
                                   workdir / "job")

    trace_dir = str(workdir / "trace") if args.trace else None
    window = Window(args.seconds, trace_dir)
    diverged = None
    try:
        result = train(train_config, boundary_hook=window)
        history = result.history
        del result
    except TrainingDiverged as e:
        diverged, history = str(e), []
    if window.stop is None:
        print(f"chipbench: the run ended before the window closed "
              f"({diverged or 'epochs ran out'})", file=sys.stderr)
        return None
    memory_peak = memory_peak_bytes(devices)
    gc.collect()

    # ---- the window's own numbers -------------------------------------
    start, stop = window.start, window.stop
    stamps = {b["epoch"]: b for b in window.boundaries}
    epochs = history[start:stop]
    wall = stamps[stop]["t"] - stamps[start]["t"]
    samples = len(epochs) * steps * tc["num_workers"] * tc["batch_size"]
    failed = sum(
        (not np.isfinite(h["loss"]))
        or stamps[h["epoch"] + 1]["programs"] != stamps[h["epoch"]]["programs"]
        for h in epochs)
    losses = [h["loss"] for h in history]
    loss_fell = bool(np.isfinite(losses[-1]) and losses[-1] < losses[0])
    for e in window.events:
        if e["kind"] == "compile":
            print(f"# program {e['label']}: compiled peak "
                  f"{e['peak_bytes'] / 1e9:.3f} GB, arguments "
                  f"{e['arg_bytes'] / 1e9:.3f} GB, "
                  f"{e['compile_seconds']:.1f} s in the ledger's compile")
    print(f"# device counters {devices[0].memory_stats()}")
    print("# hook seconds at boundaries 0, 1: "
          + ", ".join(f"{stamps[k]['hook_s']:.2f}" for k in (0, 1)))
    print(f"# window epochs {start}..{stop - 1}: {len(epochs)} epochs, "
          f"{wall:.3f} s, {failed} failed; loss {losses[0]:.6f} -> "
          f"{losses[-1]:.6f}; compile cache {cache}", flush=True)

    run = {
        "cell": job, "config": config_file, "device": device, "peaks": peaks,
        "steps": steps, "epochs": epochs, "boundaries": stamps,
        "wall_s": wall,
        "samples": samples, "setup_s": stamps[start]["t"] - t0,
        "memory_peak_bytes": memory_peak, "cache": dict(cache),
        "events": window.events, "trace": None, "traced": window.traced,
    }
    device_line = dict(device, memory_peak_bytes=memory_peak)

    if args.trace:
        # (no device plane on the CPU: a rehearsal reads no trace)
        run["trace"] = tracered.reduce_planes(tracered.load(trace_dir))
        run["traced_steps"] = (window.traced[1] - window.traced[0]) * steps
        device_line.update(
            busy_s=run["trace"]["busy_s"] if run["trace"] else 0.0,
            window_s=run["trace"]["window_s"] if run["trace"] else 0.0)

    # ---- correct: the first epoch and one step against the reference ---
    t_ref = time.perf_counter()
    numbers = check.compare(window.after, losses[0], *run_reference(
        config_file, job, window, data, train_config, "stated"))
    hook, data1, config1, loss1 = one_step(job, config_file, args.seed,
                                           workdir / "one_step")
    numbers.update(check.compare(hook.after, loss1, *run_reference(
        config_file, job, hook, data1, config1, "highest"), prefix="step1_"))
    ok, lines = check.verdict(numbers, job["limits"])
    print(f"# check loss fell: {loss_fell}; the check took "
          f"{time.perf_counter() - t_ref:.1f} s after the window", flush=True)
    correct = bool(ok and loss_fell and failed == 0)

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in catalog.metrics_of(bench, kind, job["name"]):
        value = catalog.load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    line = {"correct": correct, "attempted": len(epochs),
            "failed": int(failed), "metrics": metrics, "device": device_line,
            "loss": [losses[0], losses[-1]]}
    if run["trace"]:
        line["breakdown"] = run["trace"]["breakdown"]
    # each number compared beside its limit: the run's last lines on
    # standard error, and the result line's last key
    print("\n".join(lines), file=sys.stderr, flush=True)
    line["check"] = {k: [v, job["limits"].get(k)] for k, v in numbers.items()}
    return line
