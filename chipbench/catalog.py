"""Find a cell, its configuration, its task and its metrics by name.

``BENCHMARK.json`` names the first and the last; each is a file of its own
under ``chipbench/``, so a later PR brings any of these by adding files and
entries and edits none that is there:

* a configuration: ``configs/<name>.json`` (sizes, stated precision,
  ``reference``, ``task``) and an entry under ``configs``;
* an architecture: ``reference/<reference>.py`` with ``forward`` and
  ``forward_macs`` (``reference/__init__.py``);
* a task: ``tasks/<task>.py`` with ``make``, ``prepare`` and ``loss``
  (``tasks/__init__.py``); a configuration without the key has
  :data:`DEFAULT_TASK`;
* a cell: ``workloads/<cell>.json`` (the job's ``TrainConfig`` fields, its
  steps an epoch, its limits, its rehearsal sizes) and an entry under
  ``workloads``;
* a per-layer metric: ``metrics/<metric>.py`` with ``read(run)`` and an
  entry under ``per_layer``.

What such a PR may not touch: any file that is here already, an end-to-end
metric, a bound, ``run_seconds``.
"""

import importlib
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
DEFAULT_TASK = "image_classes"


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _entry(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}; "
                   f"it has {[e['name'] for e in entries]}")


def load_cell(name: str):
    """(BENCHMARK.json, the cell's job file, its configuration file)."""
    bench = benchmark()
    entry = _entry(bench["workloads"], name, "workload")
    job = json.loads((HERE / "workloads" / f"{name}.json").read_text())
    conf_entry = _entry(bench["configs"], entry["config"], "config")
    config = json.loads((ROOT / conf_entry["file"]).read_text())
    for key in ("config", "chips"):
        if job[key] != entry[key]:
            raise ValueError(f"{name}: job file says {key}={job[key]!r}, "
                             f"BENCHMARK.json says {entry[key]!r}")
    return bench, job, config


def load_task(config: dict):
    """The module ``chipbench/tasks/<task>.py`` that the configuration
    names."""
    name = config.get("task", DEFAULT_TASK)
    there = sorted(p.stem for p in (HERE / "tasks").glob("[!_]*.py"))
    if name not in there:
        raise KeyError(f"{config.get('name')}: no task named {name!r} under "
                       f"chipbench/tasks; there are {there}")
    return importlib.import_module(f"{__package__}.tasks.{name}")


def metrics_of(bench: dict, kind: str, cell: str):
    """The entries of ``end_to_end`` or ``per_layer`` this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def load_reader(metric: str):
    """The ``read(run)`` of ``chipbench/metrics/<metric>.py``."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench.metrics." + metric.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
