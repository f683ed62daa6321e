"""Find a cell, its configuration and its metrics by name.

``BENCHMARK.json`` names them; each is a file of its own under
``chipbench/``, so a later PR adds a cell, a configuration or a per-layer
metric by adding files and entries and edits none that is there.
"""

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


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
