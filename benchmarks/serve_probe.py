#!/usr/bin/env python
"""serve_r8: on-device evidence for the production run controller.

One supervised saved run (DESIGN.md §22) on whatever backend the window
exposes: promotion every epoch behind the signed manifest, a budget
hot-swap published before launch (it must journal as applied at the
first epoch boundary with zero retraces), the endpoint answering
/healthz /status /promoted over real HTTP, and the stop document as the
only way the run ends.  The markdown artifact records the endpoint
bodies and the journaled control/promotion events — the committable
evidence that the daemon plane survives a real-TPU window, not just the
CPU e2e suite.

Exit 0 only when the daemon drained to exit 0, /healthz and /promoted
answered 200, the hot-swap applied, and no retrace events landed.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import threading
import time
import urllib.error
import urllib.request

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from matcha_tpu.obs import read_journal  # noqa: E402
from matcha_tpu.serve import (  # noqa: E402
    Controller,
    ServeConfig,
    ServeEndpoint,
    write_control,
)


def _get(port: int, path: str):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=5) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())
    except OSError:
        return None, None


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--round", type=int, default=8)
    p.add_argument("--out", default=None,
                   help="markdown artifact (default benchmarks/serve_r{round}.md)")
    p.add_argument("--save-path", default=None,
                   help="run folder (default benchmarks/serve_run_r{round})")
    p.add_argument("--workers", type=int, default=16)
    p.add_argument("--deadline", type=float, default=300.0,
                   help="seconds to wait for /healthz and /promoted to go 200")
    args = p.parse_args(argv)
    out = args.out or os.path.join(
        REPO_ROOT, "benchmarks", f"serve_r{args.round}.md")
    save_path = args.save_path or os.path.join(
        REPO_ROOT, "benchmarks", f"serve_run_r{args.round}")
    shutil.rmtree(save_path, ignore_errors=True)

    name = f"serve_r{args.round}"
    config = {
        "name": name, "model": "mlp", "dataset": "synthetic",
        "dataset_kwargs": {"num_train": 256, "num_test": 32},
        "num_workers": args.workers, "graphid": 2, "batch_size": 16,
        # the stop document is the only way this run ends — the probe
        # publishes it once the endpoint has answered
        "epochs": 100000, "lr": 0.05, "warmup": False, "matcha": True,
        "budget": 0.5, "seed": 3, "checkpoint_every": 1, "eval_every": 0,
        "measure_comm_split": False, "savePath": save_path,
    }
    controller = Controller(ServeConfig(
        config=config, promote_every=1, backoff=0.5))
    # the hot-swap rides the first epoch boundary: published before launch
    write_control(controller.control_path, {"version": 1, "budget": 0.25})
    endpoint = ServeEndpoint({name: controller}).start()

    rc_box: dict = {}
    th = threading.Thread(
        target=lambda: rc_box.update(rc=controller.run()), daemon=True)
    th.start()
    answers: dict = {}
    deadline = time.time() + args.deadline
    while time.time() < deadline and len(answers) < 2 and th.is_alive():
        for path in ("/healthz", "/promoted"):
            code, body = _get(endpoint.port, path)
            if code == 200 and path not in answers:
                answers[path] = body
        time.sleep(0.5)
    status_code, status = _get(endpoint.port, "/status")
    write_control(controller.control_path, {"version": 2, "stop": True})
    th.join(timeout=args.deadline)
    if th.is_alive():  # the stop document was ignored — don't hang the window
        controller.shutdown()
        th.join(timeout=30.0)
    endpoint.stop()
    rc = rc_box.get("rc")

    events = read_journal(controller.journal_path) \
        if os.path.exists(controller.journal_path) else []
    controls = [{k: e.get(k) for k in ("action", "applied", "epoch",
                                       "version", "reason")}
                for e in events if e["kind"] == "control"]
    promotions = [{k: e.get(k) for k in ("action", "epoch", "metric",
                                         "serving_epoch")}
                  for e in events if e["kind"] == "promotion"]
    retraces = [e for e in events if e["kind"] == "retrace"]
    swap_applied = any(c["action"] == "apply" and c["applied"]
                       for c in controls)
    ok = (rc == 0 and "/healthz" in answers and "/promoted" in answers
          and swap_applied and not retraces)

    lines = [
        f"# serve_r{args.round}: supervised run controller",
        "",
        f"- verdict: {'OK' if ok else 'FAILED'} (daemon exit {rc}, "
        f"lifetimes {controller.lifetimes}, "
        f"restarts {controller.restarts_used})",
        f"- config: mlp/synthetic, {args.workers} workers, graphid 2, "
        f"matcha budget 0.5 -> hot-swapped 0.25 (control v1)",
        f"- hot-swap applied: {swap_applied}; retrace events: "
        f"{len(retraces)} (zero-retrace contract)",
        f"- promotions journaled: {len(promotions)}",
        "",
        "## endpoint answers",
        "",
    ]
    for path in ("/healthz", "/promoted"):
        body = json.dumps(answers.get(path), sort_keys=True, default=str)
        lines.append(f"- `{path}`: "
                     f"{'200' if path in answers else 'never 200'} {body}")
    lines.append(f"- `/status`: {status_code} "
                 f"{json.dumps(status, sort_keys=True, default=str)}")
    lines += ["", "## journaled control events", ""]
    lines += [f"- {json.dumps(c, sort_keys=True)}" for c in controls] or ["- (none)"]
    lines += ["", "## journaled promotion events", ""]
    shown = promotions[:6] + ([] if len(promotions) <= 12
                              else [None]) + promotions[-6:] \
        if len(promotions) > 12 else promotions
    lines += [f"- (... {len(promotions) - 12} more ...)" if pr is None
              else f"- {json.dumps(pr, sort_keys=True)}"
              for pr in shown] or ["- (none)"]
    lines.append("")
    with open(out, "w") as f:
        f.write("\n".join(lines))
    print(f"serve_probe: wrote {out} (verdict "
          f"{'OK' if ok else 'FAILED'})")
    shutil.rmtree(save_path, ignore_errors=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
