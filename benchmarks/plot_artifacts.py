#!/usr/bin/env python
"""Render the committed experiment artifacts as figures.

The reference's only observability is per-rank CSV logs the user eyeballs
(/root/reference/util.py:378-419); the paper's results are accuracy-vs-epoch
and accuracy-vs-communication figures.  This tool closes that gap for the
artifacts this repo commits:

* ``budget_sweep.json``  → test-accuracy vs epoch, one line per run
* ``time_to_acc.json``   → accuracy curves + wall-clock-to-target bars with
                           the comm/compute split that carries the artifact's
                           finding (comm is ~2% on-chip, CHOCO's encode ~26%)
* ``baselines_converge.jsonl`` → the converge-tier curves (64-worker
                           compression study: CHOCO's shard-size plateau vs
                           the uncompressed control reaching target)
* a Recorder run dir (``--run-dir``) → the reference-compatible CSV series

Design notes: colors are assigned to *entities* (dpsgd, matcha-0.5, ...) via
a fixed map so the same run wears the same hue in every figure; single hue
order from a colorblind-validated categorical palette; one y-axis per figure;
the numeric tables remain the committed JSONs (this renders, never replaces).

Output: PNGs under ``benchmarks/plots/`` (or ``--out-dir``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import matplotlib

matplotlib.use("Agg")
import matplotlib.pyplot as plt  # noqa: E402

# fixed entity → hue map (validated categorical palette, fixed slot order;
# color follows the run identity, never its rank in any one figure)
COLORS = {
    "dpsgd": "#2a78d6",
    "matcha-0.5": "#eb6834",
    "choco-0.5": "#1baf7a",
    "matcha-0.1": "#eda100",
    "matcha-0.25": "#e87ba4",
    "matcha-1.0": "#008300",
    # backend variants wear their parent algorithm's hue (same entity; the
    # bar tick label carries the backend distinction)
    "dpsgd-skip": "#2a78d6",
    "matcha-0.5-skip": "#eb6834",
}
INK = "#0b0b0b"
INK_2 = "#52514e"
GRID = "#e5e4e0"


def _style(ax, title, xlabel, ylabel):
    ax.set_title(title, color=INK, fontsize=11, loc="left")
    ax.set_xlabel(xlabel, color=INK_2, fontsize=9)
    ax.set_ylabel(ylabel, color=INK_2, fontsize=9)
    ax.grid(True, color=GRID, linewidth=0.8, zorder=0)
    for side in ("top", "right"):
        ax.spines[side].set_visible(False)
    for side in ("left", "bottom"):
        ax.spines[side].set_color(GRID)
    ax.tick_params(colors=INK_2, labelsize=8)


def _acc_axes(ax, runs, title, target=None, dashed=()):
    # runs named in ``dashed`` draw last with a dash pattern: used when two
    # runs provably coincide (budget 1.0 ≡ D-PSGD: same flags, same seed) so
    # the covered line stays visible instead of silently vanishing
    for r in sorted(runs, key=lambda r: r["run"] in dashed):
        curve = r["test_acc_curve"]
        epochs = range(1, len(curve) + 1)
        c = COLORS.get(r["run"], INK_2)
        style = dict(linestyle=(0, (4, 3)), zorder=4) if r["run"] in dashed \
            else dict(zorder=3)
        ax.plot(epochs, curve, color=c, linewidth=2, label=r["run"], **style)
    if target is not None:
        ax.axhline(target, color=INK_2, linewidth=1, linestyle=(0, (4, 3)),
                   zorder=2)
        ax.annotate(f"target {target}", xy=(1, target),
                    xytext=(2, -10), textcoords="offset points",
                    color=INK_2, fontsize=8)
    _style(ax, title, "epoch", "test accuracy")
    ax.set_ylim(0.0, 1.05)
    ax.legend(frameon=False, fontsize=8, labelcolor=INK_2, loc="lower right")


def plot_budget_sweep(path, out_dir):
    with open(path) as f:
        d = json.load(f)
    fig, ax = plt.subplots(figsize=(6.4, 4.0), dpi=150)
    _acc_axes(ax, d["runs"],
              "MATCHA budget sweep vs D-PSGD — test accuracy by epoch",
              dashed=("dpsgd",))
    out = os.path.join(out_dir, "budget_sweep.png")
    fig.tight_layout()
    fig.savefig(out)
    plt.close(fig)
    return out


def plot_time_to_acc(path, out_dir):
    with open(path) as f:
        d = json.load(f)
    runs = d["runs"]
    fig, (ax1, ax2) = plt.subplots(
        1, 2, figsize=(10.0, 4.0), dpi=150,
        gridspec_kw={"width_ratios": [3, 2]})
    # backend variants (-skip) rerun the same experiment through a different
    # compiled program: same seed, but f32 reassociation drifts the
    # trajectory — shown dashed in the parent algorithm's hue
    _acc_axes(ax1, runs, "Accuracy by epoch", target=d["target_acc"],
              dashed=tuple(r["run"] for r in runs if r["run"].endswith("-skip")))

    # wall-clock to target, split into comm + everything else (the artifact's
    # finding lives in this split); white seams keep segments separable
    reached = [r for r in runs if r["reached"]]
    if not reached:
        # a legitimate artifact shape (--target too high for --epochs):
        # keep the accuracy panel, say so in the empty bars panel
        ax2.text(0.5, 0.5, "no run reached the target", transform=ax2.transAxes,
                 ha="center", color=INK_2, fontsize=9)
        _style(ax2, f"Wall-clock to {d['target_acc']} accuracy", "seconds", "")
        fig.tight_layout()
        out = os.path.join(out_dir, "time_to_acc.png")
        fig.savefig(out)
        plt.close(fig)
        return out
    ys = range(len(reached))
    comm = [r["comm_time_to_target_s"] for r in reached]
    rest = [r["time_to_target_s"] - r["comm_time_to_target_s"] for r in reached]
    cols = [COLORS.get(r["run"], INK_2) for r in reached]
    # color follows the run; the comm component is the same hue with a
    # texture (not a new color), so the split never reads as a new entity
    ax2.barh(ys, rest, height=0.55, color=cols,
             edgecolor="white", linewidth=1.5, zorder=3)
    ax2.barh(ys, comm, left=rest, height=0.55, color=cols, hatch="///",
             edgecolor="white", linewidth=1.5, zorder=3)
    from matplotlib.patches import Patch

    legend_handles = [
        Patch(facecolor=INK_2, label="compute + eval"),
        Patch(facecolor=INK_2, hatch="///", edgecolor="white", label="comm"),
    ]
    for y, r in zip(ys, reached):
        ax2.annotate(
            f"{r['time_to_target_s']:.0f} s · {r['epochs_to_target']} ep · "
            f"comm {100 * r['comm_time_to_target_s'] / r['time_to_target_s']:.0f}%",
            xy=(r["time_to_target_s"], y), xytext=(4, 0),
            textcoords="offset points", va="center", color=INK_2, fontsize=8)
    ax2.set_yticks(list(ys), [r["run"] for r in reached])
    _style(ax2, f"Wall-clock to {d['target_acc']} accuracy", "seconds", "")
    ax2.set_xlim(0, max(r["time_to_target_s"] for r in reached) * 1.45)
    # below the axes, right-aligned: every in-axes or title-row placement
    # collides with a bar annotation or the title at some data shape
    ax2.legend(handles=legend_handles, frameon=False, fontsize=8,
               labelcolor=INK_2, loc="upper right", ncols=2,
               bbox_to_anchor=(1.0, -0.14), borderaxespad=0.0)
    fig.tight_layout()
    out = os.path.join(out_dir, "time_to_acc.png")
    fig.savefig(out, bbox_inches="tight")  # include the below-axes legend
    plt.close(fig)
    return out


def plot_baselines_converge(path, out_dir):
    """Converge-tier curves from the JSONL (one record per run; repeated
    configs are distinct attempts and get an ``#k`` suffix).  Entities here
    are configs, not the sweep algorithms — hues assigned by first
    appearance from the same fixed palette order."""
    with open(path) as f:
        records = [json.loads(line) for line in f if line.strip()]
    records = [r for r in records if "test_acc_curve" in r]
    if not records:
        # smoke/error records carry no curves: nothing to draw is a benign
        # outcome for this artifact, not a crash (main() keeps going)
        print(f"# no converge records with curves in {path}", file=sys.stderr)
        return None
    palette = list(dict.fromkeys(COLORS.values()))  # dedupe aliased hues
    # repeat attempts of one config share its hue but get progressively
    # sparser dashes so #2 and #3 stay tellable apart
    dashes = ["-", (0, (4, 3)), (0, (1, 2)), (0, (6, 2, 1, 2))]
    seen: dict = {}
    fig, ax = plt.subplots(figsize=(7.2, 4.2), dpi=150)
    for r in records:
        n = seen.setdefault(r["config"], {"count": 0,
                                          "color": palette[len(seen) % len(palette)]})
        n["count"] += 1
        label = r["config"] if n["count"] == 1 else f"{r['config']} #{n['count']}"
        curve = r["test_acc_curve"]
        ax.plot(range(1, len(curve) + 1), curve, color=n["color"], linewidth=2,
                linestyle=dashes[(n["count"] - 1) % len(dashes)],
                label=label, zorder=3)
    target = records[0].get("target_acc")
    if target is not None:
        ax.axhline(target, color=INK_2, linewidth=1, linestyle=(0, (4, 3)),
                   zorder=2)
        ax.annotate(f"target {target}", xy=(1, target), xytext=(2, -10),
                    textcoords="offset points", color=INK_2, fontsize=8)
    _style(ax, "Converge tier — test accuracy by epoch", "epoch",
           "test accuracy")
    ax.set_ylim(0.0, 1.05)
    # center-right: upper-left collides with the target annotation, and the
    # curves cluster along the bottom and the upper-right corner
    ax.legend(frameon=False, fontsize=8, labelcolor=INK_2, loc="center right")
    out = os.path.join(out_dir, "baselines_converge.png")
    fig.tight_layout()
    fig.savefig(out)
    plt.close(fig)
    return out


def plot_run_dir(run_dir, out_dir):
    """Plot a Recorder output dir — the reference's per-rank series naming
    (util.py:410-416): ``*-tacc.log`` test accuracy, ``*-losses.log`` train
    loss, one float per line per epoch, one file per rank.  All ranks are one
    entity (the same measure), so they share one hue at reduced opacity."""
    import glob

    tacc_files = sorted(glob.glob(os.path.join(run_dir, "*-tacc.log")))
    loss_files = sorted(glob.glob(os.path.join(run_dir, "*-losses.log")))
    if not tacc_files and not loss_files:
        raise FileNotFoundError(f"no Recorder *-tacc.log / *-losses.log in {run_dir}")
    fig, (ax1, ax2) = plt.subplots(1, 2, figsize=(10.0, 4.0), dpi=150)
    for ax, files, name in ((ax1, tacc_files, "test accuracy"),
                            (ax2, loss_files, "train loss")):
        for f in files:
            with open(f) as fh:
                series = [float(v) for v in fh if v.strip()]
            ax.plot(range(1, len(series) + 1), series, color=COLORS["dpsgd"],
                    alpha=max(0.25, 1.0 / max(len(files), 1)),
                    linewidth=2, zorder=3)
        _style(ax, f"{name} ({len(files)} ranks)", "epoch", name)
    fig.tight_layout()
    out = os.path.join(out_dir, "recorder_run.png")
    fig.savefig(out)
    plt.close(fig)
    return out


def main():
    here = os.path.dirname(os.path.abspath(__file__))
    p = argparse.ArgumentParser()
    p.add_argument("--sweep", default=os.path.join(here, "budget_sweep.json"))
    p.add_argument("--tta", default=os.path.join(here, "time_to_acc.json"))
    p.add_argument("--converge",
                   default=os.path.join(here, "baselines_converge.jsonl"))
    p.add_argument("--run-dir", default=None,
                   help="a Recorder output dir to plot instead of the artifacts")
    p.add_argument("--out-dir", default=os.path.join(here, "plots"))
    args = p.parse_args()

    os.makedirs(args.out_dir, exist_ok=True)
    outs = []
    if args.run_dir:
        outs.append(plot_run_dir(args.run_dir, args.out_dir))
    else:
        if os.path.exists(args.sweep):
            outs.append(plot_budget_sweep(args.sweep, args.out_dir))
        if os.path.exists(args.tta):
            outs.append(plot_time_to_acc(args.tta, args.out_dir))
        if os.path.exists(args.converge):
            out = plot_baselines_converge(args.converge, args.out_dir)
            if out:
                outs.append(out)
    for o in outs:
        print(o)
    if not outs:
        print("nothing to plot", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
