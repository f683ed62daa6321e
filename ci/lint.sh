#!/usr/bin/env bash
# ci/lint.sh — the static-analysis gate (ISSUE 6).
#
# Six stages, each loud on failure; the gate fails if any stage fails:
#
#   1. graftlint     GL001–GL006 (syntactic) + GL101–GL104 (SPMD dataflow)
#                    + GL201–GL203 (graftcontract) + GL301–GL304
#                    (graftdur) over the shipped surface (incl.
#                    matcha_tpu/obs and obs_tpu.py), empty baseline
#   1.5 graftcontract  GL201–GL203 in isolation: sync-budget prover
#                    against the committed sync_budget.json manifest,
#                    journal-schema call sites, checkpoint-evolution
#                    coverage — its own loud stage so a contract break is
#                    named as one, plus the contracts pytest lane
#   1.6 graftdur     GL301–GL304 in isolation: atomic-publish prover
#                    (every watched-path write through the ONE
#                    utils.atomicio.atomic_publish seam), single-writer
#                    journal + torn-tolerant readers, best-effort IO
#                    inside root-marked loops, thread-shared mutation —
#                    its own loud stage so a durability break is named as
#                    one, plus the durability pytest lane (rule triples,
#                    real-tree tamper suite, the seam under injected
#                    ENOSPC, the spec-publish squatter regression)
#   2. lint-plan     PL001–PL008 numeric verification of every committed
#                    schedule/plan artifact under benchmarks/
#   3. analysis lane the same engines + the dynamic retrace sanitizer +
#                    per-rule fixtures, as pytest (marker: analysis)
#   4. obs lane      telemetry / journal / drift / cost-ledger /
#                    overlap-truth tests (marker: obs)
#   5. obs smoke     obs_tpu.py summary over the committed reference
#                    journal — the renderer must parse what the repo ships
#   6. roofline smoke  obs_tpu.py roofline on a tiny MLP ring-4 CPU config
#                    — compiled-cost extraction must produce finite
#                    ceilings (exit 1 otherwise) and a markdown artifact
#   7. elastic lane  elastic membership (join/leave/rejoin churn e2e,
#                    policy scorer), as pytest (marker: elastic)
#   8. elasticity smoke  plan_tpu.py elasticity on a 2-event churn trace
#                    — the scorer must rank the policy grid and emit an
#                    artifact that passes its own planlint self-check
#   9. health lane   live health plane (heartbeats, anomaly detectors,
#                    watch CLI, live membership source), as pytest
#                    (marker: health)
#  10. watch smoke   obs_tpu.py watch --once on a journaled ring-4 CPU
#                    run — must emit a real per-worker table and exit 0
#                    on a healthy run (exit 1 is the flagged-fleet CI
#                    gate; a false positive here would poison it)
#  11. attribution lane  link-level attribution plane (per-matching cost
#                    estimator, link-costs artifact, timeline export,
#                    critical path), as pytest (marker: attribution)
#  11.6 overlap fixture smoke  the profile renderer must reproduce the
#                    pinned 95.0% overlap on the dbuf trace fixture
#                    (>75% acceptance floor)
#  12. attribution smoke  obs_tpu.py timeline must validate + round-trip
#                    the committed reference journal, and obs_tpu.py
#                    attribute must exit NON-zero on it (its real comm
#                    series is all-zero — an unidentifiable run failing
#                    loudly is the contract; exit 0 would mean noise was
#                    laundered into measured fact)
#  13. async lane + smoke  bounded-staleness gossip (k-deep pending ring,
#                    staleness predictor + alpha damping, local steps,
#                    fleet wall-clock model), as pytest (marker: async);
#                    then a plan_tpu.py rho --staleness smoke — the
#                    staleness-composed artifact must pass its own
#                    planlint self-check and report the damped rho < 1
#  14. serve lane + smoke  production run controller (supervised daemon,
#                    control-doc hot-swap, promotion, endpoint), as
#                    pytest (marker: serve — includes the slow kill -9
#                    crash-survival and rollback e2e); then a live
#                    serve_tpu.py daemon on a tiny MLP ring-4 run —
#                    /healthz and /promoted must answer over HTTP, a
#                    pre-published budget document must journal as
#                    applied with zero retraces, and a stop document
#                    must drain the daemon to exit 0
#
# Fast pre-commit variant: lint only what changed vs a ref —
#
#   ci/lint.sh --changed HEAD
#
# (forwards to `lint_tpu.py --changed`; plan verification and the pytest
# lane are cheap enough to always run in full).
set -u -o pipefail

cd "$(dirname "$0")/.."

CHANGED_ARGS=()
if [ "${1:-}" = "--changed" ]; then
    CHANGED_ARGS=(--changed "${2:?ci/lint.sh --changed needs a git ref}")
fi

rc=0

echo "== graftlint (GL0xx + GL1xx + GL2xx) =="
# ${arr[@]+...} expansion: empty-array-safe under `set -u` on bash < 4.4
python lint_tpu.py ${CHANGED_ARGS[@]+"${CHANGED_ARGS[@]}"} || rc=1

echo "== graftcontract (GL201-GL203 + sync_budget.json manifest) =="
python lint_tpu.py --rules GL201,GL202,GL203 \
    ${CHANGED_ARGS[@]+"${CHANGED_ARGS[@]}"} || rc=1

echo "== contracts pytest lane =="
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m pytest tests/ -q \
    -m contracts -p no:cacheprovider || rc=1

echo "== graftdur (GL301-GL304, empty baseline) =="
python lint_tpu.py --rules GL301,GL302,GL303,GL304 \
    ${CHANGED_ARGS[@]+"${CHANGED_ARGS[@]}"} || rc=1

echo "== durability pytest lane =="
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m pytest tests/ -q \
    -m durability -p no:cacheprovider || rc=1

echo "== planlint (lint-plan over benchmarks/) =="
python lint_tpu.py lint-plan || rc=1

echo "== analysis pytest lane =="
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m pytest tests/ -q \
    -m analysis -p no:cacheprovider || rc=1

echo "== obs pytest lane =="
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m pytest tests/ -q \
    -m obs -p no:cacheprovider || rc=1

echo "== obs_tpu summary smoke (reference journal) =="
python obs_tpu.py summary benchmarks/events_ring8.jsonl >/dev/null || rc=1

echo "== roofline smoke (tiny MLP ring-4, CPU provisional) =="
ROOFLINE_MD="$(mktemp)"
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python obs_tpu.py roofline \
    --workers 4 --topology ring --model mlp --dataset synthetic \
    --md "$ROOFLINE_MD" >/dev/null || rc=1
# the artifact must be a real markdown report, not an empty touch
grep -q '^# Automatic roofline' "$ROOFLINE_MD" || rc=1
rm -f "$ROOFLINE_MD"

echo "== elastic pytest lane =="
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m pytest tests/ -q \
    -m elastic -p no:cacheprovider || rc=1

echo "== elasticity smoke (2-event churn trace, ring-8) =="
ELASTIC_DIR="$(mktemp -d)"
cat > "$ELASTIC_DIR/churn.json" <<'JSON'
{"name": "ci-churn", "events": [
  {"kind": "leave",  "epoch": 1, "worker": "w3"},
  {"kind": "rejoin", "epoch": 3, "worker": "w3"}
]}
JSON
# --out arms the scorer's planlint self-check: a failing artifact exits 1
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python plan_tpu.py elasticity \
    --graphid 5 --budget 0.5 \
    --trace "$ELASTIC_DIR/churn.json" --epochs 5 --steps-per-epoch 8 \
    --mc-trials 2 --out "$ELASTIC_DIR/elasticity_plan.json" \
    >/dev/null || rc=1
rm -rf "$ELASTIC_DIR"

echo "== health pytest lane =="
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m pytest tests/ -q \
    -m health -p no:cacheprovider || rc=1

echo "== watch smoke (journaled ring-4 CPU run, healthy -> exit 0) =="
HEALTH_DIR="$(mktemp -d)"
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python train_tpu.py \
    --name watchsmoke --model mlp --dataset synthetic \
    --graphid -1 --topology ring --numworkers 4 --bs 16 --epoch 2 \
    --lr 0.05 --no-warmup --no-comm-split --save \
    --savePath "$HEALTH_DIR" >/dev/null || rc=1
WATCH_OUT="$(python obs_tpu.py watch "$HEALTH_DIR/watchsmoke_mlp" --once \
    --deadline 86400)" || rc=1
# a real table, not an empty shell: every worker row + the verdict line
for w in w0 w1 w2 w3; do
    grep -q "$w" <<<"$WATCH_OUT" || rc=1
done
grep -q 'verdict: HEALTHY' <<<"$WATCH_OUT" || rc=1
rm -rf "$HEALTH_DIR"

echo "== attribution pytest lane =="
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m pytest tests/ -q \
    -m attribution -p no:cacheprovider || rc=1

echo "== device scopes smoke (the committed v5e capture) =="
# the profile renderer on the toy program's capture from the chip must name
# its scopes from the capture's own HLO
PROFILE_OUT="$(JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python obs_tpu.py \
    profile tests/fixtures/v5e_toy.xplane.pb)" || rc=1
grep -q 'matcha/fwd_bwd' <<<"$PROFILE_OUT" || { \
    echo "device scopes smoke: no scope named: $PROFILE_OUT"; rc=1; }

echo "== attribution + timeline smoke (committed reference journal) =="
TRACE_OUT="$(mktemp)"
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python obs_tpu.py timeline \
    benchmarks/events_ring8.jsonl --out "$TRACE_OUT" >/dev/null || rc=1
grep -q 'traceEvents' "$TRACE_OUT" || rc=1
rm -f "$TRACE_OUT"
# the reference journal's REAL comm series is all-zero (CPU run,
# measure_comm_split off): attribute must exit non-zero — an
# unidentifiable run that exits 0 has laundered noise into fact
if JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python obs_tpu.py attribute \
    benchmarks/events_ring8.jsonl >/dev/null 2>&1; then
    echo "attribute smoke: expected a non-zero exit on an unidentifiable run"
    rc=1
fi

echo "== async pytest lane (bounded-staleness gossip) =="
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m pytest tests/ -q \
    -m async -p no:cacheprovider || rc=1

echo "== async smoke (plan_tpu.py rho --staleness, planlint-self-checked) =="
ASYNC_DIR="$(mktemp -d)"
# --out arms the planlint self-check (exit 1 on a failing artifact); the
# damped rho must come back < 1 — the k=2 pipeline the executor actually
# runs is stable, and the artifact must say so
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python plan_tpu.py rho \
    --graphid 5 --budget 0.5 --staleness 2 \
    --out "$ASYNC_DIR/stale_plan.json" > "$ASYNC_DIR/rho.json" || rc=1
python - "$ASYNC_DIR/rho.json" <<'PY' || rc=1
import json, sys
d = json.load(open(sys.argv[1]))
stale = d["stale"]
assert stale["staleness"] == 2, stale
assert 0 < stale["stale_alpha_scale"] < 1, stale
assert stale["rho_at_scaled_alpha"] < 1.0, stale
PY
rm -rf "$ASYNC_DIR"

echo "== serve pytest lane (incl. slow crash-survival e2e) =="
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m pytest tests/ -q \
    -m serve -p no:cacheprovider || rc=1

echo "== serve smoke (live daemon: hot-swap, /healthz, /promoted, stop) =="
SERVE_DIR="$(mktemp -d)"
cat > "$SERVE_DIR/config.json" <<'JSON'
{"name": "servesmoke", "model": "mlp", "dataset": "synthetic",
 "dataset_kwargs": {"num_train": 128, "num_test": 32},
 "num_workers": 4, "graphid": null, "topology": "ring",
 "batch_size": 16, "epochs": 100000, "lr": 0.05, "warmup": false,
 "matcha": true, "budget": 0.5, "seed": 3, "checkpoint_every": 1,
 "eval_every": 0, "measure_comm_split": false}
JSON
# publish the hot-swap BEFORE launch: it must apply at the first epoch
# boundary, as a journaled value update with zero retraces
python serve_tpu.py control --out "$SERVE_DIR/control.json" \
    --version 1 --budget 0.25 >/dev/null || rc=1
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python serve_tpu.py run \
    --config "$SERVE_DIR/config.json" --save-path "$SERVE_DIR" \
    --promote-every 1 --backoff 0.5 > "$SERVE_DIR/serve.log" 2>&1 &
SERVE_PID=$!
# the endpoint prints its ephemeral port at startup
PORT=""
for _ in $(seq 1 100); do
    PORT="$(sed -n 's|.*endpoint on http://[^:]*:\([0-9]*\).*|\1|p' \
        "$SERVE_DIR/serve.log" | head -1)"
    [ -n "$PORT" ] && break
    sleep 0.2
done
[ -n "$PORT" ] || { echo "serve smoke: endpoint never announced"; rc=1; }
# poll /healthz until the first heartbeat lands (200), and /promoted
# until the first promotion verifies (200) — both over real HTTP
[ -z "$PORT" ] || python - "$PORT" <<'PY' || rc=1
import json, sys, time, urllib.error, urllib.request
port = sys.argv[1]

def get(path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=5) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())
    except OSError:
        return None, None

deadline = time.time() + 240
ok = {}
while time.time() < deadline and len(ok) < 2:
    for path in ("/healthz", "/promoted"):
        code, body = get(path)
        if code == 200 and path not in ok:
            ok[path] = body
    time.sleep(0.5)
assert "/healthz" in ok, "healthz never went 200"
assert ok["/healthz"]["ok"] and ok["/healthz"]["verdict"] == 0
assert "/promoted" in ok, "promoted never went 200"
assert ok["/promoted"]["verified"]
code, body = get("/status")
assert code == 200 and body["trainer_alive"], body
PY
# clean shutdown through the operator path: a stop document drains the
# run and the daemon exits 0 (epochs is set far out of reach, so the
# stop document is the only way this run ends)
python serve_tpu.py control --out "$SERVE_DIR/control.json" \
    --version 2 --stop >/dev/null || rc=1
for _ in $(seq 1 600); do
    kill -0 "$SERVE_PID" 2>/dev/null || break
    sleep 0.2
done
if kill -0 "$SERVE_PID" 2>/dev/null; then
    echo "serve smoke: daemon ignored the stop document"
    kill -9 "$SERVE_PID" 2>/dev/null
    rc=1
fi
SERVE_RC=0
wait "$SERVE_PID" || SERVE_RC=$?
[ "$SERVE_RC" -eq 0 ] || { \
    echo "serve smoke: daemon exit $SERVE_RC"; cat "$SERVE_DIR/serve.log"; \
    rc=1; }
# the journal must carry the applied hot-swap, the stop, at least one
# promotion — and no retrace events (the zero-retrace contract)
python - "$SERVE_DIR/servesmoke_mlp/events.jsonl" <<'PY' || rc=1
import sys
from matcha_tpu.obs import read_journal
events = read_journal(sys.argv[1])
controls = [(e["action"], e["applied"]) for e in events
            if e["kind"] == "control"]
assert ("apply", True) in controls, controls
assert ("stop", True) in controls, controls
assert any(e["kind"] == "promotion" for e in events)
assert not [e for e in events if e["kind"] == "retrace"]
PY
# the serving directory must audit clean end-to-end
python serve_tpu.py verify "$SERVE_DIR/servesmoke_serving" \
    >/dev/null || rc=1
rm -rf "$SERVE_DIR"

echo "== chaos pytest lane (fast units) =="
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m pytest tests/ -q \
    -m 'chaos and not slow' -p no:cacheprovider || rc=1

echo "== chaos smoke (corrupt-latest + kill-mid-save + spec-squat trials) =="
# seed 0 = ckpt_bitflip (the ladder must recover from an older
# generation charging zero restarts), seed 7 = kill_mid_save (resume
# must match the uninterrupted twin exactly), seed 13 = spec_torn_tmp
# (a directory squatting on the old fixed-name spec tempfile — the
# mkstemp publish must sail past it with zero restarts: the GL301
# bugfix's end-to-end regression); replay exits non-zero when any
# invariant is violated
CHAOS_DIR="$(mktemp -d)"
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python chaos_tpu.py replay \
    --seed 0 --workdir "$CHAOS_DIR" >/dev/null || rc=1
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python chaos_tpu.py replay \
    --seed 7 --workdir "$CHAOS_DIR" >/dev/null || rc=1
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python chaos_tpu.py replay \
    --seed 13 --workdir "$CHAOS_DIR" >/dev/null || rc=1
rm -rf "$CHAOS_DIR"

exit $rc
