#!/usr/bin/env bash
# Telemetry smoke: a 5-step CPU-mesh training run with the unified
# telemetry layer on, inside a hard 60s budget — CI's proof that the
# metrics registry, the StepTimer JSONL event log and the report tool
# still work end to end.
#
# Asserts: (1) the run's JSONL event log parses line by line and holds
# one record per step; (2) fast_path_summary() equals the registry
# snapshot (the legacy views are served from the registry, no dual
# bookkeeping); (3) tools/telemetry_report.py renders the dir and exits
# 0, naming this rank's step times.
#
# Usage: tools/telemetry_smoke.sh
set -o pipefail
cd "$(dirname "$0")/.." || exit 2
REPO=$(pwd)

TDIR=$(mktemp -d /tmp/telemetry_smoke.XXXXXX)
trap 'rm -rf "$TDIR"' EXIT

# same env as testing/env.clean_cpu_env: forced CPU backend, the repo on
# PYTHONPATH
run_py() {
    timeout -k 5 50 env JAX_PLATFORMS=cpu PYTHONPATH="$REPO" \
        XLA_FLAGS="--xla_force_host_platform_device_count=4" \
        PADDLE_TELEMETRY_DIR="$TDIR" python "$@"
}

run_py - <<'PY' || { echo "telemetry_smoke: FAIL (training)" >&2; exit 1; }
import json, os
import numpy as np
import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.observability import StepTimer, metrics, aggregate

paddle.seed(0)
net = paddle.nn.Sequential(paddle.nn.Linear(16, 16), paddle.nn.Tanh(),
                           paddle.nn.Linear(16, 4))
opt = paddle.optimizer.AdamW(1e-3, parameters=net.parameters())
rng = np.random.RandomState(0)
with StepTimer(name="smoke", tokens_per_step=8 * 16) as timer:
    for step in range(5):
        x = paddle.to_tensor(rng.randn(8, 16).astype(np.float32))
        with timer.step():
            loss = (net(x) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
assert timer.steps == 5, timer.steps

# the legacy views ARE the registry: every raw counter the registry
# holds for a family must equal what fast_path_summary() serves
summary = profiler.fast_path_summary()
fams = metrics.families()
flat_summary = dict(summary)
flat_summary.update({"watchdog": summary["faults"],
                     "launch": summary["faults"],
                     "checkpoint": summary["faults"],
                     "bootstrap": summary["faults"],
                     "faults": summary["faults"]})
for fam, keys in fams.items():
    view = flat_summary.get(fam)
    if view is None:
        continue
    for k, v in keys.items():
        assert view.get(k) == v, (fam, k, v, view.get(k))
print("# registry == fast_path_summary views: OK")

aggregate.publish(step=5)        # snapshot file for the report tool
print("# prometheus export bytes:", len(metrics.to_prometheus()))
PY

# every JSONL line must parse; the log must hold 5 step records
run_py - <<PY || { echo "telemetry_smoke: FAIL (jsonl)" >&2; exit 1; }
import glob, json
steps = 0
files = glob.glob("$TDIR/events_rank*.jsonl")
assert files, "no event log written"
for path in files:
    for line in open(path):
        rec = json.loads(line)
        steps += rec.get("event") == "step"
assert steps == 5, f"expected 5 step records, found {steps}"
print("# jsonl parses:", steps, "steps")
PY

run_py tools/telemetry_report.py "$TDIR" \
    || { echo "telemetry_smoke: FAIL (report tool)" >&2; exit 1; }
run_py tools/telemetry_report.py "$TDIR" --json >/dev/null \
    || { echo "telemetry_smoke: FAIL (report --json)" >&2; exit 1; }

echo "telemetry_smoke: OK"
