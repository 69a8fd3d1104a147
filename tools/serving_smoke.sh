#!/usr/bin/env bash
# Serving smoke: 30 mixed-length requests through the continuous-batching
# engine on CPU, inside a hard 100s budget — CI's proof that the slot
# scheduler, the bucketed prefill ladder, the serving.* telemetry family
# and the persistent compilation cache still work end to end.
#
# Asserts: (1) all 30 requests complete with the requested token counts;
# (2) slot occupancy really exceeded 1 (continuous batching happened, not
# serial decode); (3) prefill compiles stay bounded by the bucket-ladder
# size and the decode step compiled exactly once; (4) the JSONL telemetry
# the run wrote parses line by line and holds serving_step records;
# (5) a SECOND engine in the same PADDLE_JIT_CACHE_DIR warm-starts with
# zero persistent-cache misses.
#
# Usage: tools/serving_smoke.sh
set -o pipefail
cd "$(dirname "$0")/.." || exit 2
REPO=$(pwd)

TDIR=$(mktemp -d /tmp/serving_smoke.XXXXXX)
trap 'rm -rf "$TDIR"' EXIT
mkdir -p "$TDIR/telemetry" "$TDIR/jit_cache"

# same env as testing/env.clean_cpu_env: forced CPU backend, the repo on
# PYTHONPATH
run_py() {
    timeout -k 5 90 env JAX_PLATFORMS=cpu PYTHONPATH="$REPO" \
        PADDLE_TELEMETRY_DIR="$TDIR/telemetry" \
        PADDLE_JIT_CACHE_DIR="$TDIR/jit_cache" python "$@"
}

run_py - <<'PY' || { echo "serving_smoke: FAIL (engine)" >&2; exit 1; }
import numpy as np
import jax
from paddle_tpu.models import gpt as G
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.observability import metrics

SEQ, BATCH = (8, 16), (1, 2)
cfg = G.GPTConfig(vocab_size=256, hidden_size=32, num_layers=2,
                  num_heads=2, max_seq_len=64, dtype="float32",
                  use_flash=False, remat=False)
params = G.init_params(cfg, jax.random.PRNGKey(0))
eng = ServingEngine((params, cfg), slots=4, max_len=32, seq_buckets=SEQ,
                    batch_buckets=BATCH)
rng = np.random.RandomState(0)
reqs = [eng.submit(rng.randint(1, 256, rng.randint(3, 15)).astype(np.int32),
                   int(rng.randint(3, 9))) for _ in range(30)]
done = eng.run()
st = eng.stats()
assert len(done) == 30, len(done)
for r in reqs:
    assert r.done and len(r.tokens) == r.max_new_tokens, (r.id, r.tokens)
assert st["slot_occupancy_peak"] > 1, st       # continuous batching happened
assert st["decode_compiles"] == 1, st
assert st["prefill_compiles"] <= len(SEQ) * len(BATCH), st
hits = metrics.counter("compile.persistent_cache_hits").value
miss = metrics.counter("compile.persistent_cache_misses").value
print(f"# serving_smoke: 30 requests ok, occupancy_peak="
      f"{st['slot_occupancy_peak']}, prefill_compiles="
      f"{st['prefill_compiles']}, cache hits={hits} misses={miss}")
PY

# warm restart: a fresh process over the same PADDLE_JIT_CACHE_DIR must
# reload every executable (zero persistent-cache misses)
run_py - <<'PY' || { echo "serving_smoke: FAIL (warm restart)" >&2; exit 1; }
import numpy as np
import jax
from paddle_tpu.models import gpt as G
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.observability import metrics

cfg = G.GPTConfig(vocab_size=256, hidden_size=32, num_layers=2,
                  num_heads=2, max_seq_len=64, dtype="float32",
                  use_flash=False, remat=False)
params = G.init_params(cfg, jax.random.PRNGKey(0))
eng = ServingEngine((params, cfg), slots=4, max_len=32, seq_buckets=(8, 16),
                    batch_buckets=(1, 2))
rng = np.random.RandomState(1)
for _ in range(6):
    eng.submit(rng.randint(1, 256, rng.randint(3, 15)).astype(np.int32), 4)
eng.run()
hits = metrics.counter("compile.persistent_cache_hits").value
miss = metrics.counter("compile.persistent_cache_misses").value
assert miss == 0, f"warm restart recompiled: {miss} cache misses"
assert hits > 0, "persistent cache never consulted"
print(f"# serving_smoke: warm restart ok ({hits} cache hits, 0 misses)")
PY

# every JSONL line must parse; the log must hold serving_step records
run_py - <<PY || { echo "serving_smoke: FAIL (jsonl)" >&2; exit 1; }
import glob, json
steps = 0
files = glob.glob("$TDIR/telemetry/events_rank*.jsonl")
assert files, "no event log written"
for path in files:
    for line in open(path):
        rec = json.loads(line)
        steps += rec.get("event") == "serving_step"
assert steps > 5, f"expected serving_step records, found {steps}"
print("# jsonl parses:", steps, "serving steps")
PY

echo "serving_smoke: OK"
