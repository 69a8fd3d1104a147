"""Sweep bench variants on the live chip (run each in a fresh process).

Usage: python tools/bench_sweep.py '<variant-json>'
  variant keys: hidden, layers, heads, seq, batch, steps, remat (bool),
  remat_policy, param_dtype, moment_dtype, disable_pallas
Prints one JSON result line.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

v = json.loads(sys.argv[1]) if len(sys.argv) > 1 else {}
if v.get("disable_pallas"):
    os.environ["PADDLE_TPU_DISABLE_PALLAS"] = "1"

import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.framework import jax_compat
from paddle_tpu.parallel.mesh import create_mesh
from paddle_tpu.models import gpt, gpt_hybrid

jax_compat.enable_persistent_cache(jax_compat.checkout_cache_dir())

cfg = gpt.GPTConfig(
    vocab_size=50304,
    hidden_size=v.get("hidden", 2048),
    num_layers=v.get("layers", 24),
    num_heads=v.get("heads", 16),
    max_seq_len=v.get("seq", 2048),
    param_dtype=v.get("param_dtype", "bfloat16"),
    remat=v.get("remat", True),
    remat_policy=v.get("remat_policy", "full"),
)
batch = v.get("batch", 4)
steps = v.get("steps", 8)
moment_dtype = jnp.dtype(v.get("moment_dtype", "bfloat16"))

dev = jax.devices()[0]
mesh = create_mesh(dp=1, tp=1, pp=1, sp=1, devices=[dev])
params, m, mv = gpt_hybrid.init_sharded(cfg, mesh, jax.random.PRNGKey(0),
                                        moment_dtype=moment_dtype)
step = gpt_hybrid.make_train_step(cfg, mesh, n_microbatch=1,
                                  xent_chunks=v.get("xent_chunks", 1))
N = cfg.max_seq_len
toks = jnp.asarray(np.random.RandomState(0).randint(
    0, cfg.vocab_size, (batch, N)), jnp.int32)
lr = jnp.float32(1e-4)

params, m, mv, loss = step(params, m, mv, jnp.int32(1), toks, toks, lr)
jax.block_until_ready(loss)
t0 = time.perf_counter()
for i in range(steps):
    params, m, mv, loss = step(params, m, mv, jnp.int32(i + 2), toks, toks, lr)
jax.block_until_ready(loss)
dt = time.perf_counter() - t0
fl = float(loss)
tps = batch * N * steps / dt
from bench import _peak_flops_kind
mfu = tps * cfg.flops_per_token() / _peak_flops_kind(dev.device_kind)
print(json.dumps({"variant": v, "tokens_per_sec": round(tps, 1),
                  "mfu": round(mfu, 4), "loss": round(fl, 4),
                  "step_ms": round(dt / steps * 1e3, 1)}))
