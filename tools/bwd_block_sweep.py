"""Sweep flash-attention backward block sizes on the live chip.
Usage: python tools/bwd_block_sweep.py  (prints one line per variant)."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import flash_attn as fa

B, N, H, D = 4, 2048, 16, 128
rng = np.random.RandomState(0)
q = jnp.asarray(rng.randn(B, N, H, D), jnp.bfloat16)
k = jnp.asarray(rng.randn(B, N, H, D), jnp.bfloat16)
v = jnp.asarray(rng.randn(B, N, H, D), jnp.bfloat16)
do = jnp.asarray(rng.randn(B, N, H, D), jnp.bfloat16)


def timeit(fn, iters=20):
    jax.block_until_ready(fn(q, k, v, do))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(q, k, v, do)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


out, lse = jax.jit(lambda q, k, v: fa._flash_attention_tpu(
    q, k, v, True, return_lse=True))(q, k, v)
jax.block_until_ready(out)
print("lse ready", flush=True)

for bq, bk in [(128, 128), (256, 256), (512, 512), (256, 512), (512, 256)]:
    try:
        f = jax.jit(lambda q, k, v, do, bq=bq, bk=bk:
                    fa._flash_attention_bwd_tpu(q, k, v, out, lse, do, True,
                                                block_q=bq, block_k=bk))
        print(f"bwd bq={bq} bk={bk}: {timeit(f):.3f} ms", flush=True)
    except Exception as e:                                 # noqa: BLE001
        print(f"bwd bq={bq} bk={bk}: FAIL {type(e).__name__}: "
              f"{str(e)[:100]}", flush=True)

g = jax.jit(jax.grad(lambda q, k, v, do: jnp.vdot(
    fa._ref_attention(q, k, v, True).astype(jnp.float32),
    do.astype(jnp.float32)), argnums=(0, 1, 2)))
print(f"xla bwd: {timeit(lambda q, k, v, do: g(q, k, v, do)):.3f} ms",
      flush=True)
