"""Time the grouped-query prefill attention alone on the live chip, the
two ways ``ops/pallas/flash_prefill.py``'s forward can take 128 query
heads over 8 K/V heads of 128 (the ``cohere2_moe`` serving cell's
widths, bf16): with the group factor (``group=16``: each query head's
grid steps fetch its K/V head's blocks, nothing repeated), and with K
and V repeated over each group's 16 heads by XLA first (``group=1``),
at 1 x 2048 and 1 x 4096, every row a true one, 4 layers under a scan
(each layer its own operands, so nothing is hoisted).

Usage (on one TPU v5e chip, one process):
    python tools/gqa_prefill_sweep.py [--buckets 1x2048,1x4096] [--iters 10]
One JSON line a variant and bucket: ``us`` wall clock a layer over
``--iters`` scans, each ending in ``block_until_ready`` (the repeat's
copies included for ``repeat``); ``mxu_share`` the causal 2 * b * nq *
s(s+1)/2 * (128 + 128) FLOPs over ``us`` at 197 TFLOP/s;
``max_abs_err`` of ``repeat`` against ``group``.  Fails without a TPU.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops.pallas import flash_prefill as fp

LAYERS, PEAK_FLOPS = 4, 197e12
NQ, NKV, HD = 128, 8, 128


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--buckets", default="1x2048,1x4096")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: a CPU timing is nobody's measurement")
    g = NQ // NKV

    def draw(key, shape):
        return jax.jit(lambda k: jax.random.normal(
            k, shape, jnp.float32).astype(jnp.bfloat16))(key)

    def group(q, k, v_t, lens):
        return fp._flash_prefill_tpu(q, k, v_t, lens, heads=NQ,
                                     scale=HD ** -0.5, group=g)

    def repeat(q, k, v_t, lens):
        b, s = q.shape[:2]
        k = jnp.repeat(k.reshape(b, s, NKV, 1, HD), g, 3).reshape(b, s, -1)
        v_t = jnp.repeat(v_t.reshape(b, NKV, 1, HD, s), g, 2).reshape(
            b, NQ * HD, s)
        return fp._flash_prefill_tpu(q, k, v_t, lens, heads=NQ,
                                     scale=HD ** -0.5)

    for bucket in args.buckets.split(","):
        b, s = (int(x) for x in bucket.split("x"))
        keys = jax.random.split(jax.random.PRNGKey(b * s), 3)
        q = draw(keys[0], (LAYERS, b, s, NQ * HD))
        k = draw(keys[1], (LAYERS, b, s, NKV * HD))
        v_t = draw(keys[2], (LAYERS, b, NKV * HD, s))
        lens = jnp.full((b,), s, jnp.int32)
        flops = 2 * b * NQ * s * (s + 1) // 2 * (2 * HD)
        outs = {}
        for name, attend in (("group", group), ("repeat", repeat)):
            fn = jax.jit(lambda q, k, v, attend=attend: jax.lax.map(
                lambda xs: attend(*xs, lens), (q, k, v)))
            outs[name] = jax.block_until_ready(fn(q, k, v_t))
            times = []
            for _ in range(args.iters):
                t = time.perf_counter()
                jax.block_until_ready(fn(q, k, v_t))
                times.append(time.perf_counter() - t)
            us = 1e6 * float(np.median(times)) / LAYERS
            rec = {"variant": name, "bucket": bucket, "us": round(us, 1),
                   "mxu_share": round(flops / (us * 1e-6) / PEAK_FLOPS, 4)}
            if name == "repeat":
                rec["max_abs_err"] = float(jnp.abs(
                    outs["repeat"].astype(jnp.float32)
                    - outs["group"].astype(jnp.float32)).max())
            print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
