"""Time the experts' grouped matmul on the live chip, kernel against
``jax.lax.ragged_dot``, at kanana-2-30b-a3b's expert shapes (128 experts
of [2048, 768] / [768, 2048], a stack of 7 layers) and the row counts
the serving cell's programs have (64 slots x 6 for decode; the prefill
buckets x 6).  Where ``ops/pallas/grouped_matmul.py``'s tile constants
come from (PERF.md section 6, PR 34).

Usage (through the chip tool, one process):
    python tools/grouped_matmul_sweep.py [rows,rows,...]
One JSON line a variant: the time a call (a scan over the 7 layers, the
layer index traced as in the model, wall clock over ``ITERS`` scans that
end in ``block_until_ready``), the bytes of the experts touched and
their share of 819 GB/s.  Fails without a TPU.
"""
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import grouped_matmul as gm

LAYERS, E, H, I, TOP_K = 7, 128, 2048, 768, 6
HBM_BYTES_PER_S = 819e9
ITERS = 10
WINDOWS = {384: (16, 32, 64, 128), 768: (32, 64, 128),
           3072: (64, 128, 256), 6144: (128, 256),
           12288: (128, 256, 512), 24576: (128, 256, 512)}


def routed_sizes(rng, rows):
    """Group sizes of ``rows / TOP_K`` tokens that each pick TOP_K
    distinct experts evenly (the cell's seeded routers are near even:
    95% of 128 touched, max / mean 2.8 at 64 tokens)."""
    picks = np.concatenate([rng.permutation(E)[:TOP_K]
                            for _ in range(rows // TOP_K)])
    return np.bincount(picks, minlength=E).astype(np.int32)


def per_call_s(fn, *args):
    """Seconds a call, from a scan of the op over the stack's layers."""
    def scan(*a):
        def body(c, li):
            return c + fn(*a, li)[0, 0].astype(jnp.float32), None
        return jax.lax.scan(body, jnp.float32(0),
                            jnp.arange(LAYERS, dtype=jnp.int32))[0]
    run = jax.jit(scan)
    jax.block_until_ready(run(*args))
    t0 = time.perf_counter()
    for _ in range(ITERS):
        out = run(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / (ITERS * LAYERS)


def main():
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: a CPU timing is nobody's measurement")
    rows_list = ([int(r) for r in sys.argv[1].split(",")]
                 if len(sys.argv) > 1 else sorted(WINDOWS))
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/grouped_matmul_sweep.jsonl", "a")
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    bf16 = jnp.bfloat16

    def draw(key, shape, scale=0.02):
        return jax.jit(lambda k: (jax.random.normal(k, shape, jnp.float32)
                                  * scale).astype(bf16))(key)

    gate = draw(keys[0], (LAYERS, E, H, I))
    up = draw(keys[1], (LAYERS, E, H, I))
    down = draw(keys[2], (LAYERS, E, I, H))
    rng = np.random.default_rng(0)

    def emit(**rec):
        line = json.dumps(rec)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    for rows in rows_list:
        sizes_np = routed_sizes(rng, rows)
        sizes = jnp.asarray(sizes_np)
        touched = int((sizes_np > 0).sum())
        shape = dict(rows=rows, touched=touched,
                     max_over_mean=round(float(sizes_np.max()
                                               / sizes_np.mean()), 2))
        xs = draw(keys[3], (rows, H), 1.0)
        mid = draw(keys[3], (rows, I), 1.0)
        expert_bytes = touched * H * I * 2

        def report(name, seconds, stacks, **more):
            emit(op=name, **shape, **more, us=round(seconds * 1e6, 1),
                 expert_mb=round(stacks * expert_bytes / 1e6, 1),
                 hbm_share=round(stacks * expert_bytes / HBM_BYTES_PER_S
                                 / seconds, 3))

        # the compiler's: the three products as the parent runs them
        report("ragged_dot.gate", per_call_s(gm._ref_grouped, xs, sizes,
                                             gate), 1)
        report("ragged_dot.down", per_call_s(gm._ref_grouped, mid, sizes,
                                             down), 1)
        # (operands, never closed over: a captured stack is a constant
        # of the program, copied on the host)
        li = jnp.int32(3)
        want_gu = jax.jit(lambda x, s, g, u, i: (
            jax.nn.silu(gm._ref_grouped(x, s, g, i))
            * gm._ref_grouped(x, s, u, i)).astype(bf16))(
                xs, sizes, gate, up, li)
        want_down = jax.jit(gm._ref_grouped)(mid, sizes, down, li)
        for tm in WINDOWS.get(rows, (gm.window_rows(rows, 2),)):
            def kernel(x, s, *rest, **kw):
                *stacks, li = rest
                return gm._grouped_tpu(x, s, tuple(stacks), li, tm=tm, **kw)
            gu = functools.partial(kernel, gate_up=True)
            err_gu = float(jnp.abs(
                jax.jit(gu)(xs, sizes, gate, up, li)
                .astype(jnp.float32) - want_gu.astype(jnp.float32)).max())
            err_down = float(jnp.abs(
                jax.jit(kernel)(mid, sizes, down, li)
                - want_down).max())
            report("kernel.gate", per_call_s(kernel, xs, sizes, gate), 1,
                   window=tm)
            report("kernel.gate_up", per_call_s(gu, xs, sizes, gate, up), 2,
                   window=tm, max_abs_err=err_gu,
                   ref_abs_max=float(jnp.abs(want_gu.astype(
                       jnp.float32)).max()))
            report("kernel.down", per_call_s(kernel, mid, sizes, down), 1,
                   window=tm, max_abs_err=err_down,
                   ref_abs_max=float(jnp.abs(want_down).max()))


if __name__ == "__main__":
    main()
