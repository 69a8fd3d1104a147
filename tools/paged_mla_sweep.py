"""Time the latent decode kernel (``ops/pallas/paged_mla.py``) on the
live chip at the kanana2 serving cell's shapes: 64 slots, 32 heads, rank
512 + a 128-lane rope row, pages of 64, a stack of 8 layers, bf16.
Where ``paged_mla.GROUP_ROWS`` comes from (PERF.md section 6, PR 36).

Usage (through the chip tool, one process):
    python tools/paged_mla_sweep.py [--parent path/to/older/paged_mla.py]
One JSON line a variant, also appended to
``chiprun_out/paged_mla_sweep.jsonl``:

- ``rows``: rows a grid step takes (``GROUP_ROWS`` set for the
  variant), at table widths 32 (lengths 200..1,520, mean 860: the
  cell's pool fill) and 16 (lengths 100..1,000);
- ``split``: the same call with the three products skipped (the page
  traffic and the grid alone) and with the copies skipped (the
  arithmetic and the grid alone); results are wrong by construction;
- ``swap``: the score products with the query as the stationary
  operand (``c . q^T``, transposed back);
- ``parent``: an older ``paged_mla.py`` loaded beside this one.

The time a call is wall clock over ``ITERS`` scans of the kernel over
the 8 layers (the layer index traced as in the model), each ending in
``block_until_ready``; ``least_us`` is the live positions x 1,152 B
(the rows as the pool holds them: 640 values) at 819 GB/s.  Fails
without a TPU.
"""
import argparse
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import paged_mla

SLOTS, HEADS, RANK, ROPE, LANES, PS, LAYERS = 64, 32, 512, 64, 128, 64, 8
PAGES = 1017
HBM_BYTES_PER_S = 819e9
ITERS = 20
SCALE = 192 ** -0.5


def per_call_s(fn, *args):
    """Seconds a call, from a scan of the kernel over the layers."""
    def scan(*a):
        def body(c, li):
            return c + fn(*a, li)[0, 0, 0].astype(jnp.float32), None
        return jax.lax.scan(body, jnp.float32(0),
                            jnp.arange(LAYERS, dtype=jnp.int32))[0]
    run = jax.jit(scan)
    jax.block_until_ready(run(*args))
    t0 = time.perf_counter()
    for _ in range(ITERS):
        out = run(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / (ITERS * LAYERS)


def traffic(rng, width, lo, hi):
    """A page table over distinct pages and lengths in [lo, hi)."""
    lens = rng.integers(lo, hi, SLOTS).astype(np.int32)
    table = np.zeros((SLOTS, width), np.int32)
    free = rng.permutation(np.arange(1, PAGES))
    at = 0
    for s, n in enumerate(lens):
        live = n // PS + 1
        table[s, :live] = free[at:at + live]
        at += live
    assert at <= PAGES - 1
    return jnp.asarray(table), jnp.asarray(lens)


class _NoCopy:
    def start(self):
        pass

    def wait(self):
        pass


class _NoCopies:
    """``pltpu`` with ``make_async_copy`` a no-op."""
    def __init__(self, real):
        self._real = real

    def make_async_copy(self, *a):
        return _NoCopy()

    def __getattr__(self, name):
        return getattr(self._real, name)


def _no_products(a, b, contract_b):
    return jnp.broadcast_to(a[:, :1].astype(jnp.float32),
                            (a.shape[0], b.shape[1 - contract_b]))


def _swapped(real):
    def dot(a, b, contract_b):
        if contract_b == 1 and a.dtype == b.dtype == jnp.bfloat16:
            return jax.lax.dot_general(
                b, a, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32).T
        return real(a, b, contract_b)
    return dot


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=None)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: a CPU timing is nobody's measurement")
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/paged_mla_sweep.jsonl", "a")
    bf16 = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 4)

    def draw(key, shape):
        return jax.jit(lambda k: jax.random.normal(
            k, shape, jnp.float32).astype(bf16))(key)

    c_pool = draw(keys[0], (LAYERS, PAGES, PS, RANK))
    r_pool = jnp.pad(draw(keys[1], (LAYERS, PAGES, PS, ROPE)),
                     ((0, 0),) * 3 + ((0, LANES - ROPE),))
    qa = draw(keys[2], (SLOTS, HEADS, RANK))
    qr = draw(keys[3], (SLOTS, HEADS, ROPE))
    rng = np.random.default_rng(0)

    def emit(**rec):
        line = json.dumps(rec)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    def measure(name, module, table, lens, check=True, **more):
        def call(qa, qr, cp, rp, pt, ln, li):
            return module._paged_mla_tpu(qa, qr, cp, rp, pt, ln, li, SCALE)
        err = None
        if check:
            li = jnp.int32(3)
            got = jax.jit(call)(qa, qr, c_pool, r_pool, table, lens, li)
            want = jax.jit(paged_mla._ref_paged_mla, static_argnums=6)(
                qa, qr, c_pool[3], r_pool[3], table, lens, SCALE)
            err = float(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32)).max())
        sec = per_call_s(call, qa, qr, c_pool, r_pool, table, lens)
        live = int(np.asarray(lens).sum()) + SLOTS
        least = live * (RANK + LANES) * 2 / HBM_BYTES_PER_S
        emit(variant=name, table_width=int(table.shape[1]),
             live_positions=live, us=round(sec * 1e6, 1),
             least_us=round(least * 1e6, 1),
             hbm_share=round(least / sec, 3), max_abs_err=err, **more)

    wide = traffic(rng, 32, 200, 1520)
    narrow = traffic(rng, 16, 100, 1000)
    picked = paged_mla.GROUP_ROWS
    for table, lens in (wide, narrow):
        for rows in (256, 512, 1024):
            paged_mla.GROUP_ROWS = rows
            G = paged_mla.group_pages(table.shape[1], PS, RANK + LANES, 2,
                                      HEADS)
            measure("rows", paged_mla, table, lens, rows=rows, group=G)
    paged_mla.GROUP_ROWS = picked

    real_dot, real_tpu = paged_mla._dot_f32, paged_mla.pltpu
    paged_mla._dot_f32 = _no_products
    measure("split.no_products", paged_mla, *wide, check=False, rows=picked)
    paged_mla._dot_f32 = real_dot
    paged_mla.pltpu = _NoCopies(real_tpu)
    measure("split.no_copies", paged_mla, *wide, check=False, rows=picked)
    paged_mla.pltpu = real_tpu
    paged_mla._dot_f32 = _swapped(real_dot)
    measure("swap.q_stationary", paged_mla, *wide, rows=picked)
    paged_mla._dot_f32 = real_dot

    if args.parent:
        spec = importlib.util.spec_from_file_location(
            "paddle_tpu.ops.pallas.paged_mla_parent", args.parent)
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
        for table, lens in (wide, narrow):
            measure("parent", parent, table, lens)


if __name__ == "__main__":
    main()
