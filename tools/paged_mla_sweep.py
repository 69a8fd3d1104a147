"""Time the latent decode kernel (``ops/pallas/paged_mla.py``) on the
live chip at a serving cell's shapes: rank 512 + a 128-lane rope row in
one pool row of 640, pages of 64, a stack of 8 layers, bf16 —

- ``--shape kanana2`` (the default): 64 slots, 32 heads, lengths
  200..1,520 over a table of 32 and 100..1,000 over a table of 16;
  where ``paged_mla.GROUP_ROWS`` comes from (PERF.md section 6);
- ``--shape longcat``: 200 slots, 64 heads, a table of 64, 5,160 pages,
  lengths drawn from ``serve-longcat-omni-reason-zipf``'s laws (a slot
  holds a request for as long as its answer runs, so requests are drawn
  by their answer's length and a slot sits at a uniform point of it).

Usage (through the chip tool, one process):
    python tools/paged_mla_sweep.py [--shape longcat]
        [--parent path/to/older/paged_mla.py]
One JSON line a variant, also appended to
``chiprun_out/paged_mla_sweep.jsonl``:

- ``rows`` (kanana2 only): rows a grid step takes (``GROUP_ROWS`` set
  for the variant) at both tables;
- ``whole`` / ``split.no_products`` / ``split.no_copies``: the call,
  the same call with the three products skipped (the page traffic and
  the grid alone) and with the copies skipped (the arithmetic and the
  grid alone; results are wrong by construction), for this kernel and,
  with ``--parent``, for an older ``paged_mla.py`` loaded beside it
  (its pool as the two arrays ``[.., 512]`` and ``[.., 128]`` where it
  takes two);
- ``swap`` (kanana2 only): the score products with the query as the
  stationary operand (``c . q^T``, transposed back).

A ``--parent`` line's ``equal_to_change`` says whether its output equals
this kernel's bit for bit on the same pool contents.

Each line carries ``descriptors``, the DMA starts and waits one call
issues on the scalar core (``paged_mla.copy_descriptors``; 4 a live
page for a kernel that takes two arrays), and that over the live pages.
The time a call is wall clock over ``ITERS`` scans of the kernel over
the 8 layers (the layer index traced as in the model), each ending in
``block_until_ready``; ``least_us`` is the live positions x 1,152 B
(the rows as the pool holds them: 640 values) at 819 GB/s.  Fails
without a TPU.
"""
import argparse
import importlib.util
import inspect
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import paged_mla

RANK, ROPE, LANES, PS, LAYERS = 512, 64, 128, 64, 8
SHAPES = {"kanana2": dict(slots=64, heads=32, pages=1017),
          "longcat": dict(slots=200, heads=64, pages=5160)}
SLOTS, HEADS, PAGES = 64, 32, 1017      # set from --shape in main()
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
    return table_for(rng, width, rng.integers(lo, hi, SLOTS))


def cell_lengths(rng, width):
    """Slot lengths of a full house of ``serve-longcat-omni-reason-zipf``
    (benchmark/traffic/backlog-reason-1k-zipf.json): prompts lognormal
    median 256, sigma 0.6 in [64, 1024], answers median 1024, sigma 0.6
    in [128, 3072]; a request is drawn by its answer's length (what it
    holds a slot for) and sits at a uniform point of its answer."""
    def law(median, lo, hi, n):
        return np.clip(np.round(median * np.exp(0.6 * rng.standard_normal(
            n))), lo, hi).astype(np.int64)
    prompt = law(256, 64, 1024, 100 * SLOTS)
    answer = law(1024, 128, 3072, 100 * SLOTS)
    pick = rng.choice(len(answer), SLOTS, p=answer / answer.sum())
    lens = prompt[pick] + rng.integers(0, answer[pick])
    return np.minimum(lens, width * PS - 1)


def table_for(rng, width, lens):
    lens = np.asarray(lens).astype(np.int32)
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


def two_arrays(module):
    """Whether ``module``'s kernel takes the pool as two arrays."""
    return "r_pool" in inspect.signature(module._paged_mla_tpu).parameters


def descriptors(module, lens, width, heads):
    """DMA starts and waits one call issues, and its live pages."""
    if two_arrays(module):
        pages = int((np.asarray(lens) // PS + 1).sum())
        return 4 * pages, pages
    G = paged_mla.group_pages(width, PS, RANK + LANES, 2, heads)
    return paged_mla.copy_descriptors(lens, G, PS)


def main():
    global SLOTS, HEADS, PAGES
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", choices=sorted(SHAPES), default="kanana2")
    ap.add_argument("--parent", default=None)
    args = ap.parse_args()
    shape = SHAPES[args.shape]
    SLOTS, HEADS, PAGES = shape["slots"], shape["heads"], shape["pages"]
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: a CPU timing is nobody's measurement")
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/paged_mla_sweep.jsonl", "a")
    bf16 = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 4)

    def draw(key, shape):
        return jax.jit(lambda k: jax.random.normal(
            k, shape, jnp.float32).astype(bf16))(key)

    # one pool of rows: c, then the rope key padded to a lane row
    pool = jnp.concatenate([
        draw(keys[0], (LAYERS, PAGES, PS, RANK)),
        jnp.pad(draw(keys[1], (LAYERS, PAGES, PS, ROPE)),
                ((0, 0),) * 3 + ((0, LANES - ROPE),))], -1)
    qa = draw(keys[2], (SLOTS, HEADS, RANK))
    qr = draw(keys[3], (SLOTS, HEADS, ROPE))
    rng = np.random.default_rng(0)
    parent = halves = None
    if args.parent:
        spec = importlib.util.spec_from_file_location(
            "paddle_tpu.ops.pallas.paged_mla_parent", args.parent)
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
        if two_arrays(parent):
            halves = (pool[..., :RANK], pool[..., RANK:])

    def emit(**rec):
        line = json.dumps(rec)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    outputs = {}

    def measure(name, module, table, lens, check=True, copies=True,
                **more):
        pools = halves if two_arrays(module) else (pool,)

        def call(qa, qr, pt, ln, *pools_layer):
            *pools, li = pools_layer
            return module._paged_mla_tpu(qa, qr, *pools, pt, ln, li, SCALE)
        err = same = None
        if check:
            got = jax.jit(call)(qa, qr, table, lens, *pools, jnp.int32(3))
            want = jax.jit(paged_mla._ref_paged_mla, static_argnums=5)(
                qa, qr, pool[3], table, lens, SCALE)
            err = float(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32)).max())
            # the parent's output against this kernel's, bit for bit
            key = (name, int(table.shape[1]))
            if module is paged_mla:
                outputs[key] = np.asarray(got)
            elif key in outputs:
                same = bool(np.array_equal(outputs[key], np.asarray(got)))
        sec = per_call_s(call, qa, qr, table, lens, *pools)
        live = int(np.asarray(lens).sum()) + SLOTS
        least = live * (RANK + LANES) * 2 / HBM_BYTES_PER_S
        n, pages = (descriptors(module, lens, table.shape[1], HEADS)
                    if copies else (0, 0))
        emit(variant=name, shape=args.shape, heads=HEADS, slots=SLOTS,
             table_width=int(table.shape[1]), live_positions=live,
             mean_len=round(float(np.mean(lens)), 1),
             us=round(sec * 1e6, 1), least_us=round(least * 1e6, 1),
             hbm_share=round(least / sec, 3), max_abs_err=err,
             equal_to_change=same, descriptors=n,
             descriptors_per_page=round(n / pages, 4) if pages else None,
             kernel="parent" if module is parent else "change", **more)

    if args.shape == "longcat":
        tables = [table_for(rng, 64, cell_lengths(rng, 64))]
    else:
        tables = [traffic(rng, 32, 200, 1520), traffic(rng, 16, 100, 1000)]
    picked = paged_mla.GROUP_ROWS
    if args.shape == "kanana2":
        for table, lens in tables:
            for rows in (256, 512, 1024):
                paged_mla.GROUP_ROWS = rows
                G = paged_mla.group_pages(table.shape[1], PS, RANK + LANES,
                                          2, HEADS)
                measure("rows", paged_mla, table, lens, rows=rows, group=G)
        paged_mla.GROUP_ROWS = picked

    first = tables[0]
    for module in [paged_mla] + ([parent] if parent else []):
        for table, lens in tables:
            measure("whole", module, table, lens, rows=picked)
        real_dot, real_tpu = module._dot_f32, module.pltpu
        module._dot_f32 = _no_products
        measure("split.no_products", module, *first, check=False,
                rows=picked)
        module._dot_f32 = real_dot
        module.pltpu = _NoCopies(real_tpu)
        measure("split.no_copies", module, *first, check=False,
                copies=False, rows=picked)
        module.pltpu = real_tpu
    if args.shape == "kanana2":
        real_dot = paged_mla._dot_f32
        paged_mla._dot_f32 = _swapped(real_dot)
        measure("swap.q_stationary", paged_mla, *first, rows=picked)
        paged_mla._dot_f32 = real_dot


if __name__ == "__main__":
    main()
