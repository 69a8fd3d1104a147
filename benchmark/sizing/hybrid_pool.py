#!/usr/bin/env python3
"""Size a HYBRID model's slots and page pool to the chip, WITHOUT the
chip: a family that keeps most of a sequence's state per SLOT (window
rings, state-space state) beside a small page pool (one layer's K/V).

    JAX_PLATFORMS=cpu python3 benchmark/sizing/hybrid_pool.py \
        --config benchmark/configs/phi4-mini-flash-serve.json \
        --traffic benchmark/traffic/backlog-reason-1k.json

Memory first, and both numbers by rule:

1. for a number of slots, ``num_pages`` is the FEWEST for which a full
   house (``slots`` final request lengths, prompt + output, each rounded
   up to pages, drawn from the traffic's own laws) overflows the pool in
   under 1% of the draws, plus the scratch page
   (``latent_pool.fewest_pages``, ``serve_pool.overflow_share``, one
   seed);
2. ``slots`` is the MOST, in multiples of 8, for which
   ``memory_analysis()`` of the engine's own decode executable and of its
   largest prefill bucket — weights, that pool and the slots' own arrays
   included — stays under 90% of the chip's ``bytes_limit`` (AOT for a
   described v5e chip, shapes for weights, nothing runs).  A slot's and a
   page's bytes are known from shapes, so one compile at the file's size
   gives what the programs need beside them; the answer is compiled
   again, and stepped down while it does not fit.

The output is quoted in the configuration file's ``sizing`` block.
"""
import argparse
import functools
import json
import math

from serve_pool import GIB, HBM_LIMIT_BYTES, overflow_share  # noqa: E402
from latent_pool import fewest_pages                         # noqa: E402

STEP = 8        # slots come in multiples of this


def compile_programs(config, num_pages, slots):
    """memory_analysis() of decode and of the largest prefill bucket at
    ``slots`` and ``num_pages``, built by the engine itself (a small
    engine: its programs take their shapes from their operands)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from benchmark.drivers import serve_family
    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.ops.pallas import utils as pallas_utils

    pallas_utils.on_tpu = lambda: True      # compile the chip's branch
    jax.config.update("jax_enable_compilation_cache", False)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    family, _, config_cls = serve_family.family_modules(config["model_type"])
    cfg = serve_family.build_config(config_cls, config)
    params = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda k: family.init_params(cfg, k),
                       jax.random.PRNGKey(0)))
    e = config["engine"]
    eng = PagedServingEngine(
        (params, cfg), capture_logits=False,
        **dict(e, slots=STEP, num_pages=2,
               seq_buckets=tuple(e["seq_buckets"]),
               batch_buckets=tuple(e["batch_buckets"])))
    ps = e["page_size"]
    cd = jnp.dtype(cfg.dtype)
    pages = tuple(sds(s, cd)
                  for s in family.paged_pool_shapes(cfg, num_pages, ps))
    per_slot = tuple(sds(s, d)
                     for s, d in family.slot_state_shapes(cfg, slots, ps))
    i32 = jnp.int32
    b, s = max(e["batch_buckets"]), max(e["seq_buckets"])
    programs = {
        "decode": (eng._build_decode(), (
            params, *pages, *per_slot, sds((slots, e["max_len"] // ps), i32),
            *(sds((slots,), i32),) * 4)),
        f"prefill_{b}x{s}": (eng._build_prefill(b, s), (
            params, *pages, *per_slot, sds((b, s), i32), sds((b,), i32),
            sds((b, s // ps), i32), sds((slots,), i32), sds((b,), i32))),
    }

    def gib(arrays):
        return sum(math.prod(x.shape) * x.dtype.itemsize
                   for x in arrays) / GIB

    out = {"weights_gib": gib(jax.tree_util.tree_leaves(params)),
           "pool_gib": gib(pages), "slot_state_gib": gib(per_slot)}
    for name, (fn, args) in programs.items():
        compiled = fn.lower(*args).compile()
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 - m.alias_size_in_bytes + m.temp_size_in_bytes)
        out[name] = {"argument_gib": m.argument_size_in_bytes / GIB,
                     "temp_gib": m.temp_size_in_bytes / GIB,
                     "total_gib": total / GIB,
                     "pallas_kernels":
                         compiled.as_text().count("tpu_custom_call")}
    return out


def fullest(programs):
    return max(p["total_gib"] for p in programs.values()
               if isinstance(p, dict)) * GIB


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--draws", type=int, default=200_000)
    ap.add_argument("--limit", type=float, default=0.01)
    ap.add_argument("--headroom", type=float, default=0.10)
    args = ap.parse_args()
    with open(args.config) as f:
        config = json.load(f)
    with open(args.traffic) as f:
        mix = json.load(f)
    e = config["engine"]
    ps = e["page_size"]
    budget = HBM_LIMIT_BYTES * (1 - args.headroom)

    @functools.lru_cache(maxsize=None)
    def pages_for(slots):
        return fewest_pages(mix, slots, ps, args.draws, args.limit) + 1

    first = compile_programs(config, e["num_pages"], e["slots"])
    page_bytes = first["pool_gib"] * GIB / e["num_pages"]
    slot_bytes = first["slot_state_gib"] * GIB / e["slots"]
    beside = (fullest(first) - e["num_pages"] * page_bytes
              - e["slots"] * slot_bytes)

    def held(slots):
        return beside + pages_for(slots) * page_bytes + slots * slot_bytes

    # held() grows with the slots: bisect on multiples of STEP
    lo, hi = 1, 64
    while lo < hi:
        mid = (lo + hi + 1) // 2
        lo, hi = (mid, hi) if held(mid * STEP) <= budget else (lo, mid - 1)
    slots = lo * STEP
    tried = []
    while True:
        pages = pages_for(slots)
        programs = compile_programs(config, pages, slots)
        tried.append({"slots": slots, "num_pages": pages,
                      "fullest_gib": fullest(programs) / GIB})
        if fullest(programs) <= budget or slots == STEP:
            break
        slots -= STEP
    more = compile_programs(config, pages_for(slots + STEP), slots + STEP)
    positions = (pages - 1) * ps
    row = {"budget_gib": budget / GIB, "page_kib": page_bytes / 1024,
           "slot_mib": slot_bytes / (1 << 20),
           "programs_beside_pool_and_slots_gib": beside / GIB,
           "slots": slots, "num_pages": pages, "pool_positions": positions,
           "house": overflow_share(mix, slots, positions, ps, args.draws, 0),
           "tried": tried, "programs": programs,
           "fits": fullest(programs) <= budget,
           "one_step_more": {"slots": slots + STEP,
                             "num_pages": pages_for(slots + STEP),
                             "fullest_gib": fullest(more) / GIB},
           "matches_the_file": (pages, slots) == (e["num_pages"],
                                                  e["slots"]),
           "compiled_at_the_files_size": first}
    print(json.dumps(row, indent=1), flush=True)


if __name__ == "__main__":
    main()
