#!/usr/bin/env python3
"""Size a LOOPED model's page pool and slots to the chip, WITHOUT the
chip.

    JAX_PLATFORMS=cpu python3 benchmark/sizing/looped_pool.py \
        --config benchmark/configs/ouro-2.6b-serve.json \
        --traffic benchmark/traffic/backlog-reason.json

``sizing/latent_pool.py``'s rule turned round, because here a cached
position is dear (K and V of every (pass, layer) pair) and memory, not
the traffic, fixes the pages:

1. ``num_pages`` — the MOST for which ``memory_analysis()`` of the
   engine's own decode executable and of its largest prefill bucket,
   weights and pool included, stays under 90% of the chip's
   ``bytes_limit`` (``latent_pool.compile_programs``: AOT for a described
   v5e chip, shapes for weights, nothing runs).  A page's bytes are known
   from shapes, so one compile at the file's size gives what the programs
   need beside the pool, and a second at the answer confirms it.
2. ``slots`` — the MOST for which a full house (``slots`` final request
   lengths, prompt + output, each rounded up to pages, drawn from the
   traffic's own laws) overflows that pool in under 1% of the draws
   (``serve_pool.overflow_share``, one seed).

The output is quoted in the configuration file's ``sizing`` block.
"""
import argparse
import json
import math

from serve_pool import GIB, HBM_LIMIT_BYTES, overflow_share  # noqa: E402
from latent_pool import compile_programs                     # noqa: E402


def most_slots(mix, pool_positions, page_size, draws, limit, ceiling=64):
    """The most slots whose house overflows in under ``limit`` of the
    draws."""
    def share(slots):
        return overflow_share(mix, slots, pool_positions, page_size, draws,
                              0)["overflow_share"]
    slots = 1
    while slots < ceiling and share(slots + 1) < limit:
        slots += 1
    return slots


def page_bytes(config):
    """Bytes one page takes of the pool: K and V of every (pass,
    layer) pair, ``page_size`` positions."""
    import jax.numpy as jnp
    pairs = config["total_ut_steps"] * config["num_hidden_layers"]
    row = config["num_attention_heads"] * config["head_dim"]
    return (2 * pairs * config["engine"]["page_size"] * row
            * jnp.dtype(config["dtype"]).itemsize)


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
    budget = HBM_LIMIT_BYTES * (1 - args.headroom)
    per_page = page_bytes(config)

    def fullest(programs):
        return max(p["total_gib"] for p in programs.values()
                   if isinstance(p, dict)) * GIB

    first = compile_programs(config, e["num_pages"])
    beside_pool = fullest(first) - e["num_pages"] * per_page
    pages = int((budget - beside_pool) // per_page)
    positions = (pages - 1) * e["page_size"]
    slots = most_slots(mix, positions, e["page_size"], args.draws,
                       args.limit)

    def house(n):
        return overflow_share(mix, n, positions, e["page_size"], args.draws,
                              0)

    row = {"budget_gib": budget / GIB, "page_mib": per_page / (1 << 20),
           "programs_beside_the_pool_gib": beside_pool / GIB,
           "num_pages": pages, "pool_positions": positions,
           "slots": slots, "house": house(slots),
           "overflow_share_one_slot_more": house(slots + 1)[
               "overflow_share"],
           "compiled_at_the_files_size": first}
    config["engine"] = dict(e, num_pages=pages, slots=slots)
    row["programs"] = compile_programs(config, pages)
    row["fits"] = fullest(row["programs"]) <= budget
    row["one_page_more_gib"] = (fullest(row["programs"]) + per_page) / GIB
    row["matches_the_file"] = (pages, slots) == (e["num_pages"], e["slots"])
    print(json.dumps(row, indent=1), flush=True)


if __name__ == "__main__":
    main()
