#!/usr/bin/env python3
"""Size a latent (MLA) page pool to its traffic, WITHOUT the chip.

    JAX_PLATFORMS=cpu python3 benchmark/sizing/latent_pool.py \
        --config benchmark/configs/kanana2-30b-a3b-serve.json \
        --traffic benchmark/traffic/backlog-256out.json [--no-compile]

The rule is ``gpt3-1.3b-serve``'s own (``sizing/serve_pool.py``, whose
Monte-Carlo this imports), read the other way round because a latent
position is cheap and the weights are not: ``slots`` is given by the
deployment, and ``num_pages`` is the FEWEST for which a full house —
``slots`` final request lengths (prompt + output, each rounded up to
pages) drawn from the traffic's own length laws — overflows the pool in
under 1% of the draws, plus the scratch page.  Then the engine's own
decode executable and its largest prefill bucket are AOT-compiled for a
described v5e chip at that size (shapes for weights; nothing runs) and
``memory_analysis()``, weights and pool included, is held to 90% of the
chip's ``bytes_limit``.  The output is quoted in the configuration
file's ``sizing`` block.
"""
import argparse
import json
import math

from serve_pool import GIB, HBM_LIMIT_BYTES, overflow_share  # noqa: E402


def fewest_pages(mix, slots, page_size, draws, limit):
    """The fewest ALLOCATABLE pages whose house overflows in under
    ``limit`` of the draws (bisection on serve_pool's own Monte-Carlo,
    one seed, so the answer is reproducible)."""
    lo, hi = 1, slots * math.ceil(
        (mix["prompt_len"]["max"] + mix["output_len"]["max"]) / page_size)
    while lo < hi:
        mid = (lo + hi) // 2
        share = overflow_share(mix, slots, mid * page_size, page_size,
                               draws, 0)["overflow_share"]
        lo, hi = (lo, mid) if share < limit else (mid + 1, hi)
    return lo


def compile_programs(config, num_pages):
    """memory_analysis() of decode and of the largest prefill bucket,
    built by the engine itself for the family the file names."""
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
        **dict(e, num_pages=2, seq_buckets=tuple(e["seq_buckets"]),
               batch_buckets=tuple(e["batch_buckets"])))
    ps, slots = e["page_size"], e["slots"]
    pools = tuple(sds(s, jnp.dtype(cfg.dtype))
                  for s in family.paged_pool_shapes(cfg, num_pages, ps))
    i32 = jnp.int32
    b, s = max(e["batch_buckets"]), max(e["seq_buckets"])
    programs = {
        "decode": (eng._build_decode(), (
            params, *pools, sds((slots, e["max_len"] // ps), i32),
            *(sds((slots,), i32),) * 4)),
        f"prefill_{b}x{s}": (eng._build_prefill(b, s), (
            params, *pools, sds((b, s), i32), sds((b,), i32),
            sds((b, s // ps), i32))),
    }
    out = {"weights_gib": sum(
        math.prod(x.shape) * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(params)) / GIB,
        "pool_gib": sum(math.prod(p.shape) * p.dtype.itemsize
                        for p in pools) / GIB}
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


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--draws", type=int, default=200_000)
    ap.add_argument("--limit", type=float, default=0.01)
    ap.add_argument("--headroom", type=float, default=0.10)
    ap.add_argument("--no-compile", action="store_true")
    args = ap.parse_args()
    with open(args.config) as f:
        config = json.load(f)
    with open(args.traffic) as f:
        mix = json.load(f)
    e = config["engine"]
    ps, slots = e["page_size"], e["slots"]
    pages = fewest_pages(mix, slots, ps, args.draws, args.limit)
    row = {"slots": slots, "page_size": ps, "num_pages": pages + 1,
           "pool_positions": pages * ps,
           "budget_gib": HBM_LIMIT_BYTES * (1 - args.headroom) / GIB,
           "house": overflow_share(mix, slots, pages * ps, ps, args.draws, 0),
           "one_page_fewer": overflow_share(mix, slots, (pages - 1) * ps, ps,
                                            args.draws, 0)["overflow_share"]}
    if not args.no_compile:
        row["programs"] = compile_programs(config, pages + 1)
        row["fits"] = all(p["total_gib"] <= row["budget_gib"]
                          for k, p in row["programs"].items()
                          if isinstance(p, dict))
    print(json.dumps(row, indent=1), flush=True)


if __name__ == "__main__":
    main()
