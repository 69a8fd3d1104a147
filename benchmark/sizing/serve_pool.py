#!/usr/bin/env python3
"""Size a serving deployment to the chip WITHOUT the chip.

    JAX_PLATFORMS=cpu python3 benchmark/sizing/serve_pool.py \
        --config benchmark/configs/gpt3-1.3b-serve.json \
        --traffic benchmark/traffic/backlog.json [--num-pages N ...] [--slots S ...]

Two pieces of arithmetic, both quoted in the configuration file:

1. ``num_pages`` — AOT-compiles the engine's own decode executable and its
   largest prefill bucket for a DESCRIBED v5e chip (the TPU compiler is
   installed here; nothing runs) and reads ``memory_analysis()``: the pool
   is the largest for which both programs, weights included, fit the
   chip's memory less the stated headroom.
2. ``slots`` — a Monte-Carlo over the traffic file's own length laws: the
   share of full houses (``slots`` final request lengths, prompt + output,
   rounded up to pages) that overflow the pool.  Slots, not pages, must
   bind: ``KVPager.admit`` reserves nothing for a request's output, so a
   pool that binds admits to the brim and then preempts.

The engine is built as a user builds it, with shapes in place of weights;
the Pallas gate (``ops/pallas/utils.on_tpu``) and buffer donation are
steered here, in the script, because ``jax.devices()`` is the CPU.
"""
import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["PADDLE_TPU_SERVING_DONATE"] = "1"

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

GIB = float(1 << 30)
HBM_LIMIT_BYTES = 16_909_336_064   # memory_stats()["bytes_limit"] of one
                                   # v5e chip (my chip run, PR 24): 15.75 GiB


def compile_programs(config, num_pages, slots):
    """memory_analysis() of decode and of the largest prefill bucket."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.models import gpt
    from paddle_tpu.ops.pallas import utils as pallas_utils

    pallas_utils.on_tpu = lambda: True      # compile the chip's branch
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    cfg = gpt.GPTConfig(**{k: config[k] for k in (
        "vocab_size", "hidden_size", "num_layers", "num_heads", "ffn_size",
        "max_seq_len", "dtype", "param_dtype")})
    params = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda k: gpt.init_params(cfg, k),
                       jax.random.PRNGKey(0)))
    e = dict(config["engine"], num_pages=num_pages, slots=slots)
    # a 2-page stand-in pool: the engine allocates its pool on the host
    # here, and only the compiled programs' shapes matter
    eng = PagedServingEngine(
        (params, cfg), capture_logits=False,
        **dict(e, num_pages=2, seq_buckets=tuple(e["seq_buckets"]),
               batch_buckets=tuple(e["batch_buckets"])))
    ps, L = e["page_size"], cfg.num_layers
    pool = sds((L, num_pages, ps, cfg.num_heads, cfg.head_dim),
               jnp.dtype(cfg.dtype))
    i32 = jnp.int32
    maxp = e["max_len"] // ps
    b, s = max(e["batch_buckets"]), max(e["seq_buckets"])
    programs = {
        "decode": (eng._build_decode(), (
            params, pool, pool, sds((slots, maxp), i32), sds((slots,), i32),
            sds((slots,), i32), sds((slots,), i32), sds((slots,), i32))),
        f"prefill_{b}x{s}": (eng._build_prefill(b, s), (
            params, pool, pool, sds((b, s), i32), sds((b,), i32),
            sds((b, s // ps), i32))),
    }
    out = {}
    for name, (fn, args) in programs.items():
        compiled = fn.lower(*args).compile()
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 - m.alias_size_in_bytes + m.temp_size_in_bytes)
        out[name] = {
            "argument_gib": m.argument_size_in_bytes / GIB,
            "output_gib": m.output_size_in_bytes / GIB,
            "alias_gib": m.alias_size_in_bytes / GIB,
            "temp_gib": m.temp_size_in_bytes / GIB,
            "total_gib": total / GIB,
            "pallas_kernels": compiled.as_text().count("tpu_custom_call")}
    return out


def overflow_share(traffic_mix, slots, pool_tokens, page_size, draws, seed):
    """Share of full houses whose final lengths overflow the pool."""
    import numpy as np
    from benchmark.lib import traffic
    rng = np.random.default_rng(seed)
    n = 4096
    final = (traffic.law_quantiles(traffic_mix["prompt_len"], n)[
                 rng.integers(0, n, (draws, slots))].round()
             + traffic.law_quantiles(traffic_mix["output_len"], n)[
                 rng.integers(0, n, (draws, slots))].round())
    pages = np.ceil(final / page_size).sum(1)
    return {"mean_final_len": float(final.mean()),
            "std_final_len": float(final.std()),
            "mean_house_tokens": float(final.sum(1).mean()),
            "mean_house_share_of_pool": float(
                pages.mean() * page_size / pool_tokens),
            "overflow_share": float((pages * page_size > pool_tokens).mean())}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--num-pages", type=int, nargs="*")
    ap.add_argument("--slots", type=int, nargs="*")
    ap.add_argument("--headroom", type=float, default=0.10,
                    help="share of the chip's memory kept free")
    ap.add_argument("--draws", type=int, default=200_000)
    ap.add_argument("--no-compile", action="store_true")
    args = ap.parse_args()
    with open(args.config) as f:
        config = json.load(f)
    with open(args.traffic) as f:
        mix = json.load(f)
    e = config["engine"]
    budget = HBM_LIMIT_BYTES * (1 - args.headroom) / GIB
    for num_pages in args.num_pages or [e["num_pages"]]:
        pool_tokens = (num_pages - 1) * e["page_size"]
        for slots in args.slots or [e["slots"]]:
            row = {"num_pages": num_pages, "slots": slots,
                   "pool_tokens": pool_tokens, "budget_gib": budget,
                   "house": overflow_share(mix, slots, pool_tokens,
                                           e["page_size"], args.draws, 0)}
            if not args.no_compile:
                row["programs"] = compile_programs(config, num_pages, slots)
                row["fits"] = all(p["total_gib"] <= budget
                                  for p in row["programs"].values())
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
