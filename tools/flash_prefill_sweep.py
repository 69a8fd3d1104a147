"""Time the latent family's prefill attention alone on the live chip,
the flash forward (``ops/pallas/flash_prefill.py``) against XLA's
``deepseek_v3._mla_attend``, at the kanana2 serving cell's shapes: 32
heads, q/k 128 + 64 wide, v 128, rank 512, bf16, the cell's five
buckets, 8 layers under a scan.  Where ``flash_prefill.BLOCKS`` and
``MIN_SCORE_BYTES`` come from (PERF.md section 6, PR 38).

Usage (through the chip tool, one process, a short ``--timeout``):
    python tools/flash_prefill_sweep.py [--buckets 1x512,1x1024] [--iters 10]
One JSON line a variant and bucket, also appended to
``chiprun_out/flash_prefill_sweep.jsonl``:

- ``expand``: the two einsums that make ``k_nope`` and ``v`` from ``c``
  and nothing else — both attentions pay them, and ``attn_us`` of every
  other line is its ``us`` less this;
- ``xla``: ``_mla_attend`` under the causal mask;
- ``flash``: the kernel at each block pair, every row a true one;
- ``flash.lens``: the same with the lengths a wave of the cell holds (one
  prompt 0.78 of the bucket long; in a wave of four, two of them and two
  pad rows of length 1), so the q blocks past them are skipped;
- ``flash.one_product``: one score product over 256 lanes (``k_nope``
  and the shared rope key side by side for every head, built by XLA
  inside the timing) in place of two over 128.

``us`` is wall clock a layer over ``--iters`` scans of the 8 layers (each
layer its own operands, so nothing is hoisted), each run ending in
``block_until_ready``; ``mxu_share`` is the attention's causal
2 * b * nh * s(s+1)/2 * (192 + 128) FLOPs over ``attn_us`` at 197
TFLOP/s; ``max_abs_err`` is against ``xla`` over the true rows.  Fails
without a TPU.
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

from paddle_tpu.models import deepseek_v3 as ds
from paddle_tpu.ops.pallas import flash_prefill as fp

LAYERS, PEAK_FLOPS = 8, 197e12
BUCKETS = ((1, 128), (1, 512), (1, 1024), (4, 512), (4, 1024))
BLOCK_PAIRS = ((128, 128), (256, 256), (256, 512), (512, 256), (512, 512),
               (1024, 256), (1024, 512), (1024, 1024))


def cell_lens(b, s):
    prompts = min(b, 2)
    return np.asarray([int(0.78 * s)] * prompts + [1] * (b - prompts),
                      np.int32)


def one_product(cfg, blk, q_nope, q_rope, c, kr, lens):
    cd = jnp.dtype(cfg.dtype)
    wuk, wuv = ds._wukv(cfg, blk)
    B, T = c.shape[:2]
    nh = cfg.num_attention_heads
    k_nope = jnp.einsum("bkc,chd->bkhd", c.astype(cd), wuk)
    v_t = jnp.einsum("bkc,chd->bhdk", c.astype(cd), wuv)
    k_cat = jnp.concatenate([k_nope, jnp.broadcast_to(
        ds._pad_rope(kr, cd)[:, :, None], (B, T, nh, ds.ROPE_LANES))], -1)
    q_cat = jnp.concatenate([q_nope, ds._pad_rope(q_rope, cd)], -1)
    return jnp.swapaxes(fp._flash_prefill_tpu(
        q_cat.reshape(B, T, -1), k_cat.reshape(B, T, -1),
        v_t.reshape(B, -1, T), lens, heads=nh,
        scale=cfg.qk_head_dim ** -0.5), 1, 2)


def expand(cfg, blk, q_nope, q_rope, c, kr, lens):
    wuk, wuv = ds._wukv(cfg, blk)
    k_nope = jnp.einsum("bkc,chd->bkhd", c, wuk)
    v = jnp.einsum("bkc,chd->bkhd", c, wuv)
    return (k_nope + v).reshape(*c.shape[:2], -1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--buckets", default=",".join(f"{b}x{s}"
                                                  for b, s in BUCKETS))
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: a CPU timing is nobody's measurement")
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/flash_prefill_sweep.jsonl", "a")
    cfg = ds.DeepseekV3Config(num_hidden_layers=LAYERS)
    nh, bf16 = cfg.num_attention_heads, jnp.bfloat16

    def draw(key, shape, scale=1.0):
        return jax.jit(lambda k: (jax.random.normal(
            k, shape, jnp.float32) * scale).astype(bf16))(key)

    def emit(**rec):
        line = json.dumps(rec)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    for bucket in args.buckets.split(","):
        b, s = (int(x) for x in bucket.split("x"))
        keys = jax.random.split(jax.random.PRNGKey(b * s), 5)
        ops = (draw(keys[0], (LAYERS, b, s, nh, cfg.qk_nope_head_dim)),
               draw(keys[1], (LAYERS, b, s, nh, cfg.qk_rope_head_dim)),
               draw(keys[2], (LAYERS, b, s, cfg.kv_lora_rank)),
               draw(keys[3], (LAYERS, b, s, cfg.qk_rope_head_dim)),
               draw(keys[4], (LAYERS, cfg.kv_lora_rank,
                              nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                    cfg.kv_lora_rank ** -0.5))
        flops = (2 * b * nh * s * (s + 1) // 2
                 * (cfg.qk_head_dim + cfg.v_head_dim))
        mask = ds._causal(s, s)
        base = {}

        def measure(variant, attend, lens, **more):
            lens = jnp.asarray(lens)

            def layer(carry, xs):
                qn, qr, c, kr, w = xs
                a = attend(cfg, {"wukv": w}, qn, qr, c, kr, lens)
                # every element, or XLA computes the one row that is read
                return carry + a.astype(jnp.float32).sum(), None

            run = jax.jit(lambda *o: jax.lax.scan(layer, jnp.float32(0),
                                                  o)[0])
            one = jax.jit(lambda *o: attend(
                cfg, {"wukv": o[4][3]}, *[x[3] for x in o[:4]], lens))
            got = np.asarray(one(*ops).astype(jnp.float32))
            err = None
            if "xla" in base and variant != "expand":
                err = max(float(np.abs(got[r, :n] - base["xla"][r, :n]).max())
                          for r, n in enumerate(np.asarray(lens)))
            base.setdefault(variant, got)
            jax.block_until_ready(run(*ops))
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = run(*ops)
            jax.block_until_ready(out)
            us = (time.perf_counter() - t0) / (args.iters * LAYERS) * 1e6
            base.setdefault("expand_us", us)
            attn_us = us - base["expand_us"]
            emit(variant=variant, bucket=bucket, us=round(us, 1),
                 attn_us=round(attn_us, 1),
                 mxu_share=(round(flops / (attn_us * 1e-6) / PEAK_FLOPS, 4)
                            if variant != "expand" else None),
                 max_abs_err=err, **more)

        full = np.full((b,), s, np.int32)
        measure("expand", expand, full)
        measure("xla", lambda cfg, blk, qn, qr, c, kr, lens: ds._mla_attend(
            cfg, blk, qn, qr, c, kr, mask), full)
        picked = fp.BLOCKS
        for bq, bk in BLOCK_PAIRS:
            if bq > s or bk > s:
                continue
            fp.BLOCKS = (bq, bk)
            measure("flash", ds._mla_attend_flash, full, blocks=[bq, bk])
            measure("flash.lens", ds._mla_attend_flash, cell_lens(b, s),
                    blocks=[bq, bk], rows=cell_lens(b, s).tolist())
        fp.BLOCKS = picked
        measure("flash.one_product", one_product, full, blocks=list(picked))


if __name__ == "__main__":
    main()
