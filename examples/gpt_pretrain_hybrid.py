"""Flagship GPT pretraining over a hybrid dp x pp x tp x sp mesh.

One shard_map'ed SPMD step: Megatron tensor parallel, GPipe pipeline over
'pp', ring-attention sequence parallel over 'sp', data parallel grad psum,
global-norm clip, fused AdamW — XLA schedules the ICI collectives.

Run (8 virtual devices):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/gpt_pretrain_hybrid.py --dp 2 --pp 2 --tp 2 --steps 5
"""
import argparse

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.framework import jax_compat
from paddle_tpu.models import gpt, gpt_hybrid
from paddle_tpu.parallel.mesh import create_mesh
from paddle_tpu.utils import CheckpointManager


def main(dp=2, pp=2, tp=2, sp=1, steps=5, batch=8, seq=128,
         ckpt_dir=None):
    cfg = gpt.GPTConfig(vocab_size=512, hidden_size=128, num_layers=4,
                        num_heads=4, max_seq_len=seq, use_flash=False,
                        remat=True, dtype="float32")
    mesh = create_mesh(dp=dp, tp=tp, pp=pp, sp=sp)
    print(f"mesh dp={dp} pp={pp} tp={tp} sp={sp}; "
          f"model {cfg.num_params()/1e6:.1f}M params")

    params, m, v = gpt_hybrid.init_sharded(cfg, mesh, jax.random.PRNGKey(0))
    step = gpt_hybrid.make_train_step(cfg, mesh, n_microbatch=2)

    rng = np.random.RandomState(0)
    for t in range(1, steps + 1):
        toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                           jnp.int32)
        params, m, v, loss = step(params, m, v, jnp.int32(t), toks, toks,
                                  jnp.float32(3e-4))
        print(f"step {t} loss {float(loss):.4f}")

    if ckpt_dir:
        import pickle
        with open(f"{ckpt_dir}/gpt_final.pkl", "wb") as f:
            pickle.dump(jax.tree.map(np.asarray, params), f)
        print(f"saved to {ckpt_dir}/gpt_final.pkl")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--pp", type=int, default=2)
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--sp", type=int, default=1)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    # an entry point: the fixed checkout cache unless one is named from
    # outside (JAX_COMPILATION_CACHE_DIR, then PADDLE_JIT_CACHE_DIR)
    jax_compat.enable_persistent_cache(jax_compat.checkout_cache_dir())
    main(dp=args.dp, pp=args.pp, tp=args.tp, sp=args.sp, steps=args.steps,
         ckpt_dir=args.ckpt_dir)
