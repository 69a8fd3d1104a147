"""Whole AdamW steps through ``gpt_hybrid.init_sharded`` +
``make_train_step`` on the cell's devices, a NEW seeded batch every step.

The record it returns (what the metric readers read):

  window_s, steps, tokens       the measured window: every step
                                dispatched in it, closed by
                                ``block_until_ready`` on the last loss
  losses                        one per measured step
  loss_ref_gap, loss_ref_tol    first loss against the plain reference
  pallas_kernels                ``tpu_custom_call``s in the compiled step
  compiles_in_window            the program's compile counters' deltas
  arch, seq_len, batch          shapes for the FLOPs function
"""
import math
import time

import numpy as np

from ..lib import probe, reference, traffic

# First loss of the train step (Pallas flash attention) against the
# plain reference's loss on the same batch and weights (XLA attention,
# the same bf16 matmul operands and float32 softmax).  The two differ in
# blocking and in where the softmax normalisation lands, i.e. in bf16
# rounding (2^-9 relative) of attention outputs, which the mean over
# batch x seq tokens averages down: chip_smoke.py saw 1.5e-5 at 2 layers
# (CHANGES.md, PR 21) and bounds it at 5e-4 (FLASH_LOSS_TOL); the same
# bound here, at 24 layers.  A causal-mask or block-indexing fault moves
# the loss in the second decimal.
LOSS_REF_TOL = 5e-4

WARM_STEPS = 2      # the compiled step's first executions, before the window
TRACED_STEPS = 4    # steps under the profiler, after the window


def run(ctx):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.framework import jax_compat
    from paddle_tpu.models import gpt, gpt_hybrid
    from paddle_tpu.parallel.mesh import create_mesh

    arch, job = ctx.config, ctx.traffic
    trainer = arch["trainer"]
    batch, seq = int(job["batch"]), int(job["seq_len"])
    cfg = gpt.GPTConfig(
        **{k: arch[k] for k in ("vocab_size", "hidden_size", "num_layers",
                                "num_heads", "ffn_size", "max_seq_len",
                                "dtype", "param_dtype")},
        use_flash=trainer["use_flash"], remat=trainer["remat"],
        remat_policy=trainer["remat_policy"])
    if seq > cfg.max_seq_len:
        raise ValueError(f"seq_len {seq} > max_seq_len {cfg.max_seq_len}")
    mesh = create_mesh(dp=1, tp=1, pp=1, sp=1, devices=ctx.devices[:1])
    rep = jax_compat.named_sharding(mesh, ())
    data = jax_compat.named_sharding(mesh, ("dp", "sp"))

    sample = traffic.zipf_sampler(cfg.vocab_size,
                                  job["token_ids"]["exponent"])
    rng = traffic.stream_rng(ctx.seed, traffic.S_TOKENS)

    def next_batch():
        """Step i's tokens and next-token labels, on the device."""
        with jax.profiler.TraceAnnotation("bench.prepare_batch"):
            toks = sample(batch * (seq + 1), rng).reshape(batch, seq + 1)
            return (jax.device_put(toks[:, :-1], data),
                    jax.device_put(toks[:, 1:], data))

    def t_of(i):
        return jax.device_put(np.int32(i), rep)

    def lr_of(i):
        """Linear warm-up to the job's rate, as every pretraining recipe
        has it: AdamW's first steps move every weight by the rate
        whatever the gradient's scale, and on Zipf tokens (one direction
        in every row of the head) 2e-5 at step 1 threw the loss from 11.1
        to 16.1 (my chip run, PR 24)."""
        return jax.device_put(np.float32(
            job["lr"] * min(1.0, i / job["warmup_steps"])), rep)

    params, m, v = jax.block_until_ready(gpt_hybrid.init_sharded(
        cfg, mesh, jax.random.PRNGKey(ctx.seed),
        moment_dtype=jnp.dtype(trainer["moment_dtype"])))
    tokens, labels = next_batch()
    step = gpt_hybrid.make_train_step(cfg, mesh)
    compiled = step.lower(params, m, v, t_of(1), tokens, labels,
                          lr_of(1)).compile()
    kernels = compiled.as_text().count("tpu_custom_call")
    ctx.note(phase="compiled", pallas_kernels=kernels)

    ref_loss = float(jax.jit(
        lambda p, t, l: reference.gpt_loss(
            p, t, l, cfg.num_heads, cfg.layer_norm_eps,
            jnp.dtype(cfg.dtype)))(params, tokens, labels))
    n = 0

    def one_step():
        nonlocal params, m, v, tokens, labels, n
        n += 1
        with jax.profiler.TraceAnnotation("bench.train_step"):
            params, m, v, loss = compiled(params, m, v, t_of(n), tokens,
                                          labels, lr_of(n))
        tokens, labels = next_batch()       # while the step runs
        return loss

    warm = [float(one_step()) for _ in range(WARM_STEPS)]
    gap = abs(warm[0] - ref_loss)
    ctx.note(phase="warm", losses=warm, reference_loss=ref_loss, gap=gap)

    # ---- the window: one step in flight ahead of the host -------------
    c0 = probe.compile_counters()
    t0 = ctx.open_window()
    losses = [one_step()]
    while True:
        losses.append(one_step())
        with jax.profiler.TraceAnnotation("bench.wait_loss"):
            losses[-2] = float(losses[-2])  # ends while the next one runs
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    losses[-1] = float(jax.block_until_ready(losses[-1]))
    window_s = time.perf_counter() - t0
    in_window = probe.delta(probe.compile_counters(), c0)
    ctx.note(phase="window_closed", window_s=window_s, steps=len(losses),
             losses_first5=losses[:5], losses_last5=losses[-5:],
             loss_max=max(losses))

    record = {"window_s": window_s, "steps": len(losses),
              "tokens": len(losses) * batch * seq, "losses": losses,
              "loss_ref_gap": gap, "loss_ref_tol": LOSS_REF_TOL,
              "pallas_kernels": kernels, "compiles_in_window": in_window,
              "arch": arch, "seq_len": seq, "batch": batch}
    if ctx.trace:
        with probe.traced(record, ctx.on_chip):
            last = [one_step() for _ in range(TRACED_STEPS)][-1]
            jax.block_until_ready(last)

    checks = {
        "losses_finite_and_falling": reference.losses_learned(losses),
        "first_loss_matches_reference": gap <= LOSS_REF_TOL,
        "no_compile_in_window": (in_window["count"] == 0 and
                                 in_window["persistent_cache_requests"] == 0),
        # what XLA built, not what the config asked for
        "flash_kernels_engaged": kernels > 0 or not ctx.on_chip,
    }
    record.update(checks=checks, attempted=len(losses),
                  failed=sum(not math.isfinite(x) for x in losses))
    return record
