"""Flagship benchmark: GPT + ERNIE + ResNet50 train-step throughput on one
chip.

Measures throughput for fully fused jitted train steps (bf16 compute on
the MXU, remat, fused AdamW) and reports MFU against the reference's 35%-MFU
north star (BASELINE.json).  Prints one JSON line per metric, in
BASELINE.json order of importance: GPT-1.3B flagship tokens/sec/chip,
ERNIE-3.0-Base pretrain tokens/sec/chip, ResNet50 static-DP imgs/sec/chip.

One process per chip: ``bench.py`` with no argument runs :func:`run` in
this process (a chip belongs to one process at a time, so nothing here
probes the device from a child).  It runs the configs it names or fails:
a device it does not know, a kernel the chip's compiler refuses or a
config that does not fit is a non-zero exit, after the metric lines
already earned — never a smaller model, the XLA path or the CPU.

The explicit modes (``--serving``, ``--dp-overlap``, ``--model-parallel``,
``--fleet``, ``--faults``, ``--eager-micro``; with ``--cpu-mesh N`` where
they need a mesh) are the CPU contract checks the ``tools/*_smoke.sh``
scripts call: they run toy models and assert counts and parities, on any
backend.  ``--cpu-mesh N`` re-execs once, only to set ``XLA_FLAGS``
before jax starts.

Timing methodology: a timed region ends in ``jax.block_until_ready`` —
jax returns before the device finishes, so a timing without it measures
the enqueue.  The steps chain on the params pytree (step i+1 consumes
step i's outputs), so waiting for the final loss bounds the whole
region.  MFU is sanity-asserted to (0, 1].
"""
import json
import math
import os
import subprocess
import sys
import time

TARGET_MFU = 0.35   # BASELINE.json north star

# bf16 peak FLOP/s per CHIP by TPU generation (public spec sheets; v5e:
# Google Cloud documentation, "TPU v5e", 197 TFLOP/s).
# libtpu device_kind strings look like "TPU v4", "TPU v5 lite", "TPU v5p",
# "TPU v6 lite" — match most-specific first.
PEAK_FLOPS = [
    ("v6 lite", 918e12), ("v6e", 918e12),
    ("v5 lite", 197e12), ("v5litepod", 197e12), ("v5e", 197e12),
    ("v5p", 459e12), ("v5", 459e12),
    ("v4", 275e12), ("v3", 123e12), ("v2", 45e12),
]

SWEEP_RESULTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tools", "bench_sweep_results.json")


def _peak_flops_kind(kind):
    """Peak bf16 FLOP/s of the device, or an error: a device that is not
    in the table has no MFU, and guessing one would put a number under
    the wrong machine's name."""
    low = kind.lower()
    for key, val in PEAK_FLOPS:
        if key in low:
            return val
    raise SystemExit(
        f"bench: unknown device_kind {kind!r} — no peak FLOP/s on record "
        "(PEAK_FLOPS); the flagship run measures accelerators only")


# --------------------------------------------------------------------------
# the flagship run (no argument): one process, one TPU client
# --------------------------------------------------------------------------

def _run_gpt_config(cfg, batch, steps, mesh, moment_dtype):
    """Build + time one GPT train-step config.  Returns (tok/s, loss)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import gpt_hybrid

    params, m, v = gpt_hybrid.init_sharded(cfg, mesh, jax.random.PRNGKey(0),
                                           moment_dtype=moment_dtype)
    step = gpt_hybrid.make_train_step(cfg, mesh, n_microbatch=1)

    N = cfg.max_seq_len
    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (batch, N)),
        jnp.int32)
    lr = jnp.float32(1e-4)

    # compile + warmup
    params, m, v, loss = step(params, m, v, jnp.int32(1), toks, toks, lr)
    jax.block_until_ready(loss)

    t0 = time.perf_counter()
    for i in range(steps):
        params, m, v, loss = step(params, m, v, jnp.int32(i + 2), toks,
                                  toks, lr)
    jax.block_until_ready(loss)       # closes the timed region
    dt = time.perf_counter() - t0
    final_loss = float(loss)
    assert np.isfinite(final_loss), f"non-finite loss {final_loss}"
    return batch * N * steps / dt, final_loss


def _ernie_state_gib(cfg):
    """fp32 params + AdamW moments + one grad tree — the deterministic
    part of the ERNIE footprint, checked against a 16GB chip before the
    timed run is spent on it."""
    return cfg.num_params() * 4 * 4 / 2**30


def _time_ernie_batch(cfg, batch, steps):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import bert

    N = cfg.max_seq_len
    params, m, v = bert.init_pretrain_state(cfg, jax.random.PRNGKey(0))
    step = bert.make_train_step(cfg)

    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, N)), jnp.int32)
    mask = rng.rand(batch, N) < 0.15            # 15% masked-LM positions
    mlm = jnp.asarray(np.where(mask, np.asarray(toks), -100), jnp.int32)
    nsp = jnp.asarray(rng.randint(0, 2, (batch,)), jnp.int32)
    lr = jnp.float32(1e-4)

    params, m, v, loss = step(params, m, v, jnp.int32(1), toks, mlm, nsp, lr)
    jax.block_until_ready(loss)       # compile + warm

    t0 = time.perf_counter()
    for i in range(steps):
        params, m, v, loss = step(params, m, v, jnp.int32(i + 2), toks,
                                  mlm, nsp, lr)
    jax.block_until_ready(loss)       # closes the timed region
    dt = time.perf_counter() - t0
    final_loss = float(loss)
    assert np.isfinite(final_loss)
    return batch * N * steps / dt, final_loss


def _emit_rate(name, batch, time_fn, flops_per_unit, unit, peak, sweep,
               sweep_key, extra):
    """Time the named batch and emit the metric JSON line (MFU over the
    35% north star as vs_baseline).  A batch that does not fit fails the
    run: a smaller one would be a different result under the same name."""
    rate, final_loss = time_fn(batch)
    mfu = rate * flops_per_unit / peak
    assert 0.0 < mfu <= 1.0, mfu
    print(json.dumps({
        "metric": name,
        "value": round(rate, 1),
        "unit": unit,
        "vs_baseline": round(mfu / TARGET_MFU, 4),
    }), flush=True)
    print(f"# {extra} batch={batch} loss={final_loss:.4f} "
          f"mfu={mfu:.3f}", file=sys.stderr)
    sweep[sweep_key] = dict(extra=extra, batch=batch,
                            rate=round(rate, 1), unit=unit,
                            mfu=round(mfu, 4),
                            loss=round(final_loss, 4))


def _ernie_flash_wins():
    """Gate ERNIE's bidirectional flash path on the kernel check's
    NON-CAUSAL fwd+bwd records (B4/N1024/H8/D64 — the D=64 encoder
    regime) actually beating XLA; BertConfig defaults use_flash=True,
    which must not reach a timed run unmeasured."""
    global _kernel_check_cache
    if _kernel_check_cache is None:
        _kernel_check_record("flash_attn_fwd")   # loads the artifact
    try:
        f = _kernel_check_cache["flash_attn_fwd"]
        b = _kernel_check_cache["flash_attn_bwd"]
        return bool(f["ok"] and b["ok"]
                    and f["pallas_ms"] < f["xla_ms"]
                    and b["pallas_ms"] < b["xla_ms"])
    except Exception:                                      # noqa: BLE001
        return False


def _run_ernie(peak, sweep):
    """ERNIE-3.0-Base pretrain throughput — BASELINE.json's named metric."""
    import dataclasses
    from paddle_tpu.models import bert

    cfg = dataclasses.replace(bert.ernie_3_base(),
                              use_flash=_ernie_flash_wins())
    state_gib = _ernie_state_gib(cfg)
    assert state_gib < 8.0, (
        f"ERNIE optimizer state alone is {state_gib:.1f}GiB — leaves no "
        "headroom for activations on a 16GB chip; shrink the config")
    steps = 10
    # batch 32: not yet sized on the chip — the first benchmark PR
    # (ROADMAP S1) fixes the cell's batch from a measured fit
    _emit_rate(
        "ernie3_base_pretrain_tokens_per_sec_per_chip", 32,
        lambda b: _time_ernie_batch(cfg, b, steps),
        cfg.flops_per_token(), "tokens/s/chip", peak, sweep, "ernie",
        f"model=ERNIE-{cfg.num_params()/1e6:.0f}M seq={cfg.max_seq_len} "
        f"steps={steps} use_flash={cfg.use_flash}")


# ResNet50 train FLOPs/img at 224x224: the public "4.09G" figure counts
# multiply-accumulates; PEAK_FLOPS (and the GPT/ERNIE 6N convention)
# count multiply and add separately, so x2 — then x3 for the backward's
# two conv passes.
RESNET50_TRAIN_FLOPS_PER_IMG = 3 * 2 * 4.09e9


def _time_resnet_batch(batch, steps, image_size=224, classes=1000):
    """One jitted static-graph DP train step (examples/resnet50_static_dp
    program) timed with device-resident feeds — the host->device
    transfer of the 38MB image batch must not pollute the step time, so
    the batch is converted once and re-fed by handle."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import static
    from paddle_tpu.vision.models import resnet50
    import paddle_tpu.nn.functional as F

    paddle.enable_static()
    try:
        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            img = static.data("image", [None, 3, image_size, image_size],
                              "float32")
            label = static.data("label", [None, 1], "int64")
            # bf16 convs on the MXU (amp O1: conv/matmul cast, norms and
            # the loss stay fp32) — the auto_cast wrappers are recorded
            # into the program, so the jitted replay keeps them
            with paddle.amp.auto_cast():
                logits = resnet50(num_classes=classes)(img)
                loss = F.cross_entropy(logits, label).mean()
            opt = paddle.optimizer.Momentum(learning_rate=0.002,
                                            momentum=0.9, weight_decay=1e-4)
            opt.minimize(loss)
            exe = static.Executor()
            exe.run(startup)

            rng = np.random.RandomState(0)
            x = paddle.to_tensor(rng.randn(
                batch, 3, image_size, image_size).astype(np.float32))
            y = paddle.to_tensor(rng.randint(
                0, classes, (batch, 1)).astype(np.int64))
            feed = {"image": x, "label": y}

            # compile+warm BOTH variants: the steady loop runs fetchless
            # (a fetch every step would serialize host and device), and
            # the final fetch — Executor.run hands back host values —
            # closes the timed region
            lv, = exe.run(main, feed=feed, fetch_list=[loss])
            exe.run(main, feed=feed, fetch_list=[])
            t0 = time.perf_counter()
            for _ in range(steps - 1):
                exe.run(main, feed=feed, fetch_list=[])
            lv, = exe.run(main, feed=feed, fetch_list=[loss])
            final_loss = float(np.asarray(lv))  # the fetch waits
            dt = time.perf_counter() - t0
            assert np.isfinite(final_loss)
            return batch * steps / dt, final_loss
    finally:
        paddle.disable_static()


def _run_resnet(peak, sweep):
    """ResNet50 imgs/sec/chip — BASELINE.json configs[1] (static-graph DP).
    vs_baseline uses the same MFU-over-0.35 yardstick as the other lines."""
    steps = 10
    # batch 64: not yet sized on the chip (see _run_ernie)
    _emit_rate(
        "resnet50_imgs_per_sec_per_chip", 64,
        lambda b: _time_resnet_batch(b, steps),
        RESNET50_TRAIN_FLOPS_PER_IMG, "imgs/s/chip", peak, sweep,
        "resnet50", f"model=ResNet50 image=224 steps={steps}")


def run():
    """The flagship run: the three BASELINE.json configs on the one chip,
    in this process.  Each config runs as named or the run fails — after
    printing the lines already earned."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.framework import jax_compat
    from paddle_tpu.parallel.mesh import create_mesh
    from paddle_tpu.models import gpt

    jax_compat.enable_persistent_cache(jax_compat.checkout_cache_dir())
    dev = jax.devices()[0]
    peak = _peak_flops_kind(dev.device_kind)     # unknown device: exits
    sweep = {"device_kind": dev.device_kind, "gpt_configs": []}
    # GPT-3 1.3B-class flagship (BASELINE.json configs[3]): hidden 2048,
    # 24 layers, head_dim 128, seq 2048, batch 4.  bf16 params + bf16
    # moments fit the 16GB v5e chip (fp32 AdamW state alone would need
    # 15.9GB).  use_flash honors the committed kernel-check sweep: the
    # Pallas path is taken where tools/tpu_kernel_check.json records it
    # beating XLA at this shape.
    cfg = gpt.GPTConfig(
        vocab_size=50304, hidden_size=2048, num_layers=24, num_heads=16,
        max_seq_len=2048, param_dtype="bfloat16",
        use_flash=_flash_wins_per_kernel_check(),
        use_fused_ffn=_fused_ffn_wins_per_kernel_check())
    batch, steps = 4, 8
    mesh = create_mesh(dp=1, tp=1, pp=1, sp=1, devices=[dev])
    tokens_per_sec, loss = _run_gpt_config(cfg, batch, steps, mesh,
                                           jnp.bfloat16)
    mfu = tokens_per_sec * cfg.flops_per_token() / peak
    assert 0.0 < mfu <= 1.0, (
        f"insane MFU {mfu:.3f} — the timed region is not bounded by "
        "block_until_ready")
    print(json.dumps({
        "metric": "gpt_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / TARGET_MFU, 4),
    }), flush=True)
    print(f"# model=GPT-{cfg.num_params()/1e6:.0f}M "
          f"seq={cfg.max_seq_len} batch={batch} loss={loss:.4f} "
          f"mfu={mfu:.3f} device={dev.device_kind}", file=sys.stderr)
    sweep["gpt_configs"].append(
        {"hidden": cfg.hidden_size, "batch": batch, "steps": steps,
         "seq": cfg.max_seq_len, "use_flash": bool(cfg.use_flash),
         "use_fused_ffn": bool(cfg.use_fused_ffn),
         "tokens_per_sec": round(tokens_per_sec, 1),
         "mfu": round(mfu, 4), "loss": round(loss, 4)})
    _dump_sweep(sweep)   # persist incrementally: a later failure keeps it

    # second metric line: ERNIE-3.0-Base (the BASELINE.json headline)
    _run_ernie(peak, sweep)
    _dump_sweep(sweep)

    # third metric line: ResNet50 imgs/sec/chip (BASELINE.json configs[1])
    _run_resnet(peak, sweep)
    _dump_sweep(sweep)


_kernel_check_cache = None


def _kernel_check_record(key):
    """The named record from the committed on-chip kernel sweep, but ONLY
    when its gate is a measured True (never route the flagship through
    a losing kernel, never trust a stale green or a budget-starved
    null).  Returns None otherwise.  The artifact is parsed once per
    process."""
    global _kernel_check_cache
    if _kernel_check_cache is None:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "tools", "tpu_kernel_check.json")
        try:
            with open(path) as f:
                _kernel_check_cache = json.load(f)
        except Exception:                                  # noqa: BLE001
            _kernel_check_cache = {}
    try:
        rec = _kernel_check_cache[key]
        return rec if rec["pallas_beats_xla"] is True else None
    except Exception:                                      # noqa: BLE001
        return None


def _fused_ffn_wins_per_kernel_check():
    """Enable the Pallas fused FFN only when the fresh sweep shows its
    grad step beating XLA at the flagship shape — installing the
    measured (and parity-checked) winning tiling."""
    rec = _kernel_check_record("fused_ffn_bench_shape")
    if rec is None:
        return False
    from paddle_tpu.ops.pallas import fused_ffn as ff
    ff.set_default_blocks(rec.get("best_blocks"))
    return True


def _flash_wins_per_kernel_check():
    """Enable the Pallas flash path only when the fresh sweep shows it
    beating XLA at the bench shape — installing the winning tilings AND
    backward strategy so the executed configuration is exactly the one
    the gate approved."""
    rec = _kernel_check_record("flash_attn_bench_shape")
    if rec is None:
        return False
    from paddle_tpu.ops.pallas import flash_attn as fa
    fa.set_default_blocks(fwd=rec.get("best_fwd_blocks"),
                          bwd=rec.get("best_bwd_blocks"),
                          bwd_fused=rec.get("best_bwd_fused", False))
    return True


def _dump_sweep(sweep):
    """Persist per-config measurements so perf claims are an artifact,
    not a comment."""
    try:
        with open(SWEEP_RESULTS, "w") as f:
            json.dump(sweep, f, indent=1)
    except OSError as e:
        print(f"# could not write sweep results: {e}", file=sys.stderr)


# --------------------------------------------------------------------------
# --eager-micro  (eager-loop dispatch/optimizer fast-path microbench)
# --------------------------------------------------------------------------

def eager_micro():
    """Measure the jit-cached eager dispatch + fused optimizer step.

    Asserts the tentpole claims instead of trusting them: steady-state
    steps (N>2) issue ZERO new traces (dispatch cache miss counter flat),
    the fused optimizer performs exactly 1 compiled call per step
    regardless of parameter count, and the fast path trains numerically
    identically (atol 1e-6 fp32) to the per-param eager loop.  Runs on any
    backend (CPU smoke included) — the win being measured is host
    dispatch overhead, not FLOPs.
    """
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import profiler
    from paddle_tpu.observability import StepTimer
    from paddle_tpu.observability import metrics as obs_metrics
    from paddle_tpu.ops import dispatch
    from paddle_tpu.optimizer import optimizer as opt_mod

    def build(n_layers=6, width=64):
        paddle.seed(11)
        layers = []
        for _ in range(n_layers):
            layers += [nn.Linear(width, width), nn.Tanh()]
        layers.append(nn.Linear(width, 8))
        return nn.Sequential(*layers)

    def run_loop(steps, fused, cache):
        os.environ["PADDLE_TPU_FUSED_STEP"] = "1" if fused else "0"
        os.environ["PADDLE_TPU_DISPATCH_CACHE"] = "1" if cache else "0"
        # compile on the 2nd sighting so steady state is reached by step 3
        os.environ["PADDLE_TPU_DISPATCH_CACHE_WARMUP"] = "2"
        try:
            net = build()
            opt = paddle.optimizer.AdamW(
                1e-3, parameters=net.parameters(), weight_decay=0.01,
                grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
            x = paddle.to_tensor(np.random.RandomState(0)
                                 .randn(32, 64).astype(np.float32))
            dispatch.clear_cache()
            dispatch.reset_cache_stats()
            opt_mod.reset_fused_stats()
            per_step = []
            timer = StepTimer(
                name=f"eager_micro_{'fast' if fused else 'ref'}",
                publish_interval=0)
            compiles0 = obs_metrics.counter("compile.count").value
            t0 = time.perf_counter()
            with timer:
                for i in range(steps):
                    with timer.step():
                        loss = (net(x) ** 2).mean()
                        loss.backward()
                        opt.step()
                        opt.clear_grad()
                    s = dispatch.cache_stats()
                    f = dict(opt_mod._fused_stats)
                    per_step.append((s["misses"], s["hits"],
                                     f["compiles"], f["calls"]))
            float(loss.numpy())         # host fetch closes the region
            dt = time.perf_counter() - t0
            counters = profiler.fast_path_summary()
            telem = {"compiles": obs_metrics.counter("compile.count")
                     .value - compiles0,
                     "step_time_ms": {
                         k: (round(v * 1e3, 3) if v is not None else None)
                         for k, v in timer.percentiles().items()}}
            params = [np.asarray(p.numpy()) for p in net.parameters()]
            return (per_step, dt, params, float(loss.numpy()), counters,
                    telem)
        finally:
            os.environ.pop("PADDLE_TPU_FUSED_STEP", None)
            os.environ.pop("PADDLE_TPU_DISPATCH_CACHE", None)
            os.environ.pop("PADDLE_TPU_DISPATCH_CACHE_WARMUP", None)

    steps = 10
    hist, dt_fast, params_fast, loss_fast, counters, telem = run_loop(
        steps, True, True)
    _, dt_slow, params_slow, loss_slow, _, _ = run_loop(
        steps, False, False)

    # steady state: no step after the 2nd may trace anything new
    new_traces_late = [hist[i][0] - hist[i - 1][0]
                       for i in range(2, steps)]
    assert all(n == 0 for n in new_traces_late), (
        f"steady-state retraces detected: {new_traces_late}")
    # fused step: 1 compile total, exactly 1 compiled call per step
    assert hist[-1][2] == 1, f"fused compiles {hist[-1][2]} != 1"
    calls_per_step = [hist[i][3] - hist[i - 1][3] for i in range(1, steps)]
    assert all(c == 1 for c in calls_per_step), calls_per_step
    # numerical parity against the per-param eager loop
    for a, b in zip(params_fast, params_slow):
        np.testing.assert_allclose(a, b, atol=1e-6)

    print(json.dumps({
        "metric": "eager_micro_steps_per_sec",
        "value": round(steps / dt_fast, 2),
        "unit": "steps/s",
        "vs_baseline": round(dt_slow / dt_fast, 3),   # speedup vs uncached
        # registry-backed telemetry: XLA compile count + step-time
        # percentiles for the fast loop (the old output had means only)
        "telemetry": {**telem,
                      "registry": {"dispatch_cache":
                                   counters["dispatch_cache"],
                                   "fused_step": counters["fused_step"]}},
    }), flush=True)
    print(f"# eager-micro: fast={steps / dt_fast:.2f} steps/s "
          f"uncached={steps / dt_slow:.2f} steps/s "
          f"speedup={dt_slow / dt_fast:.2f}x "
          f"loss_parity={abs(loss_fast - loss_slow):.2e} "
          f"counters={counters}", file=sys.stderr)


# --------------------------------------------------------------------------
# --dp-overlap  (pipelined data-parallel step on a device mesh)
# --------------------------------------------------------------------------

def dp_overlap():
    """Pipelined DP train step vs the unbucketed sync-at-end reducer.

    Runs the SAME model + data stream through two schedules on the
    device mesh (all local devices; ``--cpu-mesh N`` forces an N-device
    XLA host-platform mesh — the CPU contract check the smokes call):

      sync      one flat all_reduce launched AFTER backward finishes,
                per-param unbucket write-back, fused optimizer step,
                synchronous per-step H2D input transfer;
      overlap   size-capped buckets (reverse registration order) whose
                collectives launch from the grad-ready hooks while
                backward is still walking earlier layers, reduced flats
                consumed directly by the donated fused optimizer step
                (one jitted scale+unflatten+update), input batches
                prefetched to device one step ahead.

    Asserts exactly one collective launch per bucket per step and
    overlap-vs-sync parameter parity to 1e-6 after 10 timed steps, then
    ALWAYS prints a final parsed-JSON line with both step times and the
    overlap/prefetch counters before enforcing the speedup floor
    (BENCH_DP_MIN_REDUCTION, default 0.20)."""
    import numpy as np
    import jax
    from paddle_tpu.framework.jax_compat import make_mesh
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.distributed as dist
    from paddle_tpu import io, profiler
    from paddle_tpu.distributed import reducer as reducer_mod
    from paddle_tpu.observability import StepTimer
    from paddle_tpu.observability import metrics as obs_metrics

    width = int(os.environ.get("BENCH_DP_WIDTH", 768))
    depth = int(os.environ.get("BENCH_DP_DEPTH", 8))
    batch = int(os.environ.get("BENCH_DP_BATCH", 128))
    bucket_mb = float(os.environ.get("BENCH_DP_BUCKET_MB", 4))
    steps = int(os.environ.get("BENCH_DP_STEPS", 10))
    warmup = 2
    min_reduction = float(os.environ.get("BENCH_DP_MIN_REDUCTION", 0.20))

    devices = jax.devices()
    mesh = make_mesh(np.array(devices), ("dp",))

    def build():
        paddle.seed(42)
        layers = [nn.Linear(width, width), nn.Tanh()]
        for _ in range(depth - 1):
            layers += [nn.Linear(width, width), nn.Tanh()]
        layers.append(nn.Linear(width, 8))
        return nn.Sequential(*layers)

    rng = np.random.RandomState(0)
    batches = [{"x": rng.randn(batch, width).astype(np.float32),
                "y": rng.randn(batch, 8).astype(np.float32)}
               for _ in range(steps + warmup)]

    def run(mode):
        obs_metrics.reset("reducer")
        obs_metrics.reset("prefetch")
        net = build()
        if mode == "overlap":
            dp = dist.DataParallel(net, mesh=mesh, bucket_size_mb=bucket_mb,
                                   overlap=True, fuse_into_step=True)
            it = io.prefetch_to_device(iter(batches))
        else:
            # unbucketed sync-at-end: ONE flat bucket, launched at
            # end-of-backward finalize, unbucketed back per param
            dp = dist.DataParallel(net, mesh=mesh, bucket_size_mb=1e9,
                                   overlap=False)
            it = iter(batches)
        opt = paddle.optimizer.Momentum(0.01, parameters=net.parameters())
        n_buckets = len(dp.reducer.buckets)

        def one_step():
            b = next(it)
            if mode == "overlap":
                x, y = paddle.Tensor(b["x"]), paddle.Tensor(b["y"])
            else:
                x = paddle.to_tensor(b["x"])
                y = paddle.to_tensor(b["y"])
            loss = paddle.nn.functional.mse_loss(dp(x), y)
            loss.backward()
            if mode == "overlap":
                dp.step_fused(opt)
            else:
                opt.step()
            opt.clear_grad()
            return loss

        for _ in range(warmup):
            loss = one_step()
        float(loss.numpy())               # drain warmup
        launched0 = reducer_mod.reducer_stats()["collectives_launched"]
        timer = StepTimer(name=f"dp_{mode}", publish_interval=0)
        compiles0 = obs_metrics.counter("compile.count").value
        t0 = time.perf_counter()
        with timer:
            for _ in range(steps):
                with timer.step():
                    loss = one_step()
        for p in net.parameters():        # host sync closes the region
            p.value.block_until_ready()
        final_loss = float(loss.numpy())
        dt = (time.perf_counter() - t0) / steps
        stats = reducer_mod.reducer_stats()
        launched = stats["collectives_launched"] - launched0
        assert launched == n_buckets * steps, (
            f"{mode}: {launched} collective launches for "
            f"{n_buckets} buckets x {steps} steps — exactly one per "
            "bucket per step is the contract")
        params = [np.asarray(p.numpy()) for p in net.parameters()]
        telem = {"compiles": obs_metrics.counter("compile.count").value
                 - compiles0,
                 "step_time_ms": {
                     k: (round(v * 1e3, 3) if v is not None else None)
                     for k, v in timer.percentiles().items()}}
        return dt, params, final_loss, n_buckets, stats, telem

    dt_sync, params_sync, loss_sync, _, _, telem_sync = run("sync")
    dt_ov, params_ov, loss_ov, n_buckets, stats, telem_ov = run("overlap")
    prefetch = profiler.prefetch_stats()

    for a, b in zip(params_ov, params_sync):
        np.testing.assert_allclose(a, b, atol=1e-6)

    reduction = 1.0 - dt_ov / dt_sync
    print(json.dumps({
        "metric": "dp_overlap_step_time_ms",
        "value": round(dt_ov * 1e3, 2),
        "unit": "ms/step",
        "vs_baseline": round(dt_sync / dt_ov, 4),
        "sync_step_time_ms": round(dt_sync * 1e3, 2),
        "reduction_pct": round(reduction * 100, 1),
        "devices": len(devices),
        "buckets": n_buckets,
        "steps": steps,
        "counters": {"reducer": stats, "prefetch": prefetch},
        # step-time percentiles (p50/p95, not just means) + XLA compile
        # counts per schedule, all served from the metrics registry
        "telemetry": {"overlap": telem_ov, "sync": telem_sync},
    }), flush=True)
    print(f"# dp-overlap: sync={dt_sync*1e3:.1f}ms "
          f"overlap={dt_ov*1e3:.1f}ms reduction={reduction*100:.1f}% "
          f"loss_parity={abs(loss_sync - loss_ov):.2e} "
          f"overlap_ratio={stats['overlap_ratio']} "
          f"prefetch_hits={prefetch['hits']}/{prefetch['batches']}",
          file=sys.stderr)
    assert reduction >= min_reduction, (
        f"overlap step-time reduction {reduction*100:.1f}% is below the "
        f"{min_reduction*100:.0f}% floor (sync {dt_sync*1e3:.1f}ms vs "
        f"overlap {dt_ov*1e3:.1f}ms)")


# --------------------------------------------------------------------------
# child: --serving  (continuous-batching serving engine benchmark)
# --------------------------------------------------------------------------

def serving_bench():
    """Continuous-batching serving engine: tokens/s and request latency
    through the slot-pooled KV cache (ISSUE 5 tentpole), then the paged
    KV engine (ISSUE 8) against it at a FIXED KV byte budget.

    Asserts the tentpole claims instead of trusting them: the decode-step
    executable compiles exactly ONCE and stays constant while requests
    churn through slots (a warmup wave fills+drains the pool first, then
    the measured wave runs with zero new XLA compiles anywhere), prefill
    compiles stay bounded by the (batch, seq) bucket-ladder size, and the
    slot-batched engine's per-token LOGITS and token ids match per-request
    ``models.gpt.generate`` to 1e-5.  The paged phase re-runs the same
    mixed-length trace through a PagedServingEngine whose page pool holds
    EXACTLY the baseline pool's bytes, and asserts the ISSUE-8 criteria:
    ``kv_bytes_per_token <= 0.6x`` the slot-contiguous baseline,
    ``>= 1.5x`` admitted concurrency at that byte budget, decode_compiles
    still 1, zero steady-state compiles, and token-exact parity.  A third
    QUANTIZED phase (ISSUE 9: int8 weight-only executables + int8 paged
    KV) re-runs the trace once more at the fp32 paged pool's byte budget
    and asserts ``kv_bytes_per_token <= 0.5x`` the paged-fp32 number,
    ``>= 1.3x`` its admitted concurrency, max logit error within the
    declared budget (BENCH_QUANT_LOGIT_BUDGET, default 0.05) with
    greedy-token match, and the same compile invariants.  Runs on
    any backend (CPU smoke included) — the contract being measured is
    compile reuse + scheduling + memory accounting, not FLOPs.  A fourth
    SPECULATION phase (ISSUE 13, :func:`_serving_spec_phase`) runs
    draft/ngram speculative decoding on a repetitive-suffix workload;
    it is self-contained, so ``BENCH_SERVING_PHASES=spec`` runs it alone
    (tools/spec_smoke.sh's budget) — the base/paged/quant trio is
    monolithic (each phase is the next one's byte-budget baseline) and
    runs whenever the knob includes ``base``.  The ``tp`` phase
    (ISSUE 15, :func:`_serving_tp_phase`) serves past one device on a
    tensor-parallel mesh and now carries the tp x int8 composition pass
    (ISSUE 20); the ``pp`` phase (ISSUE 20, :func:`_serving_pp_phase`)
    serves past one HOST on a 2x2 pp x tp mesh — both self-contained
    and mesh-re-execing like spec.  Knobs:
    BENCH_SERVING_REQUESTS (default 24), BENCH_SERVING_SLOTS (default 4)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu import profiler
    from paddle_tpu.models import gpt as G
    from paddle_tpu.inference.serving import (PagedServingEngine,
                                              ServingEngine)
    from paddle_tpu.observability import metrics as obs_metrics

    phases = {p.strip() for p in os.environ.get(
        "BENCH_SERVING_PHASES", "base,spec,tp,pp").split(",") if p.strip()}
    unknown = phases - {"base", "spec", "tp", "pp"}
    if unknown:
        # a typo'd phase list must not read as a green bench that
        # measured nothing ("base" covers the monolithic
        # base/paged/quant trio; "spec" the speculation phase; "tp"
        # the tensor-parallel phase, ISSUE 15; "pp" the
        # pipeline-stage phase, ISSUE 20)
        sys.exit(f"BENCH_SERVING_PHASES: unknown phase(s) "
                 f"{sorted(unknown)} — valid: base, spec, tp, pp")
    if "base" not in phases:
        if "spec" in phases:
            _serving_spec_phase()
        if "tp" in phases:
            _serving_tp_phase()
        if "pp" in phases:
            _serving_pp_phase()
        return

    slots = int(os.environ.get("BENCH_SERVING_SLOTS", 4))
    # enough requests that the pool must churn whatever the slot count
    n_requests = int(os.environ.get("BENCH_SERVING_REQUESTS",
                                    max(24, 3 * slots)))
    seq_buckets = (8, 16, 32)
    batch_buckets = (1, 2)
    cfg = G.gpt_tiny()
    params = G.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine((params, cfg), slots=slots, max_len=96,
                           seq_buckets=seq_buckets,
                           batch_buckets=batch_buckets,
                           # the measured wave submits everything upfront
                           max_queue=max(n_requests, 8 * slots),
                           capture_logits=True)

    def make_requests(n, seed_off=0):
        r = np.random.RandomState(seed_off)
        return [(r.randint(1, cfg.vocab_size,
                           r.randint(3, 28)).astype(np.int32),
                 int(r.randint(4, 16))) for _ in range(n)]

    # warmup: compile every (batch, seq) ladder executable + the decode
    # step before traffic, exactly like a production server boot
    engine.warmup()
    warm = engine.stats()
    assert warm["decode_compiles"] == 1, warm
    # warmup latencies include compile time — don't let them pollute the
    # measured wave's percentiles; same for its slot-occupancy peak, or
    # the churn assertion below would be satisfied by warmup alone
    obs_metrics.histogram("serving.request_latency_s").reset()
    obs_metrics.histogram("serving.decode_step_s").reset()
    engine.reset_occupancy_peak()
    compiles0 = obs_metrics.counter("compile.count").value
    admitted0 = engine.stats()["requests_admitted"]

    class KVSampler:
        """Per-step KV accounting: time-averaged bytes reserved per
        token actually held, plus paged page-utilization."""

        def __init__(self):
            self.bytes_sum = 0
            self.tok_sum = 0
            self.util = []
            self.n = 0

        def sample(self, st):
            if st["kv_tokens_held"]:
                self.bytes_sum += st["kv_bytes_reserved"]
                self.tok_sum += st["kv_tokens_held"]
                self.n += 1
                if "page_utilization" in st:
                    self.util.append(st["page_utilization"])

        def bytes_per_token(self):
            return self.bytes_sum / max(1, self.tok_sum)

        def mean_util(self):
            return (sum(self.util) / len(self.util)) if self.util else None

    # measured wave: requests churn through slots with ZERO new compiles
    reqs = []
    kv_base = KVSampler()
    t0 = time.perf_counter()
    for p, m in make_requests(n_requests, 2):
        reqs.append(engine.submit(p, m))
    done = []
    while engine._busy():
        done.extend(engine.step())
        kv_base.sample(engine.stats())
    # tokens are host ints already — the engine fetches per step, so the
    # timed region is bounded without an extra device sync
    dt = time.perf_counter() - t0
    stats = engine.stats()
    new_compiles = obs_metrics.counter("compile.count").value - compiles0

    assert len(done) == n_requests, (len(done), n_requests)
    # decode-step compile count CONSTANT through slot churn
    assert stats["decode_compiles"] == 1, stats
    assert new_compiles == 0, (
        f"steady-state serving retraced: {new_compiles} new XLA compiles "
        "during the measured wave")
    ladder = len(seq_buckets) * len(batch_buckets)
    assert stats["prefill_compiles"] <= ladder, (stats, ladder)
    # churn really happened: the measured wave alone outnumbers the pool
    assert stats["requests_admitted"] - admitted0 == n_requests
    assert n_requests > slots
    assert stats["slot_occupancy_peak"] >= min(slots, 2)

    # parity: slot-batched logits + tokens vs per-request generate
    max_logit_diff = 0.0
    for req in reqs[:6]:
        prompt = jnp.asarray(req.prompt)[None]
        want = np.asarray(G.generate(params, cfg, prompt,
                                     req.max_new_tokens))[0,
                                                          len(req.prompt):]
        got = np.asarray(req.tokens)
        assert (want == got).all(), (req.id, want, got)
        # logits replay through the reference single-request cache path
        cache = G.init_cache(cfg, 1, len(req.prompt) + req.max_new_tokens)
        lg, cache = G.forward_cached(params, prompt, cfg, cache)
        ref_rows = [np.asarray(lg[0, -1])]
        for tok in req.tokens[:-1]:
            lg, cache = G.forward_cached(
                params, jnp.asarray([[tok]], jnp.int32), cfg, cache)
            ref_rows.append(np.asarray(lg[0, -1]))
        for ref, row in zip(ref_rows, req.logits):
            max_logit_diff = max(max_logit_diff,
                                 float(np.abs(ref - row).max()))
    assert max_logit_diff < 1e-5, max_logit_diff

    # ---- paged phase (ISSUE 8): same trace, same KV byte budget -------
    # the paged pool holds EXACTLY the baseline pool's positions
    # (slots * max_len), cut into page_size-token pages — any extra
    # concurrency it admits comes from paging alone, not extra memory
    page_size = 8
    max_len = 96
    num_pages = (slots * max_len) // page_size
    paged_slots = 3 * slots
    paged = PagedServingEngine(
        (params, cfg), slots=paged_slots, max_len=max_len,
        page_size=page_size, num_pages=num_pages,
        seq_buckets=seq_buckets, batch_buckets=batch_buckets,
        prefill_chunk=16,                 # prompts > 16 admit chunked
        max_queue=max(n_requests, 8 * paged_slots),
        capture_logits=True)              # the quant phase's fp32 reference
    paged.warmup()
    paged.reset_occupancy_peak()
    assert paged.stats()["kv_bytes_total"] == engine.stats()[
        "kv_bytes_reserved"], "byte budgets diverged"
    compiles1 = obs_metrics.counter("compile.count").value
    kv_paged = KVSampler()
    preqs = []
    t1 = time.perf_counter()
    for p, m in make_requests(n_requests, 2):     # the SAME mixed trace
        preqs.append(paged.submit(p, m))
    pdone = []
    while paged._busy():
        pdone.extend(paged.step())
        kv_paged.sample(paged.stats())
    dt_paged = time.perf_counter() - t1
    pstats = paged.stats()
    paged_new_compiles = (obs_metrics.counter("compile.count").value
                          - compiles1)
    assert len(pdone) == n_requests, (len(pdone), n_requests)
    assert pstats["decode_compiles"] == 1, pstats
    assert paged_new_compiles == 0, (
        f"paged steady state retraced: {paged_new_compiles} new XLA "
        "compiles (warmup must cover ladder + chunk + COW copy)")
    # token-exact parity on the paged path (after the compile assert:
    # gpt.generate itself compiles)
    for req in preqs[:6]:
        want = np.asarray(G.generate(params, cfg,
                                     jnp.asarray(req.prompt)[None],
                                     req.max_new_tokens))[0,
                                                          len(req.prompt):]
        assert (want == np.asarray(req.tokens)).all(), (req.id,)
    bpt_base = kv_base.bytes_per_token()
    bpt_paged = kv_paged.bytes_per_token()
    ratio = bpt_paged / bpt_base
    assert ratio <= 0.6, (
        f"paged kv_bytes_per_token {bpt_paged:.0f} is {ratio:.2f}x the "
        f"slot-contiguous baseline {bpt_base:.0f} (need <= 0.6x)")
    conc_gain = pstats["slot_occupancy_peak"] / max(
        1, stats["slot_occupancy_peak"])
    assert conc_gain >= 1.5, (
        f"paged admitted concurrency {pstats['slot_occupancy_peak']} is "
        f"only {conc_gain:.2f}x the baseline "
        f"{stats['slot_occupancy_peak']} at the same KV byte budget "
        "(need >= 1.5x)")

    # ---- quantized phase (ISSUE 9): same trace, same KV byte budget ---
    # int8 weights + int8 paged KV against the fp32 paged engine: the
    # pool gets however many int8+scale pages fit in the SAME bytes the
    # fp32 paged pool used, so every extra admitted request comes from
    # quantization alone.  Accuracy is gated, not assumed: max logit
    # error within the declared budget AND greedy-token match on the
    # bench prompts.
    logit_budget = float(os.environ.get("BENCH_QUANT_LOGIT_BUDGET", 0.05))
    budget_bytes = pstats["kv_bytes_total"]
    # bytes per page in the int8 pool: 2 pools of 1-byte elements plus
    # 2 fp32 per-position-per-head scale rows, per layer
    q_page_bytes = 2 * cfg.num_layers * (
        page_size * cfg.num_heads * cfg.head_dim
        + page_size * cfg.num_heads * 4)
    q_num_pages = budget_bytes // q_page_bytes
    q_slots = 2 * paged_slots
    quant = PagedServingEngine(
        (params, cfg), slots=q_slots, max_len=max_len,
        page_size=page_size, num_pages=q_num_pages,
        seq_buckets=seq_buckets, batch_buckets=batch_buckets,
        prefill_chunk=16, quant="int8", kv_dtype="int8",
        max_queue=max(n_requests, 8 * q_slots), capture_logits=True)
    quant.warmup()
    quant.reset_occupancy_peak()
    qtotal = quant.stats()["kv_bytes_total"]
    assert qtotal <= budget_bytes, (qtotal, budget_bytes)
    compiles2 = obs_metrics.counter("compile.count").value
    kv_quant = KVSampler()
    qreqs = []
    t2 = time.perf_counter()
    for p, m in make_requests(n_requests, 2):     # the SAME mixed trace
        qreqs.append(quant.submit(p, m))
    qdone = []
    while quant._busy():
        qdone.extend(quant.step())
        kv_quant.sample(quant.stats())
    dt_quant = time.perf_counter() - t2
    qstats = quant.stats()
    quant_new_compiles = (obs_metrics.counter("compile.count").value
                          - compiles2)
    assert len(qdone) == n_requests, (len(qdone), n_requests)
    assert qstats["decode_compiles"] == 1, qstats
    assert quant_new_compiles == 0, (
        f"quantized steady state retraced: {quant_new_compiles} new XLA "
        "compiles")
    # accuracy budget vs the fp32 paged engine on the same prompts:
    # greedy tokens EXACT, per-token logit rows within the budget
    max_quant_err = 0.0
    for pr, qr in zip(preqs, qreqs):
        assert pr.tokens == qr.tokens, (
            f"quantized greedy tokens diverged from fp32 on {qr.id}: "
            f"{pr.tokens} vs {qr.tokens}")
        for fr, qrow in zip(pr.logits, qr.logits):
            max_quant_err = max(max_quant_err,
                                float(np.abs(fr - qrow).max()))
    assert max_quant_err <= logit_budget, (
        f"quantized max logit error {max_quant_err:.4f} exceeds the "
        f"declared budget {logit_budget}")
    bpt_quant = kv_quant.bytes_per_token()
    q_ratio = bpt_quant / bpt_paged
    assert q_ratio <= 0.5, (
        f"quantized kv_bytes_per_token {bpt_quant:.0f} is {q_ratio:.2f}x "
        f"the fp32 paged number {bpt_paged:.0f} (need <= 0.5x)")
    q_conc_gain = qstats["slot_occupancy_peak"] / max(
        1, pstats["slot_occupancy_peak"])
    assert q_conc_gain >= 1.3, (
        f"quantized admitted concurrency {qstats['slot_occupancy_peak']} "
        f"is only {q_conc_gain:.2f}x the fp32 paged "
        f"{pstats['slot_occupancy_peak']} at the same byte budget "
        "(need >= 1.3x)")

    total_tokens = sum(len(r.tokens) for r in reqs)
    paged_tokens = sum(len(r.tokens) for r in preqs)
    quant_tokens = sum(len(r.tokens) for r in qreqs)
    lat = obs_metrics.histogram("serving.request_latency_s").summary()
    counters = profiler.fast_path_summary()
    print(json.dumps({
        "metric": "serving_tokens_per_sec",
        "value": round(total_tokens / dt, 2),
        "unit": "tokens/s",
        "requests": n_requests,
        "slots": slots,
        "latency_ms": {"p50": round(lat["p50"] * 1e3, 3),
                       "p95": round(lat["p95"] * 1e3, 3)},
        "decode_step_ms": {
            "p50": round(obs_metrics.histogram("serving.decode_step_s")
                         .percentile(50) * 1e3, 3),
            "p95": round(obs_metrics.histogram("serving.decode_step_s")
                         .percentile(95) * 1e3, 3)},
        "max_logit_diff": max_logit_diff,
        "kv": {
            "baseline": {
                "kv_bytes_total": engine.stats()["kv_bytes_reserved"],
                "kv_bytes_per_token": round(bpt_base, 1),
                "admitted_concurrency": stats["slot_occupancy_peak"]},
            "paged": {
                "kv_bytes_total": pstats["kv_bytes_total"],
                "kv_bytes_per_token": round(bpt_paged, 1),
                "page_utilization": round(kv_paged.mean_util() or 0, 4),
                "admitted_concurrency": pstats["slot_occupancy_peak"],
                "page_size": page_size, "num_pages": num_pages,
                "paged_slots": paged_slots,
                "tokens_per_sec": round(paged_tokens / dt_paged, 2),
                "prefix_page_hits": pstats["prefix_page_hits"],
                "prefill_chunks": pstats["prefill_chunks"],
                "cow_copies": pstats["cow_copies"],
                "preemptions": pstats["preemptions"]},
            "quant": {
                "quant": "int8", "kv_dtype": "int8",
                "kv_bytes_total": qstats["kv_bytes_total"],
                "kv_bytes_per_token": round(bpt_quant, 1),
                "bytes_per_token_vs_paged": round(q_ratio, 4),
                "page_utilization": round(kv_quant.mean_util() or 0, 4),
                "admitted_concurrency": qstats["slot_occupancy_peak"],
                "concurrency_gain_vs_paged": round(q_conc_gain, 2),
                "num_pages": q_num_pages, "slots": q_slots,
                "tokens_per_sec": round(quant_tokens / dt_quant, 2),
                "max_logit_err": round(max_quant_err, 6),
                "logit_budget": logit_budget,
                "greedy_match": True,
                "prefix_page_hits": qstats["prefix_page_hits"],
                "quant_matmuls": qstats["quant_matmuls"],
                "kv_quant_bytes_saved": qstats["kv_quant_bytes_saved"],
                "dequant_kernel_calls":
                    counters["serving"].get("dequant_kernel_calls", 0),
                "preemptions": qstats["preemptions"]},
            "bytes_per_token_ratio": round(ratio, 4),
            "concurrency_gain": round(conc_gain, 2)},
        "telemetry": {"steady_state_compiles": new_compiles,
                      "paged_steady_state_compiles": paged_new_compiles,
                      "quant_steady_state_compiles": quant_new_compiles,
                      "registry": {"serving": counters["serving"]}},
    }), flush=True)
    print(f"# serving: {total_tokens / dt:.1f} tok/s "
          f"over {n_requests} churned requests on {slots} slots, "
          f"prefill_compiles={stats['prefill_compiles']}<=ladder {ladder}, "
          f"decode_compiles={stats['decode_compiles']}, "
          f"logit_parity={max_logit_diff:.2e}", file=sys.stderr)
    print(f"# serving/paged: {paged_tokens / dt_paged:.1f} tok/s, "
          f"kv bytes/token {bpt_paged:.0f} vs {bpt_base:.0f} "
          f"({ratio:.2f}x <= 0.6x), concurrency "
          f"{pstats['slot_occupancy_peak']} vs "
          f"{stats['slot_occupancy_peak']} ({conc_gain:.1f}x >= 1.5x), "
          f"chunks={pstats['prefill_chunks']}, "
          f"preemptions={pstats['preemptions']}", file=sys.stderr)
    print(f"# serving/quant: {quant_tokens / dt_quant:.1f} tok/s, "
          f"kv bytes/token {bpt_quant:.0f} vs paged {bpt_paged:.0f} "
          f"({q_ratio:.2f}x <= 0.5x), concurrency "
          f"{qstats['slot_occupancy_peak']} vs "
          f"{pstats['slot_occupancy_peak']} ({q_conc_gain:.1f}x >= 1.3x), "
          f"logit_err={max_quant_err:.2e} <= {logit_budget}, "
          f"greedy tokens exact", file=sys.stderr)

    # ---- speculation phase (ISSUE 13): drafting + one-step verify ----
    if "spec" in phases:
        _serving_spec_phase()
    # ---- tensor-parallel phase (ISSUE 15): serve past one device ----
    if "tp" in phases:
        _serving_tp_phase()
    # ---- pipeline-stage phase (ISSUE 20): serve past one HOST ----
    if "pp" in phases:
        _serving_pp_phase()


def _serving_tp_phase():
    """Tensor-parallel serving phase (ISSUE 15 tentpole): a gpt config
    whose fp32 weights EXCEED one simulated device's byte budget serves
    on a 2-device tp mesh — params placed with the megatron column/row
    rules from distributed/auto/rules.py, the paged KV pool sharded
    over 'tp' on the head axis — and the phase asserts the claims:

    * full fp32 param bytes > BENCH_TP_DEVICE_BUDGET_MB (default 8MB:
      the simulated per-device budget) while the SHARDED engine's
      per-device param bytes fit under it,
    * decode_compiles == 1 and ZERO steady-state XLA compiles through
      a churned mixed-length wave (chunked prefill included),
    * token-exact greedy parity vs the single-device
      ``models.gpt.generate`` reference on every request.

    A second COMPOSITION pass (ISSUE 20) re-runs the same trace through
    ``PagedServingEngine(tp=2, quant="int8", kv_dtype="int8")`` — the
    combination the tp=1-only quant guard used to refuse — at the fp32
    tp engine's exact KV byte budget, and asserts greedy tokens still
    match the single-device fp32 reference, per-token logit rows within
    BENCH_QUANT_LOGIT_BUDGET (default 0.05) of the fp32 tp engine, and
    ``kv_bytes_per_token <= 0.5x`` the tp fp32 paged number.

    Needs >= 2 devices: on a single-device backend the phase re-execs
    itself as a ``--cpu-mesh 2`` child running only this phase, so
    ``bench.py --serving`` always emits the serving_tp_tokens_per_sec
    metric line.  Knobs: BENCH_TP_DEGREE (default 2),
    BENCH_TP_DEVICE_BUDGET_MB (8), BENCH_TP_REQUESTS (16)."""
    import jax
    tp = int(os.environ.get("BENCH_TP_DEGREE", 2))
    if jax.device_count() < tp:
        env = dict(os.environ)
        env["BENCH_SERVING_PHASES"] = "tp"
        env.pop("BENCH_CPU_MESH_CHILD", None)
        print(f"# serving/tp: {jax.device_count()} device(s) visible — "
              f"re-running the tp phase on a --cpu-mesh {tp} child",
              file=sys.stderr)
        rc = subprocess.call(
            [sys.executable, "-u", os.path.abspath(__file__),
             "--serving", "--cpu-mesh", str(tp)], env=env)
        if rc != 0:
            sys.exit(f"serving tp phase failed in the cpu-mesh child "
                     f"(rc={rc})")
        return

    import numpy as np
    import jax.numpy as jnp
    from paddle_tpu.models import gpt as G
    from paddle_tpu.distributed.auto import rules
    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.observability import metrics as obs_metrics

    budget = int(float(os.environ.get("BENCH_TP_DEVICE_BUDGET_MB", 8))
                 * 2**20)
    n_requests = int(os.environ.get("BENCH_TP_REQUESTS", 16))
    # ~13.8MB of fp32 weights: over the 8MB simulated device budget
    # replicated, ~7.2MB/device sharded at tp=2
    cfg = G.GPTConfig(
        vocab_size=int(os.environ.get("BENCH_TP_VOCAB", 1024)),
        hidden_size=int(os.environ.get("BENCH_TP_HIDDEN", 256)),
        num_layers=int(os.environ.get("BENCH_TP_LAYERS", 4)),
        num_heads=int(os.environ.get("BENCH_TP_HEADS", 4)),
        max_seq_len=128, dtype="float32", use_flash=False, remat=False)
    params = G.init_params(cfg, jax.random.PRNGKey(0))
    full_bytes = rules.bytes_per_device(params)
    assert full_bytes > budget, (
        f"tp phase config fits one device ({full_bytes} <= {budget} "
        "bytes) — it would prove nothing; raise the model or lower "
        "BENCH_TP_DEVICE_BUDGET_MB")

    engine = PagedServingEngine(
        (params, cfg), tp=tp, slots=4, max_len=96, page_size=8,
        seq_buckets=(8, 16, 32), batch_buckets=(1, 2), prefill_chunk=16,
        max_queue=max(n_requests, 32), capture_logits=True)
    per_dev = engine.param_bytes_per_device()
    assert per_dev <= budget, (
        f"sharded params still exceed the per-device budget: "
        f"{per_dev} > {budget} bytes at tp={tp}")
    engine.warmup()
    engine.reset_occupancy_peak()
    compiles0 = obs_metrics.counter("compile.count").value

    class KVSampler:
        """Per-step KV accounting: time-averaged bytes reserved per
        token actually held (same estimator as the base trio's)."""

        def __init__(self):
            self.bytes_sum = 0
            self.tok_sum = 0

        def sample(self, st):
            if st["kv_tokens_held"]:
                self.bytes_sum += st["kv_bytes_reserved"]
                self.tok_sum += st["kv_tokens_held"]

        def bytes_per_token(self):
            return self.bytes_sum / max(1, self.tok_sum)

    def make_requests():
        # identical trace for the fp32 and int8 passes
        r = np.random.RandomState(5)
        out = []
        for _ in range(n_requests):
            # lengths span the ladder AND the chunked path (> chunk)
            p = r.randint(1, cfg.vocab_size,
                          r.randint(3, 30)).astype(np.int32)
            out.append((p, int(r.randint(4, 14))))
        return out

    kv_fp32 = KVSampler()
    reqs = []
    t0 = time.perf_counter()
    for p, m in make_requests():
        reqs.append(engine.submit(p, m))
    done = []
    while engine._busy():
        done.extend(engine.step())
        kv_fp32.sample(engine.stats())
    dt = time.perf_counter() - t0
    st = engine.stats()
    new_compiles = obs_metrics.counter("compile.count").value - compiles0

    assert len(done) == n_requests, (len(done), n_requests)
    assert st["decode_compiles"] == 1, st
    assert new_compiles == 0, (
        f"tp steady state retraced: {new_compiles} new XLA compiles")
    assert st["tp"] == tp, st
    # token-exact greedy parity vs the SINGLE-DEVICE reference (the
    # renegotiation-free invariant: sharding must change the clock,
    # never the tokens) — after the compile assert, generate compiles
    wants = []
    for req in reqs:
        want = np.asarray(G.generate(params, cfg,
                                     jnp.asarray(req.prompt)[None],
                                     req.max_new_tokens))[0,
                                                          len(req.prompt):]
        wants.append(want)
        assert (want == np.asarray(req.tokens)).all(), (
            f"tp engine lost token parity on {req.id}: "
            f"{list(want)} vs {req.tokens}")

    total_tokens = sum(len(r.tokens) for r in done)
    print(json.dumps({
        "metric": "serving_tp_tokens_per_sec",
        "value": round(total_tokens / dt, 2),
        "unit": "tokens/s",
        "tp": tp,
        "devices": jax.device_count(),
        "param_bytes_full": int(full_bytes),
        "param_bytes_per_device": int(per_dev),
        "device_budget_bytes": budget,
        "fits_one_device": False,
        "per_device_under_budget": True,
        "requests": n_requests,
        "decode_compiles": st["decode_compiles"],
        "steady_state_compiles": new_compiles,
        "prefill_chunks": st["prefill_chunks"],
        "token_parity": True,
    }), flush=True)
    print(f"# serving/tp: {full_bytes / 2**20:.1f}MB fp32 model (> "
          f"{budget / 2**20:.0f}MB/device budget) served on a {tp}-dev "
          f"tp mesh at {per_dev / 2**20:.1f}MB/device, "
          f"{total_tokens / dt:.1f} tok/s, decode_compiles=1, "
          f"0 steady-state compiles, token-exact vs single-device",
          file=sys.stderr)

    # ---- tp x int8 composition pass (ISSUE 20): the pair the old
    # guard refused.  Same trace, the fp32 tp engine's exact KV byte
    # budget, weights AND KV quantized — sharding plus quantization
    # must still change only the clock, never the tokens.
    logit_budget = float(os.environ.get("BENCH_QUANT_LOGIT_BUDGET",
                                        0.05))
    budget_bytes = st["kv_bytes_total"]
    # bytes per page in the int8 pool: 2 pools of 1-byte elements plus
    # 2 fp32 per-position-per-head scale rows, per layer
    q_page_bytes = 2 * cfg.num_layers * (
        8 * cfg.num_heads * cfg.head_dim + 8 * cfg.num_heads * 4)
    quant = PagedServingEngine(
        (params, cfg), tp=tp, quant="int8", kv_dtype="int8", slots=4,
        max_len=96, page_size=8, num_pages=budget_bytes // q_page_bytes,
        seq_buckets=(8, 16, 32), batch_buckets=(1, 2), prefill_chunk=16,
        max_queue=max(n_requests, 32), capture_logits=True)
    qtotal = quant.stats()["kv_bytes_total"]
    assert qtotal <= budget_bytes, (qtotal, budget_bytes)
    quant.warmup()
    quant.reset_occupancy_peak()
    compiles1 = obs_metrics.counter("compile.count").value
    kv_int8 = KVSampler()
    qreqs = []
    t1 = time.perf_counter()
    for p, m in make_requests():                  # the SAME mixed trace
        qreqs.append(quant.submit(p, m))
    qdone = []
    while quant._busy():
        qdone.extend(quant.step())
        kv_int8.sample(quant.stats())
    dt_q = time.perf_counter() - t1
    qst = quant.stats()
    q_new = obs_metrics.counter("compile.count").value - compiles1
    assert len(qdone) == n_requests, (len(qdone), n_requests)
    assert qst["decode_compiles"] == 1, qst
    assert q_new == 0, (
        f"tp x int8 steady state retraced: {q_new} new XLA compiles")
    # greedy tokens vs the SINGLE-DEVICE FP32 reference (not merely the
    # fp32 tp engine): quantization noise must stay under the argmax
    max_err = 0.0
    for want, fr, qr in zip(wants, reqs, qreqs):
        assert (want == np.asarray(qr.tokens)).all(), (
            f"tp x int8 greedy tokens diverged from the fp32 "
            f"single-device reference on {qr.id}: "
            f"{list(want)} vs {qr.tokens}")
        for frow, qrow in zip(fr.logits, qr.logits):
            max_err = max(max_err, float(np.abs(frow - qrow).max()))
    assert max_err <= logit_budget, (
        f"tp x int8 max logit error {max_err:.4f} exceeds the declared "
        f"budget {logit_budget}")
    bpt_fp32 = kv_fp32.bytes_per_token()
    bpt_int8 = kv_int8.bytes_per_token()
    q_ratio = bpt_int8 / bpt_fp32
    assert q_ratio <= 0.5, (
        f"tp x int8 kv_bytes_per_token {bpt_int8:.0f} is "
        f"{q_ratio:.2f}x the tp fp32 paged number {bpt_fp32:.0f} "
        "(need <= 0.5x)")
    q_tokens = sum(len(r.tokens) for r in qdone)
    print(json.dumps({
        "metric": "serving_tp_int8_tokens_per_sec",
        "value": round(q_tokens / dt_q, 2),
        "unit": "tokens/s",
        "tp": tp,
        "quant": "int8",
        "kv_dtype": "int8",
        "kv_bytes_per_token_fp32": round(bpt_fp32, 1),
        "kv_bytes_per_token_int8": round(bpt_int8, 1),
        "kv_bytes_ratio": round(q_ratio, 3),
        "max_logit_err": round(max_err, 6),
        "logit_budget": logit_budget,
        "decode_compiles": qst["decode_compiles"],
        "steady_state_compiles": q_new,
        "token_parity": True,
    }), flush=True)
    print(f"# serving/tp+int8: {q_tokens / dt_q:.1f} tok/s at tp={tp}, "
          f"kv bytes/token {bpt_int8:.0f} vs fp32 {bpt_fp32:.0f} "
          f"({q_ratio:.2f}x <= 0.5x), logit_err={max_err:.2e} <= "
          f"{logit_budget}, greedy tokens exact vs single-device fp32",
          file=sys.stderr)


def _serving_pp_phase():
    """Pipeline-stage serving phase (ISSUE 20 tentpole): a gpt config
    whose fp32 weights EXCEED the combined byte budget of an entire
    tp=2 tier (2 devices x BENCH_PP_DEVICE_BUDGET_MB, default 8MB each)
    serves on a 2x2 ('pp','tp') mesh — depth split into pp stage rows
    running the 1F1B microbatch loop inside ONE donated decode
    executable, width split over tp within each stage — and asserts:

    * full fp32 param bytes > tp_degree x budget (tensor parallelism
      ALONE cannot place this model on one tier: the pp axis is doing
      real memory work),
    * every stage row's per-device bytes (params + stage-local KV
      pool, :meth:`stage_bytes`) fit under the budget,
    * decode_compiles == 1 — ONE stage-loop executable spans all
      stages; there is no per-stage program to drift — and ZERO
      steady-state XLA compiles through a churned mixed-length wave,
    * token-exact greedy parity vs the single-device
      ``models.gpt.generate`` reference on every request.

    Needs >= 4 devices: on a smaller backend the phase re-execs itself
    as a ``--cpu-mesh 4`` child running only this phase, so
    ``bench.py --serving`` always emits the serving_pp_tokens_per_sec
    metric line.  Knobs: BENCH_PP_STAGES (default 2), BENCH_TP_DEGREE
    (2), BENCH_PP_DEVICE_BUDGET_MB (8), BENCH_PP_REQUESTS (12)."""
    import jax
    pp = int(os.environ.get("BENCH_PP_STAGES", 2))
    tp = int(os.environ.get("BENCH_TP_DEGREE", 2))
    if jax.device_count() < pp * tp:
        env = dict(os.environ)
        env["BENCH_SERVING_PHASES"] = "pp"
        env.pop("BENCH_CPU_MESH_CHILD", None)
        print(f"# serving/pp: {jax.device_count()} device(s) visible — "
              f"re-running the pp phase on a --cpu-mesh {pp * tp} "
              "child", file=sys.stderr)
        rc = subprocess.call(
            [sys.executable, "-u", os.path.abspath(__file__),
             "--serving", "--cpu-mesh", str(pp * tp)], env=env)
        if rc != 0:
            sys.exit(f"serving pp phase failed in the cpu-mesh child "
                     f"(rc={rc})")
        return

    import numpy as np
    import jax.numpy as jnp
    from paddle_tpu.models import gpt as G
    from paddle_tpu.distributed.auto import rules
    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.observability import metrics as obs_metrics

    budget = int(float(os.environ.get("BENCH_PP_DEVICE_BUDGET_MB", 8))
                 * 2**20)
    n_requests = int(os.environ.get("BENCH_PP_REQUESTS", 12))
    # ~21MB of fp32 weights: over a 2-device tier's 16MB combined
    # budget, ~5.3MB/device on the 2x2 pp x tp grid
    cfg = G.GPTConfig(
        vocab_size=int(os.environ.get("BENCH_PP_VOCAB", 1024)),
        hidden_size=int(os.environ.get("BENCH_PP_HIDDEN", 320)),
        num_layers=int(os.environ.get("BENCH_PP_LAYERS", 4)),
        num_heads=int(os.environ.get("BENCH_PP_HEADS", 4)),
        max_seq_len=128, dtype="float32", use_flash=False, remat=False)
    params = G.init_params(cfg, jax.random.PRNGKey(0))
    full_bytes = rules.bytes_per_device(params)
    assert full_bytes > tp * budget, (
        f"pp phase config fits a tp={tp} tier ({full_bytes} <= "
        f"{tp * budget} bytes) — it would prove nothing about the pp "
        "axis; raise the model or lower BENCH_PP_DEVICE_BUDGET_MB")

    # slots % pp == 0 so decode runs pp microbatches (real 1F1B
    # overlap, no bubble-only schedule); no prefill_chunk — pp
    # prefills whole buckets through the stage ring
    engine = PagedServingEngine(
        (params, cfg), tp=tp, pp=pp, slots=4, max_len=96, page_size=8,
        seq_buckets=(8, 16, 32), batch_buckets=(1, 2),
        max_queue=max(n_requests, 32))
    stages = engine.stage_bytes()
    assert len(stages) == pp, stages
    for s, row in enumerate(stages):
        got = row["params"] + row["kv"]
        assert got <= budget, (
            f"stage {s} exceeds the per-device budget: params "
            f"{row['params']} + kv {row['kv']} = {got} > {budget}")
    engine.warmup()
    engine.reset_occupancy_peak()
    compiles0 = obs_metrics.counter("compile.count").value

    rng = np.random.RandomState(7)
    reqs = []
    t0 = time.perf_counter()
    for _ in range(n_requests):
        p = rng.randint(1, cfg.vocab_size,
                        rng.randint(3, 30)).astype(np.int32)
        reqs.append(engine.submit(p, int(rng.randint(4, 14))))
    done = []
    while engine._busy():
        done.extend(engine.step())
    dt = time.perf_counter() - t0
    st = engine.stats()
    new_compiles = obs_metrics.counter("compile.count").value - compiles0

    assert len(done) == n_requests, (len(done), n_requests)
    assert st["decode_compiles"] == 1, st
    assert new_compiles == 0, (
        f"pp steady state retraced: {new_compiles} new XLA compiles")
    assert st["pp"] == pp and st["tp"] == tp, st
    # token-exact greedy parity vs the SINGLE-DEVICE reference — the
    # 1F1B schedule and the psum('tp') partial sums must change the
    # clock, never the tokens
    for req in reqs:
        want = np.asarray(G.generate(params, cfg,
                                     jnp.asarray(req.prompt)[None],
                                     req.max_new_tokens))[0,
                                                          len(req.prompt):]
        assert (want == np.asarray(req.tokens)).all(), (
            f"pp engine lost token parity on {req.id}: "
            f"{list(want)} vs {req.tokens}")

    total_tokens = sum(len(r.tokens) for r in done)
    print(json.dumps({
        "metric": "serving_pp_tokens_per_sec",
        "value": round(total_tokens / dt, 2),
        "unit": "tokens/s",
        "pp": pp,
        "tp": tp,
        "devices": jax.device_count(),
        "param_bytes_full": int(full_bytes),
        "stage_bytes": [{k: int(v) for k, v in row.items()}
                        for row in stages],
        "device_budget_bytes": budget,
        "fits_one_tier": False,
        "per_stage_under_budget": True,
        "requests": n_requests,
        "decode_compiles": st["decode_compiles"],
        "steady_state_compiles": new_compiles,
        "token_parity": True,
    }), flush=True)
    worst = max(r["params"] + r["kv"] for r in stages)
    print(f"# serving/pp: {full_bytes / 2**20:.1f}MB fp32 model (> "
          f"{tp * budget / 2**20:.0f}MB tp={tp} tier budget) served on "
          f"a {pp}x{tp} pp x tp mesh at {worst / 2**20:.1f}MB/device "
          f"worst stage, {total_tokens / dt:.1f} tok/s, "
          f"decode_compiles=1 across all {pp} stages, 0 steady-state "
          f"compiles, token-exact vs single-device", file=sys.stderr)


def _serving_spec_phase():
    """Speculation phase (ISSUE 13): draft-model and prompt-lookup
    speculative decoding over the paged engine, on a repetitive-suffix
    workload (testing/traffic.py's shared-prefix knob; greedy decoding
    of the seeded model settles into attractor cycles — exactly the
    repetitive traffic prompt-lookup drafting exploits).  Self-contained
    (builds its own non-speculative reference engine) so the smoke can
    run it alone via ``BENCH_SERVING_PHASES=spec``.

    Asserts, per mode (``ngram`` model-free; ``draft`` with a
    same-config same-seed self-draft — the acceptance-machinery
    attestation, acceptance ~= k by construction):

    * accepted_tokens/step > 1.5 (the >1 speedup factor vs one-token
      decode; BENCH_SPEC_MIN_ACCEPT overrides),
    * token-EXACT greedy parity vs the non-speculative paged engine on
      every request,
    * the fixed executable set: ``decode_compiles == 1`` (the one
      donated verify step — never a compile per accept length),
      ``spec_draft_compiles`` <= 2 (draft prefill + the fused
      catch-up/draft step; 0 for ngram), prefill ladder bound,
    * zero steady-state XLA compiles after warmup,
    * and on ``kv_dtype="int8"``: token parity vs a non-speculative
      int8 engine plus live prefix-page hits (the page-byte/prefix-hash
      determinism contract is byte-asserted in tests/test_speculative.py;
      here the shared-prefix cache demonstrably still matches).
    Knobs: BENCH_SPEC_REQUESTS (default 12), BENCH_SPEC_K (default 4),
    BENCH_SPEC_INT8=0 skips the int8 leg (the CPU smoke's budget)."""
    import dataclasses
    import numpy as np
    import jax
    from paddle_tpu.models import gpt as G
    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.inference.speculative import SpeculativeServingEngine
    from paddle_tpu.observability import metrics as obs_metrics
    from paddle_tpu.testing import traffic

    cfg = G.gpt_tiny()
    params = G.init_params(cfg, jax.random.PRNGKey(0))
    spec_k = int(os.environ.get("BENCH_SPEC_K", 4))
    n_req = int(os.environ.get("BENCH_SPEC_REQUESTS", 12))
    min_accept = float(os.environ.get("BENCH_SPEC_MIN_ACCEPT", 1.5))
    # prefix_len == page_size: the shared system prefix fills a whole
    # page, so the prefix-page cache can actually hit (a partial-page
    # prefix hashes together with the request-unique tail)
    arrivals = traffic.generate(traffic.TrafficSpec(
        duration_s=2.0 * n_req, base_rate=1.0, seed=11,
        vocab=cfg.vocab_size, prompt_len=(10, 0.3, 9, 12),
        output_tokens=(24, 0.3, 16, 32),
        prefix_hit_rate=0.75, prefix_pool=2, prefix_len=8))[:n_req]
    assert len(arrivals) == n_req, (len(arrivals), n_req)
    work = [(a.prompt, a.max_new_tokens) for a in arrivals]
    kw = dict(slots=4, max_len=48, page_size=8, seq_buckets=(8, 16),
              batch_buckets=(1, 2), max_queue=4 * n_req)

    ref = PagedServingEngine((params, cfg), **kw)
    ref.warmup()
    t0 = time.perf_counter()
    rrefs = [ref.submit(p, m) for p, m in work]
    ref.run()
    dt_ref = time.perf_counter() - t0
    ref_tokens = [r.tokens for r in rrefs]
    ref_steps = ref.stats()["decode_steps"]

    modes = {}
    for mode, mkw in (("ngram", {}),
                      ("draft", {"spec_draft_cfg": dataclasses.asdict(cfg),
                                 "spec_draft_seed": 0})):
        eng = SpeculativeServingEngine((params, cfg), spec_mode=mode,
                                       spec_k=spec_k, **mkw, **kw)
        eng.warmup()
        compiles0 = obs_metrics.counter("compile.count").value
        t1 = time.perf_counter()
        reqs = [eng.submit(p, m) for p, m in work]
        eng.run(max_steps=100 * n_req)
        dt = time.perf_counter() - t1
        st = eng.stats()
        new_compiles = obs_metrics.counter("compile.count").value - compiles0
        for r, want in zip(reqs, ref_tokens):
            assert r.tokens == want, (
                f"spec/{mode} diverged from the non-speculative paged "
                f"engine on {r.id}: {r.tokens} vs {want}")
        assert st["decode_compiles"] == 1, st
        assert new_compiles == 0, (
            f"spec/{mode} steady state retraced: {new_compiles} new XLA "
            "compiles (the verify must never compile per accept length)")
        draft_budget = 2 if mode == "draft" else 0
        assert st["spec_draft_compiles"] <= draft_budget, st
        ladder = len(kw["seq_buckets"]) * len(kw["batch_buckets"])
        assert st["prefill_compiles"] <= ladder, (st, ladder)
        acc = st["accepted_tokens_per_step"]
        assert acc > min_accept, (
            f"spec/{mode} accepted_tokens/step {acc} <= {min_accept} on "
            "the repetitive-suffix workload")
        modes[mode] = {
            "accepted_tokens_per_step": acc,
            "spec_steps": st["spec_steps"],
            "decode_steps": st["decode_steps"],
            "drafted_tokens": st["drafted_tokens"],
            "accepted_tokens": st["accepted_tokens"],
            "rejected_tokens": st["rejected_tokens"],
            "decode_compiles": st["decode_compiles"],
            "spec_draft_compiles": st["spec_draft_compiles"],
            "steady_state_compiles": new_compiles,
            "tokens_per_sec": round(
                sum(len(r.tokens) for r in reqs) / dt, 2),
            "target_forwards_vs_nonspec": round(
                st["decode_steps"] / max(1, ref_steps), 4),
        }
        print(f"# serving/spec {mode}: acc/step={acc} (>{min_accept}), "
              f"parity token-exact over {n_req} requests, "
              f"decode_compiles={st['decode_compiles']}, "
              f"spec_draft_compiles={st['spec_draft_compiles']}, "
              f"steady_compiles={new_compiles}, "
              f"verify_steps={st['decode_steps']} vs "
              f"{ref_steps} non-spec decode steps", file=sys.stderr)

    int8_leg = None
    if os.environ.get("BENCH_SPEC_INT8", "1") != "0":
        q_ref = PagedServingEngine((params, cfg), quant="int8",
                                   kv_dtype="int8", **kw)
        q_ref.warmup()
        q_refs = [q_ref.submit(p, m) for p, m in work]
        q_ref.run()
        q_spec = SpeculativeServingEngine((params, cfg), spec_mode="ngram",
                                          spec_k=spec_k, quant="int8",
                                          kv_dtype="int8", **kw)
        q_spec.warmup()
        q_reqs = [q_spec.submit(p, m) for p, m in work]
        q_spec.run(max_steps=100 * n_req)
        qst = q_spec.stats()
        for a, b in zip(q_refs, q_reqs):
            assert a.tokens == b.tokens, (
                f"spec int8 diverged from non-spec int8 on {b.id}")
        assert qst["decode_compiles"] == 1, qst
        # the shared-prefix cache still hits under speculation: page
        # bytes (prompt pages are never touched by the spec window, and
        # committed positions write sequential-exact bytes) stayed
        # deterministic enough for the content-hash contract
        assert qst["prefix_page_hits"] > 0, qst
        int8_leg = {
            "accepted_tokens_per_step": qst["accepted_tokens_per_step"],
            "prefix_page_hits": qst["prefix_page_hits"],
            "greedy_match_vs_nonspec_int8": True,
            "decode_compiles": qst["decode_compiles"]}
        print(f"# serving/spec int8: acc/step="
              f"{qst['accepted_tokens_per_step']}, parity token-exact vs "
              f"non-spec int8, prefix_page_hits="
              f"{qst['prefix_page_hits']}", file=sys.stderr)

    print(json.dumps({
        "metric": "serving_spec_accepted_tokens_per_step",
        "value": modes["ngram"]["accepted_tokens_per_step"],
        "unit": "tokens/step",
        "requests": n_req, "spec_k": spec_k,
        "min_accept": min_accept,
        "parity": "token-exact",
        "workload": {"prefix_hit_rate": 0.75,
                     "nonspec_decode_steps": ref_steps,
                     "nonspec_tokens_per_sec": round(
                         sum(len(t) for t in ref_tokens) / dt_ref, 2)},
        "modes": modes,
        "int8": int8_leg,
    }), flush=True)


# --------------------------------------------------------------------------
# --model-parallel  (composed TP+PP+ZeRO train step on the mesh)
# --------------------------------------------------------------------------

def model_parallel_bench():
    """Model-parallel scale-out (ISSUE 10): the composed GSPMD TP + 1F1B
    PP + ZeRO train step (paddle_tpu.distributed.auto) on a dp×tp×pp
    mesh (default 2x2x2 over 8 devices; ``--cpu-mesh 8`` forces the
    host-platform mesh).  Three asserted phases:

      parity    a FITTING config (gpt_tiny) trains BENCH_MP_STEPS steps
                on the mesh (zero_stage=2, microbatched pipeline) and
                against a jitted single-device reference with identical
                AdamW/clip semantics; per-step |loss diff| must stay
                within BENCH_MP_PARITY (default 1e-5).
      scale     a config whose REPLICATED params+Adam moments exceed the
                simulated per-device budget (BENCH_MP_DEVICE_BUDGET_MB,
                default 8) trains on the mesh; the per-device param +
                optimizer bytes actually pinned (addressable shards)
                must fit the budget, and the loss must fall.
      contract  optimizer-state bytes/device shrink >= BENCH_MP_MIN_SHRINK
                (default 1.9 — the dp=2 ZeRO floor; tp/pp sharding
                pushes it well past) vs replication, and the sharding.*
                counters match the step's static collective plan exactly:
                ONE dp reduce-scatter per param bucket per step, the
                planned tp psums and pp ppermute handoffs per axis.

    Always prints the parsed JSON metric line
    (model_parallel_step_time_ms) before enforcing the floors."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed import auto
    from paddle_tpu.models import gpt
    from paddle_tpu.optimizer.functional import adamw_update
    from paddle_tpu.observability import metrics as obs_metrics
    from paddle_tpu.observability import timeline as obs_timeline
    obs_timeline.install_compile_hook()   # count XLA retraces honestly

    steps = int(os.environ.get("BENCH_MP_STEPS", 5))
    budget_mb = float(os.environ.get("BENCH_MP_DEVICE_BUDGET_MB", 8))
    parity_tol = float(os.environ.get("BENCH_MP_PARITY", 1e-5))
    min_shrink = float(os.environ.get("BENCH_MP_MIN_SHRINK", 1.9))
    dp, tp, pp = (int(x) for x in
                  os.environ.get("BENCH_MP_MESH", "2x2x2").split("x"))
    micro = int(os.environ.get("BENCH_MP_MICRO", 2))
    LR = 1e-3
    HY = dict(beta1=0.9, beta2=0.95, eps=1e-8, weight_decay=0.1,
              clip_norm=1.0)
    mesh = auto.make_mesh(dp=dp, tp=tp, pp=pp)
    key = jax.random.PRNGKey(0)

    def batch_for(cfg, seq):
        rng = np.random.RandomState(0)
        toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, seq)),
                           jnp.int32)
        return toks, toks

    def mesh_losses(cfg, toks, labels):
        params, m, v = auto.init_state(cfg, mesh, key, zero_stage=2)
        step = auto.make_train_step(cfg, mesh, n_microbatch=micro,
                                    zero_stage=2, **HY)
        losses, t_first = [], None
        t0 = time.perf_counter()
        for t in range(1, steps + 1):
            params, m, v, loss = step(params, m, v, t, toks, labels, LR)
            losses.append(float(loss))       # host sync per step
            if t == 1:
                t_first = time.perf_counter() - t0
        dt = ((time.perf_counter() - t0 - t_first) / max(steps - 1, 1)
              if steps > 1 else t_first)
        return losses, dt, step.plan

    # ---- phase 1: parity (fitting config vs single-device reference)
    fit_cfg = gpt.gpt_tiny()
    toks, labels = batch_for(fit_cfg, 64)
    mesh_l, _, _ = mesh_losses(fit_cfg, toks, labels)

    from paddle_tpu.models.gpt_hybrid import NO_DECAY as no_decay
    from paddle_tpu.models.gpt_hybrid import LN_NAMES as ln_names

    def ref_step(params, m, v, t, tk, lb):
        loss, grads = jax.value_and_grad(
            lambda p: gpt.loss_fn(p, tk, lb, fit_cfg))(params)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                          for g in jax.tree_util.tree_leaves(grads)))
        scale = jnp.minimum(1.0, HY["clip_norm"] / jnp.maximum(gn, 1e-12))
        grads = jax.tree_util.tree_map(lambda g: g * scale, grads)

        def upd(path, p, g, mm, vv):
            leaf = str(getattr(path[-1], "key", path[-1]))
            decay = leaf not in no_decay and leaf not in ln_names
            return adamw_update(p, g, mm, vv, LR, t, HY["beta1"],
                                HY["beta2"], HY["eps"],
                                HY["weight_decay"], decay)
        out = jax.tree_util.tree_map_with_path(upd, params, grads, m, v)
        tup = lambda o: isinstance(o, tuple) and len(o) == 3  # noqa: E731
        return (jax.tree_util.tree_map(lambda o: o[0], out, is_leaf=tup),
                jax.tree_util.tree_map(lambda o: o[1], out, is_leaf=tup),
                jax.tree_util.tree_map(lambda o: o[2], out, is_leaf=tup),
                loss)

    rp = gpt.init_params(fit_cfg, key)
    rm = jax.tree_util.tree_map(lambda p: jnp.zeros_like(p), rp)
    rv = jax.tree_util.tree_map(lambda p: jnp.zeros_like(p), rp)
    jref = jax.jit(ref_step)
    ref_l = []
    for t in range(1, steps + 1):
        rp, rm, rv, loss = jref(rp, rm, rv, jnp.float32(t), toks, labels)
        ref_l.append(float(loss))
    parity = max(abs(a - b) for a, b in zip(mesh_l, ref_l))

    # ---- phase 2: the config that cannot fit replicated
    big_cfg = gpt.GPTConfig(
        vocab_size=int(os.environ.get("BENCH_MP_VOCAB", 1024)),
        hidden_size=int(os.environ.get("BENCH_MP_HIDDEN", 128)),
        num_layers=int(os.environ.get("BENCH_MP_LAYERS", 4)),
        num_heads=8, max_seq_len=128, dtype="float32",
        use_flash=False, remat=False)
    n_params = sum(
        int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(
            jax.eval_shape(lambda k: gpt.init_params(big_cfg, k), key)))
    replicated_mb = n_params * 4 * 3 / (1 << 20)    # params + m + v fp32
    assert replicated_mb > budget_mb, (
        f"scale config too small: replicated params+moments "
        f"{replicated_mb:.1f}MB must exceed the simulated "
        f"{budget_mb:.0f}MB device budget")

    auto.reset_sharding_stats()
    c0 = obs_metrics.counter("compile.count").value
    big_toks, big_labels = batch_for(big_cfg, 64)
    big_l, dt, plan = mesh_losses(big_cfg, big_toks, big_labels)
    compiles = obs_metrics.counter("compile.count").value - c0
    stats = auto.sharding_stats()
    per_device_mb = (stats["param_bytes_per_device"]
                     + stats["opt_state_bytes_per_device"]) / (1 << 20)
    shrink = stats["opt_state_shrink"]
    expected = {"dp": plan.dp_collectives * steps,
                "tp": plan.tp_collectives * steps,
                "pp": plan.pp_collectives * steps}
    got = {ax: stats[f"collectives_{ax}"] for ax in ("dp", "tp", "pp")}

    print(json.dumps({
        "metric": "model_parallel_step_time_ms",
        "value": round(dt * 1e3, 2),
        "unit": "ms/step",
        "mesh": {"dp": dp, "tp": tp, "pp": pp,
                 "devices": dp * tp * pp},
        "steps": steps,
        "n_microbatch": micro,
        "zero_stage": 2,
        "parity_max_loss_diff": parity,
        "loss_first": round(big_l[0], 6),
        "loss_last": round(big_l[-1], 6),
        "device_budget_mb": budget_mb,
        "replicated_state_mb": round(replicated_mb, 2),
        "per_device_state_mb": round(per_device_mb, 2),
        "opt_state_shrink": shrink,
        "bubble_fraction_pct": stats["bubble_fraction_pct"],
        "collectives": {"expected_per_axis": expected, "counted": got,
                        "bytes": {ax: stats[f"bytes_{ax}"]
                                  for ax in ("dp", "tp", "pp")}},
        "zero_leaves": {"sharded": stats["zero_sharded_leaves"],
                        "replicated": stats["zero_replicated_leaves"]},
        "telemetry": {"compiles": compiles},
    }), flush=True)
    print(f"# model-parallel: parity={parity:.2e} (tol {parity_tol}) "
          f"shrink={shrink}x budget={budget_mb}MB "
          f"replicated={replicated_mb:.1f}MB "
          f"per_device={per_device_mb:.2f}MB", file=sys.stderr)

    assert parity <= parity_tol, (
        f"mesh-vs-single-device loss parity {parity:.2e} exceeds "
        f"{parity_tol}")
    assert big_l[-1] < big_l[0] and all(np.isfinite(big_l)), (
        f"scale config failed to train: losses {big_l}")
    assert per_device_mb <= budget_mb, (
        f"per-device state {per_device_mb:.2f}MB exceeds the simulated "
        f"{budget_mb:.0f}MB budget the replicated run failed")
    assert shrink >= min_shrink, (
        f"optimizer-state bytes/device shrink {shrink}x is below the "
        f"{min_shrink}x floor at dp={dp}")
    for ax in ("dp", "tp", "pp"):
        assert got[ax] == expected[ax], (
            f"{ax} collectives {got[ax]} != plan {expected[ax]} — one "
            "collective per bucket per axis per step is the contract")
    print("# model-parallel: ok — sharding counters nonzero and "
          "plan-exact, ZeRO shrink + parity attested", file=sys.stderr)


# --------------------------------------------------------------------------
# child: --faults  (kill-and-recover chaos benchmark)
# --------------------------------------------------------------------------

def faults_bench():
    """Chaos e2e: a supervised 2-process data-parallel run has one worker
    killed mid-step by the deterministic fault registry; the launcher
    supervisor SIGTERMs the survivor, relaunches the group on a fresh
    coordinator port, the workers resume from the last PUBLISHED async
    checkpoint, and the final parameters must match an uninterrupted
    single-process run to 1e-6 (same per-step batches on every rank make
    the DP-averaged gradient exactly the local gradient).  Emits one
    parsed JSON metric line with the measured time-to-recover.

    Never touches the jax backend itself — workers are clean re-execed
    interpreters — so it runs standalone
    (``--cpu-mesh N`` recommended off-TPU).  Knobs: BENCH_FAULTS_STEPS
    (default 8), BENCH_FAULTS_KILL_STEP (default steps//2),
    BENCH_FAULTS_NPROCS (default 2)."""
    import shutil
    import tempfile

    import numpy as np
    from paddle_tpu.distributed.launch import supervise, launch_stats

    steps = int(os.environ.get("BENCH_FAULTS_STEPS", 8))
    kill_step = int(os.environ.get("BENCH_FAULTS_KILL_STEP",
                                   max(steps // 2, 2)))
    nprocs = int(os.environ.get("BENCH_FAULTS_NPROCS", 2))
    repo = os.path.dirname(os.path.abspath(__file__))
    work = tempfile.mkdtemp(prefix="paddle_tpu_faults_")

    def env_base(tag):
        from paddle_tpu.testing.env import clean_cpu_env
        # one host device per worker: the DP transport here is the
        # cross-PROCESS eager path, extra local devices just cost memory
        env = clean_cpu_env(repo, device_count=1)
        env["PADDLE_COLLECTIVE_TIMEOUT"] = \
            os.environ.get("PADDLE_COLLECTIVE_TIMEOUT", "30")
        env.pop("PADDLE_FAULTS", None)
        # per-scenario telemetry dir: workers write JSONL step records
        # the parent merges into the cross-rank block below
        env["PADDLE_TELEMETRY_DIR"] = os.path.join(work, tag, "telemetry")
        return env

    def worker_argv(tag):
        return ["-m", "paddle_tpu.testing.recovery_worker",
                "--ckpt", os.path.join(work, tag, "ckpt"),
                "--out", os.path.join(work, tag, "out"),
                "--steps", str(steps)]

    try:
        # reference: uninterrupted single-process run
        t0 = time.perf_counter()
        ref = supervise(worker_argv("ref"), nprocs=1,
                        env_base=env_base("ref"))
        ref_s = time.perf_counter() - t0
        assert ref["rc"] == 0, f"reference run failed: {ref}"

        # chaos: kill one worker mid-step on the first incarnation
        env = env_base("chaos")
        victim = min(1, nprocs - 1)
        env["PADDLE_FAULTS"] = \
            f"kill:step={kill_step},rank={victim},restart=0,code=43"
        summary = supervise(worker_argv("chaos"), nprocs=nprocs,
                            env_base=env, log_dir=os.path.join(work, "logs"),
                            max_restarts=2, backoff=0.5)
        assert summary["rc"] == 0, (
            f"supervised run did not recover: {summary}")
        assert summary["restarts_used"] == 1, summary
        inc = summary["incidents"][0]
        assert inc["rank"] == victim and inc["exit_code"] == 43, inc

        out = os.path.join(work, "chaos", "out")
        resumed = [f for f in os.listdir(out) if f.startswith("resumed_1")]
        assert resumed, "relaunched workers never wrote resume markers"
        with open(os.path.join(out, sorted(resumed)[0])) as f:
            marker = json.load(f)
        # resumed from a PUBLISHED checkpoint: at least one optimizer
        # step survived the crash, and never past the kill point
        assert 1 <= marker["resumed_step"] < kill_step + 1, marker
        ttr = marker["time"] - inc["time"]
        assert ttr > 0, (marker, inc)

        ref_params = np.load(os.path.join(work, "ref", "out",
                                          "params_rank0.npz"))
        chaos_params = np.load(os.path.join(out, "params_rank0.npz"))
        for k in ref_params.files:
            np.testing.assert_allclose(chaos_params[k], ref_params[k],
                                       atol=1e-6)

        # merged cross-rank telemetry from the chaos workers' JSONL logs:
        # per-rank step counts/times + the supervision counter family
        telem = {"registry": {"launch": dict(launch_stats())}}
        try:
            from paddle_tpu.observability import aggregate
            report = aggregate.merge_from_dir(
                os.path.join(work, "chaos", "telemetry"))
            telem["ranks"] = {
                r: {"steps": v["steps"],
                    "step_wall_p50_s": v["step_wall_p50_s"],
                    "step_wall_p95_s": v["step_wall_p95_s"]}
                for r, v in report["ranks"].items()}
        except Exception as e:                             # noqa: BLE001
            telem["ranks"] = {"error": f"{type(e).__name__}: {e}"}

        print(json.dumps({
            "metric": "fault_recovery_time_s",
            "value": round(ttr, 3),
            "unit": "s",
            "vs_baseline": round(ttr / ref_s, 4),
            "kill_step": kill_step,
            "resumed_step": marker["resumed_step"],
            "steps": steps,
            "nprocs": nprocs,
            "restarts_used": summary["restarts_used"],
            "incident_exit_code": inc["exit_code"],
            "telemetry": telem,
        }), flush=True)
        print(f"# faults: killed rank {victim} at step {kill_step}, "
              f"resumed from step {marker['resumed_step']}, "
              f"time-to-recover {ttr:.2f}s (clean run {ref_s:.2f}s), "
              f"params match to 1e-6", file=sys.stderr)
    finally:
        shutil.rmtree(work, ignore_errors=True)


# --------------------------------------------------------------------------
# child: --fleet  (fault-tolerant serving-fleet chaos benchmark)
# --------------------------------------------------------------------------

def _jit_cache_dir():
    """The compile cache the fleet phases' replicas share: where the one
    rule puts it (framework/jax_compat.py::resolve_cache_dir —
    ``JAX_COMPILATION_CACHE_DIR``, else ``PADDLE_JIT_CACHE_DIR``, else
    the checkout's fixed ``.jax_cache``).  Logs and journals stay in the
    phase's temp dir; the cache does not — its path is part of its key,
    so a directory made per run never hits."""
    from paddle_tpu.framework import jax_compat
    return jax_compat.resolve_cache_dir(jax_compat.checkout_cache_dir())[0]


def fleet_bench():
    """Serving-fleet e2e benches (ISSUE 7 + ISSUE 11), phase-selectable
    via BENCH_FLEET_PHASES (default "chaos,autoscale"):

    * ``chaos`` — sustained synthetic traffic through a 2-replica
      supervised fleet, one replica SIGKILLed mid-run WITH requests in
      flight.  Asserts the durability contract instead of trusting it:
      ZERO lost requests, token-exact outputs for the re-queued
      requests vs an uninterrupted run, requeues >= 1, the replacement
      replica warm-restarts from the shared persistent compilation
      cache (0 cache misses), p99 under BENCH_FLEET_P99_S (default
      30s).  Emits the fleet_recovery_time_s JSON metric line.
    * ``autoscale`` — SLO-driven elasticity under realistic traffic: a
      seeded Poisson stream with a 3x burst (testing/traffic.py) drives
      an Autoscaler-governed fleet between BENCH_AS_MIN and
      BENCH_AS_MAX replicas.  Asserts interactive p99 <= the
      PADDLE_FLEET_SLO_P99_S target, replicas_up RISES during the burst
      and FALLS after cooldown, only batch-class requests are shed,
      every scale-up replica joins warm (0 persistent-cache misses),
      zero admitted requests lost, and goodput (SLO-met tokens/s) beats
      a static fleet pinned at BENCH_AS_MIN replicas over the identical
      arrivals (skippable via BENCH_AS_STATIC=0 for the smoke budget).
      Emits the fleet_autoscale_goodput_tps JSON metric line.

    * ``routerchaos`` — control-plane fault tolerance (ISSUE 18): a
      journaled disaggregated fleet runs under the supervised router
      (``fleet_supervisor.py``); the router is SIGKILLed mid-traffic
      with in-flight AND parked-handoff work, relaunched against the
      same journal, and re-adopts the surviving workers.  Asserts zero
      admitted requests lost, token-exact parity vs an unkilled run,
      worker pids UNCHANGED (re-adoption, not replica restarts), zero
      XLA compiles during re-adoption, and journal write overhead
      within BENCH_RC_MIN_RATIO of the unjournaled tokens/s.  Emits
      fleet_router_recovery_s + fleet_journal_overhead JSON metrics.

    * ``trace`` — distributed-tracing overhead (ISSUE 19): tracing-on
      serving throughput within BENCH_TRACE_OVERHEAD (0.95x) of
      tracing-off on one in-process engine, interleaved A/B medians.
      The disagg and kvtier phases additionally run their fleets with
      PADDLE_TRACE=1 and assert on the assembled lifecycles (full hop
      chain, zero negative spans, phase p99s summing to the e2e p99
      within BENCH_TRACE_SUM_TOL).  Emits serving_trace_overhead.

    Replicas are clean re-execed CPU-backend interpreters (same dance as
    --faults), so this runs standalone — ``--cpu-mesh N`` recommended
    off-TPU.  Knobs: BENCH_FLEET_REPLICAS
    (default 2), BENCH_FLEET_REQUESTS (default 24), BENCH_FLEET_TOKENS
    (default 48), BENCH_AS_{MIN,MAX,RATE,DURATION_S,SLO_S,COOLDOWN_S,
    MAX_PENDING,STATIC}, BENCH_RC_{REQUESTS,TOKENS,OVERHEAD,
    MIN_RATIO}."""
    import shutil
    import tempfile

    from paddle_tpu.testing.env import clean_cpu_env

    repo = os.path.dirname(os.path.abspath(__file__))
    work = tempfile.mkdtemp(prefix="paddle_tpu_fleet_")
    env = clean_cpu_env(repo, device_count=1)
    env.pop("PADDLE_FAULTS", None)
    # an ambient artifact dir would contaminate the aot phase's
    # persistent-cache-only baseline boot — the phase plumbs its own
    env.pop("PADDLE_AOT_CACHE_DIR", None)
    phases = [p.strip() for p in os.environ.get(
        "BENCH_FLEET_PHASES",
        "chaos,autoscale,aot,disagg,trace,kvtier,routerchaos").split(",")
        if p.strip()]
    try:
        if "chaos" in phases:
            _fleet_chaos_phase(work, env)
        if "autoscale" in phases:
            _fleet_autoscale_phase(work, env)
        if "aot" in phases:
            _fleet_aot_phase(work, env)
        if "disagg" in phases:
            _fleet_disagg_phase(work, env)
        if "trace" in phases:
            _fleet_trace_phase(work, env)
        if "kvtier" in phases:
            _fleet_kvtier_phase(work, env)
        if "routerchaos" in phases:
            _fleet_routerchaos_phase(work, env)
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _fleet_chaos_phase(work, env):
    from paddle_tpu.inference.fleet import ServingFleet

    replicas = int(os.environ.get("BENCH_FLEET_REPLICAS", 2))
    n_requests = int(os.environ.get("BENCH_FLEET_REQUESTS", 24))
    gen_tokens = int(os.environ.get("BENCH_FLEET_TOKENS", 48))
    p99_bound = float(os.environ.get("BENCH_FLEET_P99_S", 30))

    import numpy as np
    spec = {"cfg": {"vocab_size": 256, "hidden_size": 32, "num_layers": 2,
                    "num_heads": 2, "max_seq_len": 128, "dtype": "float32",
                    "use_flash": False, "remat": False},
            "seed": 0, "slots": 2, "max_len": 8 + gen_tokens,
            "seq_buckets": [8], "batch_buckets": [1, 2]}
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 256, int(rng.randint(3, 8)))
               for _ in range(n_requests)]
    cache = _jit_cache_dir()

    def make_fleet(tag):
        return ServingFleet(
            spec, replicas=replicas, env_base=env,
            jit_cache_dir=cache,
            log_dir=os.path.join(work, tag, "logs"),
            telemetry_dir=os.path.join(work, tag, "telemetry"),
            heartbeat_s=20, restart_backoff_s=0.2)

    # reference: the SAME traffic, nobody killed (also fills the
    # persistent cache the chaos fleet's replicas warm-boot from)
    fleet = make_fleet("ref")
    assert fleet.await_healthy(timeout=120) == replicas
    for i, p in enumerate(prompts):
        fleet.submit(p, gen_tokens, request_id=f"req{i}")
    done, failed = fleet.drain(timeout=300)
    assert not failed and len(done) == n_requests, (len(done), failed)
    ref_tokens = {rid: r.tokens for rid, r in done.items()}
    assert fleet.stats()["incidents"] == 0
    fleet.close()

    # chaos: same traffic, one replica SIGKILLed holding live work
    fleet = make_fleet("chaos")
    assert fleet.await_healthy(timeout=120) == replicas
    victim = fleet._replicas[0]
    killed_holding = None
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        fleet.submit(p, gen_tokens, request_id=f"req{i}")
        if killed_holding is None and i >= n_requests // 3:
            # sustained traffic reached the victim: kill it the
            # moment it really holds in-flight requests
            deadline = time.time() + 10
            while not victim.inflight and time.time() < deadline:
                time.sleep(0.002)
            killed_holding = len(victim.inflight)
            fleet.kill_replica(victim.id)
    done, failed = fleet.drain(timeout=300)
    wall = time.perf_counter() - t0
    assert killed_holding and killed_holding > 0, (
        "victim never held in-flight work — the kill tested nothing")
    # the durability contract, asserted
    assert not failed, f"requests LOST/failed: {failed}"
    assert len(done) == n_requests, (len(done), n_requests)
    st = fleet.stats()
    assert st["requeues"] >= 1, st
    mismatch = [rid for rid in ref_tokens
                if done[rid].tokens != ref_tokens[rid]]
    assert not mismatch, (
        f"re-queued requests lost token parity: {mismatch}")
    # the replacement replica must be back — and warm
    assert fleet.await_healthy(timeout=120) == replicas
    st = fleet.stats()
    assert st["recoveries"], "no recovery recorded"
    rec = st["recoveries"][-1]
    assert rec["warm_cache_misses"] == 0, (
        f"replacement replica recompiled: {rec}")
    ttr = fleet.recovery_time_s()
    lat = st["latency_s"]
    assert lat["p99"] is not None and lat["p99"] <= p99_bound, lat

    telem = {"registry": {"fleet": {k: st[k] for k in (
        "requests_admitted", "requests_completed", "requeues",
        "retries", "incidents", "replica_restarts",
        "heartbeat_misses", "sheds", "dup_completions")}}}
    try:
        from paddle_tpu.observability import aggregate
        report = aggregate.merge_from_dir(
            os.path.join(work, "chaos", "telemetry"))
        telem["replicas"] = {
            r: {"steps": v["steps"], "faults": v["faults"]}
            for r, v in report["ranks"].items()}
    except Exception as e:                             # noqa: BLE001
        telem["replicas"] = {"error": f"{type(e).__name__}: {e}"}
    fleet.close()

    print(json.dumps({
        "metric": "fleet_recovery_time_s",
        "value": round(ttr, 3),
        "unit": "s",
        "vs_baseline": round(ttr / wall, 4),
        "requests": n_requests,
        "replicas": replicas,
        "lost_requests": 0,
        "requeues": st["requeues"],
        "killed_holding": killed_holding,
        "latency_ms": {"p50": round(lat["p50"] * 1e3, 3),
                       "p99": round(lat["p99"] * 1e3, 3)},
        "warm_cache_misses": rec["warm_cache_misses"],
        "telemetry": telem,
    }), flush=True)
    print(f"# fleet: {n_requests} requests over {replicas} replicas, "
          f"SIGKILL with {killed_holding} in flight -> "
          f"{st['requeues']} requeued, 0 lost, token-exact, "
          f"recovery {ttr:.2f}s, p99 {lat['p99'] * 1e3:.0f}ms",
          file=sys.stderr)


def _fleet_autoscale_phase(work, env):
    """ISSUE 11: SLO-driven elasticity under a generated 3x Poisson
    burst — see fleet_bench's docstring for the asserted contract."""
    import threading

    from paddle_tpu.inference.autoscale import Autoscaler
    from paddle_tpu.inference.fleet import (FleetOverloaded,
                                            ServingFleet)
    from paddle_tpu.testing import traffic as T

    min_r = int(os.environ.get("BENCH_AS_MIN", 1))
    max_r = int(os.environ.get("BENCH_AS_MAX", 3))
    slo_s = float(os.environ.get("PADDLE_FLEET_SLO_P99_S",
                                 os.environ.get("BENCH_AS_SLO_S", 4.0)))
    duration = float(os.environ.get("BENCH_AS_DURATION_S", 18.0))
    base_rate = float(os.environ.get("BENCH_AS_RATE", 20.0))
    cooldown = float(os.environ.get("BENCH_AS_COOLDOWN_S", 2.0))
    max_pending = int(os.environ.get("BENCH_AS_MAX_PENDING", 96))
    run_static = os.environ.get("BENCH_AS_STATIC", "1") != "0"

    gen_hi = 64
    spec = {"cfg": {"vocab_size": 256, "hidden_size": 32, "num_layers": 2,
                    "num_heads": 2, "max_seq_len": 128, "dtype": "float32",
                    "use_flash": False, "remat": False},
            "seed": 0, "slots": 2, "max_len": 8 + gen_hi,
            "seq_buckets": [8], "batch_buckets": [1, 2]}
    arrivals = T.generate(T.TrafficSpec(
        duration_s=duration, base_rate=base_rate, seed=11,
        bursts=((0.28, 0.72, 3.0),), diurnal_amplitude=0.15,
        prompt_len=(5, 0.4, 4, 8), output_tokens=(44, 0.3, 24, gen_hi),
        prefix_hit_rate=0.3, prefix_len=3, batch_fraction=0.3))
    cache = _jit_cache_dir()

    def run(tag, autoscale):
        fleet = ServingFleet(
            spec, replicas=min_r, env_base=env, jit_cache_dir=cache,
            log_dir=os.path.join(work, tag, "logs"),
            telemetry_dir=os.path.join(work, tag, "telemetry"),
            heartbeat_s=20, restart_backoff_s=0.2,
            max_pending=max_pending)
        counts = {"submitted": 0, "admit_sheds": 0}
        series = []                      # (t, replicas_up, configured)
        stop_sampling = threading.Event()

        def sample():
            while not stop_sampling.is_set():
                series.append((time.perf_counter(), fleet.replicas_up(),
                               fleet.nreplicas))
                stop_sampling.wait(0.1)
        scaler = None
        try:
            assert fleet.await_healthy(timeout=180) == min_r
            if autoscale:
                scaler = Autoscaler(
                    fleet, slo_p99_s=slo_s, min_replicas=min_r,
                    max_replicas=max_r, cooldown_s=cooldown,
                    interval_s=0.2, window_s=8.0, down_ticks=10,
                    up_backlog_per_replica=1.5).start()
            sampler = threading.Thread(target=sample, daemon=True)
            sampler.start()

            def submit(a):
                try:
                    fleet.submit(a.prompt, a.max_new_tokens,
                                 request_id=a.request_id,
                                 priority=a.priority)
                    counts["submitted"] += 1
                except FleetOverloaded:
                    counts["admit_sheds"] += 1     # named, at admission
            t0 = time.perf_counter()
            T.replay(arrivals, submit)
            done, failed = fleet.drain(timeout=300)
            wall = time.perf_counter() - t0
            if autoscale:
                # after the burst + cooldown the fleet must de-provision
                deadline = time.monotonic() + 60
                while fleet.nreplicas > min_r \
                        and time.monotonic() < deadline:
                    time.sleep(0.2)
            stop_sampling.set()
            sampler.join(timeout=5)
            st = fleet.stats()
            sc = scaler.stats() if scaler else {}
        finally:
            if scaler:
                scaler.stop()
            stop_sampling.set()
            fleet.close()
        from paddle_tpu.observability.metrics import \
            nearest_rank_percentile
        slo_met_tokens = sum(
            len(r.tokens) for r in done.values()
            if r.latency() is not None and r.latency() <= slo_s)
        lats = {"interactive": [], "batch": []}
        for r in done.values():
            lats[r.priority].append(r.latency())

        def p99(xs):
            return nearest_rank_percentile(sorted(xs), 99)
        return {
            "tag": tag, "wall_s": wall, "done": done, "failed": failed,
            "stats": st, "scaler": sc, "counts": counts,
            "series": series, "goodput_tps": slo_met_tokens / wall,
            "p99_interactive_s": p99(lats["interactive"]),
            "p99_batch_s": p99(lats["batch"]),
            "final_replicas": fleet.nreplicas,
        }

    static = run("as_static", autoscale=False) if run_static else None
    elastic = run("as_elastic", autoscale=True)

    st = elastic["stats"]
    # the SLO contract: interactive p99 under the target
    assert elastic["p99_interactive_s"] is not None \
        and elastic["p99_interactive_s"] <= slo_s, (
        f"interactive p99 {elastic['p99_interactive_s']} over the "
        f"SLO target {slo_s}s")
    # elasticity: replicas_up rose during the burst and fell after
    peak_up = max(up for (_, up, _c) in elastic["series"])
    peak_cfg = max(c for (_, _up, c) in elastic["series"])
    assert peak_up > min_r, (
        f"replicas_up never rose above {min_r} — no scale-up happened")
    assert elastic["final_replicas"] == min_r, (
        f"fleet did not de-provision: {elastic['final_replicas']} "
        f"replicas after cooldown (min {min_r})")
    assert st["scale_ups"] >= 1 and st["scale_downs"] >= 1, st
    # graceful degradation: the shed axe NEVER hits the interactive
    # class (batch existed throughout — the traffic is 30% batch)
    assert st["sheds_interactive"] == 0, st
    failed_reasons = {rid: r.error for rid, r in elastic["failed"].items()}
    bad_fail = {rid: e for rid, e in failed_reasons.items()
                if "shed_overload" not in (e or "")}
    assert not bad_fail, f"non-shed failures: {bad_fail}"
    shed_classes = {elastic["failed"][rid].priority
                    for rid in elastic["failed"]}
    assert shed_classes <= {"batch"}, (
        f"sheds hit non-batch classes: {shed_classes}")
    # zero-lost: every admitted id completed or failed NAMED (the
    # displaced batch sheds are in `failed` with reason shed_overload)
    assert len(elastic["done"]) + len(elastic["failed"]) \
        == elastic["counts"]["submitted"], (
        len(elastic["done"]), len(elastic["failed"]),
        elastic["counts"]["submitted"])
    # warm elasticity: every scale-up replica that JOINED did so with 0
    # persistent-cache misses (shared PADDLE_JIT_CACHE_DIR).  A late
    # scale-up drained away before its hello has no miss count — and
    # compiled nothing.
    ups = [e for e in st["scale_events"] if e["action"] == "scale_up"
           and "hello_t" in e]
    assert ups and all(e.get("warm_cache_misses") == 0 for e in ups), (
        st["scale_events"])
    vs_static = None
    if static is not None:
        vs_static = elastic["goodput_tps"] / max(static["goodput_tps"],
                                                 1e-9)
        assert elastic["goodput_tps"] >= static["goodput_tps"], (
            f"elastic goodput {elastic['goodput_tps']:.1f} tok/s did "
            f"not beat the static baseline "
            f"{static['goodput_tps']:.1f} tok/s")

    print(json.dumps({
        "metric": "fleet_autoscale_goodput_tps",
        "value": round(elastic["goodput_tps"], 1),
        "unit": "slo_met_tokens/s",
        "vs_static": round(vs_static, 3) if vs_static else None,
        "static_goodput_tps": (round(static["goodput_tps"], 1)
                               if static else None),
        "slo_p99_s": slo_s,
        "p99_interactive_s": round(elastic["p99_interactive_s"], 3),
        "p99_batch_s": (round(elastic["p99_batch_s"], 3)
                        if elastic["p99_batch_s"] else None),
        "arrivals": len(arrivals),
        "submitted": elastic["counts"]["submitted"],
        "completed": len(elastic["done"]),
        "lost_requests": 0,
        "replicas": {"min": min_r, "max": max_r, "peak_up": peak_up,
                     "peak_configured": peak_cfg,
                     "final": elastic["final_replicas"]},
        "scale_ups": st["scale_ups"], "scale_downs": st["scale_downs"],
        "sheds": {"batch": st["sheds_batch"],
                  "interactive": st["sheds_interactive"],
                  "admission": elastic["counts"]["admit_sheds"]},
        "warm_scaleup_cache_misses": 0,
        "autoscale": {k: elastic["scaler"].get(k) for k in (
            "ticks", "scale_ups", "scale_downs", "holds_cooldown",
            "holds_bounds", "tick_errors")},
    }), flush=True)
    print(f"# autoscale: {len(arrivals)} arrivals over {duration:.0f}s "
          f"(3x burst), replicas {min_r}->{peak_cfg}->"
          f"{elastic['final_replicas']}, interactive p99 "
          f"{elastic['p99_interactive_s']:.2f}s vs SLO {slo_s}s, "
          f"goodput {elastic['goodput_tps']:.0f} tok/s"
          + (f" ({vs_static:.2f}x static)" if vs_static else "")
          + f", batch sheds {st['sheds_batch']}, 0 lost",
          file=sys.stderr)


def _fleet_aot_phase(work, env):
    """ISSUE 14: AOT-serialized executables -> zero-compile fleet cold
    start.  Three replica boots over the SAME checkpoint + ladder:

    1. *seed* — one replica with PADDLE_AOT_CACHE_DIR + the shared
       persistent cache: compiles everything, serializes every
       executable into the artifact dir, and produces the reference
       tokens.
    2. *persist* — a FRESH replica process with the persistent cache
       only (today's warm-restart path): still pays trace+lowering on
       every ladder rung before its first token.
    3. *aot* — a FRESH replica with the artifact dir: loads serialized
       executables (no trace, no lowering, no backend compile) and
       serves its first token with ZERO XLA compiles — attested from
       the replica's own compile counters riding the fleet hello/stats
       (the numeric-contract channel), not inferred.

    Asserts: aot replica xla_compiles == 0 (hello AND post-traffic),
    aot_hits >= 1, token-exact parity across all three boots, and
    time-to-first-token (process spawn -> first completed request)
    drops >= BENCH_AOT_MIN_SPEEDUP (default 3) vs the persist boot.
    Emits the fleet_aot_coldstart_ttft_s JSON metric."""
    from paddle_tpu.inference.fleet import ServingFleet

    gen_tokens = int(os.environ.get("BENCH_AOT_TOKENS", 16))
    min_speedup = float(os.environ.get("BENCH_AOT_MIN_SPEEDUP", 3.0))

    import numpy as np
    jit_cache = _jit_cache_dir()
    aot_cache = os.path.join(work, "aot_artifacts")
    params_npz = os.path.join(work, "aot_params.npz")

    # the production boot shape: replicas load a CHECKPOINT (pure
    # device_put — the seeded init would compile RNG executables and
    # muddy the zero-compile attestation); one npz is shared by every
    # boot so parity is over identical weights
    import jax
    from paddle_tpu.models import gpt as G
    cfg_kw = {"vocab_size": 512, "hidden_size": 256, "num_layers": 4,
              "num_heads": 4, "max_seq_len": 320, "dtype": "float32",
              "use_flash": False, "remat": False}
    G.save_params_npz(params_npz,
                      G.init_params(G.GPTConfig(**cfg_kw),
                                    jax.random.PRNGKey(0)))
    # a production-shaped prefill ladder (8 seq x 3 batch rungs): the
    # persistent-cache path pays trace+lowering per rung, the artifact
    # path loads rungs lazily — exactly the gap this phase measures
    spec = {"cfg": cfg_kw, "params_npz": params_npz, "paged": True,
            "slots": 6, "max_len": 256,
            "seq_buckets": [16, 32, 48, 64, 96, 128, 192, 256],
            "batch_buckets": [1, 2, 4], "page_size": 16}
    rng = np.random.RandomState(17)
    # lengths span the ladder; the longest leaves room for gen_tokens
    # inside max_len (230 + 16 < 256) while still bucketing to the top
    prompts = [rng.randint(1, 512, n) for n in (8, 21, 45, 70, 130, 230)]

    def boot(tag, with_aot):
        t0 = time.perf_counter()
        fleet = ServingFleet(
            spec, replicas=1, env_base=env, jit_cache_dir=jit_cache,
            aot_cache_dir=(aot_cache if with_aot else None),
            log_dir=os.path.join(work, tag, "logs"),
            heartbeat_s=60, spawn_timeout_s=240)
        try:
            assert fleet.await_healthy(timeout=240) == 1
            # TTFT: process spawn -> the first request's completion
            fleet.submit(prompts[0], gen_tokens, request_id=f"{tag}-0")
            done, failed = fleet.drain(timeout=120)
            ttft = time.perf_counter() - t0
            assert not failed and f"{tag}-0" in done, (tag, failed)
            hello = fleet._replicas[0].hello or {}
            # the rest of the traffic exercises every remaining rung —
            # the aot replica's lazy artifact loads must stay
            # compile-free through it
            for i, p in enumerate(prompts[1:], 1):
                fleet.submit(p, gen_tokens, request_id=f"{tag}-{i}")
            done2, failed2 = fleet.drain(timeout=180)
            assert not failed2, (tag, failed2)
            done.update(done2)
            last = fleet._replicas[0].last_stats or {}
            toks = {i: done[f"{tag}-{i}"].tokens
                    for i in range(len(prompts))}
        finally:
            fleet.close()
        return {"tag": tag, "ttft_s": ttft, "tokens": toks,
                "hello_compile": hello.get("compile") or {},
                "final_compile": {"xla_compiles": last.get("xla_compiles"),
                                  "aot": last.get("aot")}}

    seed = boot("aot_seed", with_aot=True)
    persist = boot("aot_persist", with_aot=False)
    aot = boot("aot_warm", with_aot=True)

    # token-exact parity over identical weights: the artifact path must
    # change nothing but the clock
    assert seed["tokens"] == persist["tokens"] == aot["tokens"], (
        "cold-boot paths lost token parity")
    # the zero-compile attestation, from the replica's own counters
    hc = aot["hello_compile"]
    fc = aot["final_compile"]
    assert hc.get("xla_compiles") == 0, (
        f"artifact-warm replica compiled at boot: {hc}")
    assert fc.get("xla_compiles") == 0, (
        f"artifact-warm replica compiled under traffic: {fc}")
    assert (fc.get("aot") or {}).get("hits", 0) >= 1, fc
    assert (fc.get("aot") or {}).get("errors", 0) == 0, fc
    # the persistent-only boot really did recompile (the gap is real)
    assert persist["final_compile"]["xla_compiles"], persist
    speedup = persist["ttft_s"] / max(aot["ttft_s"], 1e-9)
    assert speedup >= min_speedup, (
        f"aot cold-start TTFT {aot['ttft_s']:.2f}s is only "
        f"{speedup:.2f}x the persistent-cache path "
        f"{persist['ttft_s']:.2f}s (need >= {min_speedup}x)")

    print(json.dumps({
        "metric": "fleet_aot_coldstart_ttft_s",
        "value": round(aot["ttft_s"], 3),
        "unit": "s",
        "vs_persistent_cache": round(speedup, 2),
        "persist_ttft_s": round(persist["ttft_s"], 3),
        "seed_ttft_s": round(seed["ttft_s"], 3),
        "min_speedup": min_speedup,
        "aot_replica": {"xla_compiles": 0,
                        "aot_hits": fc["aot"]["hits"],
                        "aot_errors": fc["aot"]["errors"]},
        "ladder_rungs": len(spec["seq_buckets"])
        * len(spec["batch_buckets"]),
        "requests": len(prompts),
        "token_parity": True,
    }), flush=True)
    print(f"# aot-coldstart: replacement replica TTFT "
          f"{aot['ttft_s']:.2f}s vs {persist['ttft_s']:.2f}s "
          f"persistent-cache ({speedup:.2f}x, >= {min_speedup}x "
          f"asserted), 0 XLA compiles on the artifact-warm replica, "
          f"token-exact across all three boots", file=sys.stderr)


def _fleet_disagg_phase(work, env):
    """ISSUE 15: prefill/decode disaggregation — decode p99 stays FLAT
    while long-prompt prefills hammer the prefill pool.

    A 1-prefill + 1-decode disaggregated fleet serves two waves of
    short interactive requests (paced arrivals, decode-heavy):

    * *quiet* — shorts alone; their decode-phase p99 (handoff ->
      completion, decode-pool queueing included) is the baseline.
    * *loaded* — the same paced shorts while a hammer thread keeps
      BENCH_DISAGG_LONG_CONC long prompts (BENCH_DISAGG_LONG_LEN
      tokens, fresh content each so the prefix cache can't deflate the
      prefill cost) outstanding on the prefill pool for the whole wave.

    Asserts: loaded decode p99 <= BENCH_DISAGG_P99_RATIO (1.3) x the
    quiet baseline, ZERO lost requests across both waves (every long
    included), and kv_handoffs > 0 (the pages really crossed the
    router).  A unified 2-replica fleet runs the identical waves for
    comparison (BENCH_DISAGG_UNIFIED=0 skips it — the smoke's budget):
    there the long prefills share executors with short decodes, so the
    shorts' end-to-end p99 degrades — the number the JSON reports next
    to the flat disaggregated one.  Emits fleet_disagg_decode_p99_s.

    The disaggregated fleet runs with PADDLE_TRACE=1 (ISSUE 19): every
    short request's assembled lifecycle must carry the full hop chain
    (admit -> dispatch -> park -> ship -> inject -> completion -> ack)
    with ZERO negative spans after clock-skew correction, and the
    per-phase p99 attribution must SUM to within BENCH_TRACE_SUM_TOL
    (10%) of the measured e2e p99 — the telescoping-boundary contract.
    The rollup is embedded as the JSON line's "trace" block."""
    import threading

    import numpy as np
    from paddle_tpu.inference.fleet import ServingFleet
    from paddle_tpu.observability import aggregate, timeline
    from paddle_tpu.observability.metrics import nearest_rank_percentile

    n_short = int(os.environ.get("BENCH_DISAGG_SHORT", 16))
    short_gen = int(os.environ.get("BENCH_DISAGG_SHORT_GEN", 24))
    pace = float(os.environ.get("BENCH_DISAGG_PACE_S", 0.12))
    long_len = int(os.environ.get("BENCH_DISAGG_LONG_LEN", 192))
    long_conc = int(os.environ.get("BENCH_DISAGG_LONG_CONC", 3))
    ratio_bound = float(os.environ.get("BENCH_DISAGG_P99_RATIO", 1.3))
    p99_floor = float(os.environ.get("BENCH_DISAGG_P99_FLOOR_S", 0.05))
    run_unified = os.environ.get("BENCH_DISAGG_UNIFIED", "1") != "0"

    # one 224-wide prefill bucket and NO chunking: a long admission is
    # one big dispatch — exactly the head-of-line blocker
    # disaggregation exists to keep off the decode pool
    spec = {"cfg": {"vocab_size": 512, "hidden_size": 128,
                    "num_layers": 3, "num_heads": 4, "max_seq_len": 256,
                    "dtype": "float32", "use_flash": False,
                    "remat": False},
            "seed": 0, "paged": True, "slots": 4, "max_len": 224,
            "page_size": 8, "seq_buckets": [8, 224],
            "batch_buckets": [1]}
    rng = np.random.RandomState(23)
    shorts_toks = [rng.randint(1, 512, int(rng.randint(4, 8)))
                   for _ in range(n_short)]
    cache = _jit_cache_dir()

    def wave(fleet, tag, with_longs):
        """Paced shorts (optionally under the long-prompt hammer);
        returns (short_requests, longs_submitted)."""
        stop = threading.Event()
        longs = []

        def hammer():
            import zlib
            i = 0
            # crc32, not hash(): PYTHONHASHSEED randomizes str hashes
            # per interpreter, and the long-prompt stream must be
            # byte-identical run to run
            lrng = np.random.RandomState(zlib.crc32(tag.encode()))
            while not stop.is_set():
                live = [r for r in longs if not (r.done or r.failed)]
                while len(live) < long_conc and not stop.is_set():
                    # longs ride the batch class (the production shape:
                    # bulk summarization behind interactive chat), so
                    # the weighted-fair dispatch keeps shorts first in
                    # BOTH pools' queues
                    r = fleet.submit(
                        lrng.randint(1, 512, long_len), 2,
                        request_id=f"{tag}-long{i}", priority="batch")
                    longs.append(r)
                    live.append(r)
                    i += 1
                time.sleep(0.005)

        th = None
        if with_longs:
            th = threading.Thread(target=hammer, daemon=True)
            th.start()
            time.sleep(0.4)     # saturate the prefill pool first
        shorts = []
        for i, p in enumerate(shorts_toks):
            shorts.append(fleet.submit(p, short_gen,
                                       request_id=f"{tag}-s{i}"))
            time.sleep(pace)
        deadline = time.time() + 180
        while any(not (r.done or r.failed) for r in shorts) \
                and time.time() < deadline:
            time.sleep(0.02)
        stop.set()
        if th is not None:
            th.join(timeout=10)
        done, failed = fleet.drain(timeout=180)
        assert not failed, (tag, {k: v.error for k, v in failed.items()})
        assert all(r.done for r in shorts), (
            f"{tag}: shorts unfinished within the deadline")
        return shorts, len(longs)

    # with ~10-16 shorts per wave the nearest-rank p99 IS the max — on
    # a 1-core CPU box one scheduler stall fails the ratio with no real
    # leak.  The smoke drops to p90 (sheds exactly the worst sample; a
    # REAL prefill leak inflates every loaded short, p90 included —
    # the unified comparison degrades across the board); the default
    # bench keeps the PR-15 headline p99.
    pctl = float(os.environ.get("BENCH_DISAGG_PCTL", 99))

    def p99_of(reqs, kind):
        lats = sorted((r.decode_latency() if kind == "decode"
                       else r.latency()) for r in reqs)
        return nearest_rank_percentile(lats, pctl)

    # ---- disaggregated fleet: quiet then loaded, one boot, traced ----
    tel = os.path.join(work, "disagg", "telemetry")
    # replicas inherit the trace knobs via env; the router IS this
    # process, so it gets them through os.environ + configure — both
    # restored before the untraced unified comparison boots
    trace_prev = os.environ.get("PADDLE_TRACE")
    os.environ["PADDLE_TRACE"] = "1"
    timeline.configure(tel)
    fleet = ServingFleet(
        spec, roles=["prefill", "decode"],
        env_base=dict(env, PADDLE_TELEMETRY_DIR=tel, PADDLE_TRACE="1"),
        jit_cache_dir=cache,
        log_dir=os.path.join(work, "disagg", "logs"),
        heartbeat_s=30, restart_backoff_s=0.2)
    try:
        assert fleet.await_healthy(timeout=180) == 2
        quiet_shorts, _ = wave(fleet, "dq", with_longs=False)
        loaded_shorts, n_longs = wave(fleet, "dl", with_longs=True)
        st = fleet.stats()
    finally:
        fleet.close()
        if trace_prev is None:
            os.environ.pop("PADDLE_TRACE", None)
        else:
            os.environ["PADDLE_TRACE"] = trace_prev
        timeline.configure(None)
    assert n_longs > 0, "the hammer never submitted a long prompt"
    assert st["kv_handoffs"] > 0, st
    assert st["replicas_by_role"] == {"decode": 1, "prefill": 1}, st
    p99_quiet = p99_of(quiet_shorts, "decode")
    p99_loaded = p99_of(loaded_shorts, "decode")
    # a tiny quiet baseline would turn scheduler noise into a failed
    # ratio: the floor keeps the assertion about DEGRADATION, not
    # micro-jitter
    ratio = p99_loaded / max(p99_quiet, p99_floor)
    assert ratio <= ratio_bound, (
        f"disaggregated decode p99 degraded {ratio:.2f}x under prefill "
        f"pressure ({p99_quiet * 1e3:.0f}ms -> {p99_loaded * 1e3:.0f}ms"
        f"; bound {ratio_bound}x) — the prefill pool is leaking into "
        "the decode pool")
    e2e_quiet_d = p99_of(quiet_shorts, "e2e")
    e2e_loaded_d = p99_of(loaded_shorts, "e2e")

    # ---- trace assembly over the disaggregated run (ISSUE 19) ----
    sum_tol = float(os.environ.get("BENCH_TRACE_SUM_TOL", 0.10))
    lifecycles = aggregate.assemble_traces(tel)
    shorts_lc = [lc for lc in lifecycles
                 if (lc.get("priority") or "") == "interactive"]
    assert len(shorts_lc) == 2 * n_short, (
        f"expected {2 * n_short} short lifecycles (quiet + loaded), "
        f"assembled {len(shorts_lc)} of {len(lifecycles)} total")
    hop_chain = ("admit", "dispatch", "park", "ship", "inject",
                 "completion", "ack")
    for lc in shorts_lc:
        hops = lc["hops"]
        idx = []
        for h in hop_chain:
            assert h in hops, (lc["request_id"], h, hops)
            idx.append(hops.index(h))
        assert idx == sorted(idx), (
            f"{lc['request_id']}: hops out of causal order: {hops}")
        assert lc["negative_spans"] == 0, lc
    attr = aggregate.trace_attribution(shorts_lc)
    assert attr["negative_spans"] == 0, attr
    # the telescoping contract is PER LIFECYCLE: the p99-rank request's
    # phase decomposition must sum to its measured e2e latency (its
    # e2e IS the rollup's nearest-rank e2e p99).  Summing each phase's
    # independent p99 would mix different requests' worst cases and is
    # NOT expected to telescope.
    by_e2e = sorted(shorts_lc, key=lambda lc: lc["e2e_s"])
    p99_lc = by_e2e[max(1, math.ceil(0.99 * len(by_e2e))) - 1]
    e2e_p99_t = p99_lc["e2e_s"]
    assert abs(e2e_p99_t - attr["e2e"]["p99"]) < 1e-6, (
        e2e_p99_t, attr["e2e"])
    phase_sum_p99 = sum(p99_lc["phases"].values())
    drift = abs(phase_sum_p99 - e2e_p99_t) / max(e2e_p99_t, 1e-9)
    assert drift <= sum_tol, (
        f"p99-rank lifecycle {p99_lc['request_id']}: phase attribution "
        f"sums to {phase_sum_p99:.4f}s vs its measured e2e "
        f"{e2e_p99_t:.4f}s ({drift:.1%} apart; tolerance "
        f"{sum_tol:.0%}) — the phase boundaries no longer telescope")
    trace_block = {
        "lifecycles": len(shorts_lc),
        "negative_spans": 0,
        "dominant_phase": attr.get("dominant_phase"),
        "phases_p99_s": {ph: attr["phases"][ph]["p99"]
                         for ph in attr["phases"]},
        "p99_request": p99_lc["request_id"],
        "p99_breakdown_s": p99_lc["phases"],
        "phase_sum_p99_s": round(phase_sum_p99, 4),
        "e2e_p99_s": round(e2e_p99_t, 4),
        "sum_drift": round(drift, 4),
    }

    # ---- unified comparison: same waves, 2 unified replicas ----
    unified = None
    if run_unified:
        fleet = ServingFleet(
            spec, replicas=2, env_base=env, jit_cache_dir=cache,
            log_dir=os.path.join(work, "unified", "logs"),
            heartbeat_s=30, restart_backoff_s=0.2)
        try:
            assert fleet.await_healthy(timeout=180) == 2
            uq, _ = wave(fleet, "uq", with_longs=False)
            ul, _ = wave(fleet, "ul", with_longs=True)
        finally:
            fleet.close()
        u_quiet = p99_of(uq, "e2e")
        u_loaded = p99_of(ul, "e2e")
        unified = {"p99_quiet_s": round(u_quiet, 4),
                   "p99_loaded_s": round(u_loaded, 4),
                   "degradation": round(
                       u_loaded / max(u_quiet, p99_floor), 3)}

    print(json.dumps({
        "metric": "fleet_disagg_decode_p99_s",
        "value": round(p99_loaded, 4),
        "unit": "s",
        "quiet_p99_s": round(p99_quiet, 4),
        "ratio_vs_quiet": round(ratio, 3),
        "ratio_bound": ratio_bound,
        "pctl": pctl,
        "e2e_p99_quiet_s": round(e2e_quiet_d, 4),
        "e2e_p99_loaded_s": round(e2e_loaded_d, 4),
        "shorts": n_short,
        "longs_completed": n_longs,
        "long_len": long_len,
        "lost_requests": 0,
        "kv_handoffs": st["kv_handoffs"],
        "kv_handoff_bytes": st["kv_handoff_bytes"],
        "handoff_reships": st["handoff_reships"],
        "roles": {"prefill": 1, "decode": 1},
        "unified_baseline": unified,
        "trace": trace_block,
    }), flush=True)
    print(f"# disagg: decode p{pctl:g} {p99_quiet * 1e3:.0f}ms quiet -> "
          f"{p99_loaded * 1e3:.0f}ms under {n_longs} long-prompt "
          f"prefills ({ratio:.2f}x <= {ratio_bound}x), "
          f"{st['kv_handoffs']} kv handoffs "
          f"({st['kv_handoff_bytes'] / 1024:.0f}KB), 0 lost"
          + (f"; unified e2e p99 {unified['p99_quiet_s'] * 1e3:.0f}ms"
             f" -> {unified['p99_loaded_s'] * 1e3:.0f}ms "
             f"({unified['degradation']:.2f}x)" if unified else ""),
          file=sys.stderr)
    print(f"# disagg-trace: {len(shorts_lc)} lifecycles assembled, full "
          f"hop chain, 0 negative spans; phase p99 sum "
          f"{phase_sum_p99 * 1e3:.0f}ms vs e2e p99 "
          f"{e2e_p99_t * 1e3:.0f}ms ({drift:.1%} <= {sum_tol:.0%}), "
          f"dominant phase {attr.get('dominant_phase')}",
          file=sys.stderr)


def _fleet_trace_phase(work, env):
    """ISSUE 19: full trace capture must be cheap enough to leave on —
    tracing-on serving throughput within BENCH_TRACE_OVERHEAD (0.95x)
    of tracing-off on the SAME engine.

    One in-process paged engine serves identical waves with the
    telemetry dir active in BOTH arms (serving_step JSONL is the PR-4
    baseline cost); only PADDLE_TRACE flips.  Arms interleave
    off/on/off/on for BENCH_TRACE_ROUNDS rounds and compare MEDIANS, so
    box weather (the 1.5x day-to-day CPU swing) hits both equally.
    Also asserts the traced arm actually captured span events — a
    "free" tracer that emitted nothing would pass the ratio trivially.
    Emits the serving_trace_overhead JSON metric line."""
    import numpy as np

    import jax
    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.models import gpt as G
    from paddle_tpu.observability import aggregate, timeline

    floor = float(os.environ.get("BENCH_TRACE_OVERHEAD", 0.95))
    rounds = int(os.environ.get("BENCH_TRACE_ROUNDS", 5))
    n_req = int(os.environ.get("BENCH_TRACE_REQUESTS", 24))
    gen = int(os.environ.get("BENCH_TRACE_TOKENS", 32))

    cfg = G.GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                      num_heads=2, max_seq_len=128, dtype="float32",
                      use_flash=False, remat=False)
    params = G.init_params(cfg, jax.random.PRNGKey(0))
    eng = PagedServingEngine((params, cfg), slots=4, max_len=64,
                             page_size=8, seq_buckets=(16,),
                             batch_buckets=(1, 2))
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, 256, int(rng.randint(6, 14)))
               for _ in range(n_req)]
    tel = os.path.join(work, "trace_overhead")

    def wave():
        t0 = time.perf_counter()
        reqs = [eng.submit(p, gen) for p in prompts]
        while any(not (r.done or r.failed) for r in reqs):
            eng.step()
        assert all(r.done for r in reqs)
        return sum(len(r.tokens) for r in reqs) \
            / (time.perf_counter() - t0)

    trace_prev = os.environ.get("PADDLE_TRACE")
    timeline.configure(tel)
    tps_off, tps_on = [], []
    try:
        os.environ["PADDLE_TRACE"] = "0"
        wave()                        # prime every executable first
        for r in range(rounds):
            # alternate arm order: within-process drift (allocator
            # growth, page-cache warmth) must not always land on the
            # same arm
            arms = ("0", "1") if r % 2 == 0 else ("1", "0")
            for arm in arms:
                os.environ["PADDLE_TRACE"] = arm
                (tps_on if arm == "1" else tps_off).append(wave())
    finally:
        if trace_prev is None:
            os.environ.pop("PADDLE_TRACE", None)
        else:
            os.environ["PADDLE_TRACE"] = trace_prev
        timeline.configure(None)
    off = sorted(tps_off)[len(tps_off) // 2]
    on = sorted(tps_on)[len(tps_on) // 2]
    ratio = on / off
    n_span = len(aggregate.trace_events_from_dir(tel))
    assert n_span > 0, "traced arm captured zero span events"
    assert ratio >= floor, (
        f"tracing-on throughput {on:.0f} tok/s is {ratio:.3f}x of "
        f"tracing-off {off:.0f} tok/s (floor {floor}x) — full capture "
        "is no longer cheap enough to leave on")
    print(json.dumps({
        "metric": "serving_trace_overhead",
        "value": round(ratio, 4),
        "unit": "ratio",
        "floor": floor,
        "tps_off": round(off, 1),
        "tps_on": round(on, 1),
        "rounds": rounds,
        "trace_events": n_span,
    }), flush=True)
    print(f"# trace: tracing-on {on:.0f} tok/s vs off {off:.0f} tok/s "
          f"({ratio:.3f}x >= {floor}x floor) with {n_span} span events "
          "captured", file=sys.stderr)


def _fleet_kvtier_phase(work, env):
    """ISSUE 17: fleet-scale KV — prefix-sticky routing over a host-RAM
    page tier, validated against a single giant replica.

    A 2-replica unified fleet with a deliberately tight device page
    pool and a host tier serves three waves: shared-prefix traffic
    (testing/traffic.py), a churn wave of unique prompts that forces
    the earlier chains off-device (spills), then exact repeats of the
    first wave's prompts — which the sticky router sends back to their
    chain's owner, where the pages FAULT BACK through the inject
    executable instead of re-prefilling.

    Asserts: fleet-wide prefix hit-rate within BENCH_KVTIER_RATIO
    (1.3x) of a single giant replica (2x slots/pages/tier) on the
    identical arrivals; >= 1 spill and >= 1 hash-verified fault-back
    with zero rejects; every completed request TOKEN-EXACT between the
    two runs (greedy determinism — a corrupt spill or misrouted chain
    would break parity); decode_compiles == 1 and zero steady-state
    compiles on every replica; zero lost requests.  Emits the
    fleet_prefix_hit_rate JSON metric line.

    The fleet run (not the giant baseline) is traced (ISSUE 19):
    unified lifecycles must assemble with zero negative spans, and the
    one-line trace posture rides the JSON as its "trace" block."""
    import numpy as np
    from paddle_tpu.inference.fleet import ServingFleet
    from paddle_tpu.observability import aggregate, timeline
    from paddle_tpu.testing import traffic as T

    ratio_bound = float(os.environ.get("BENCH_KVTIER_RATIO", 1.3))
    duration_s = float(os.environ.get("BENCH_KVTIER_DURATION_S", 5.0))
    rate = float(os.environ.get("BENCH_KVTIER_RATE", 5.0))
    n_repeat = int(os.environ.get("BENCH_KVTIER_REPEATS", 16))
    n_churn = int(os.environ.get("BENCH_KVTIER_CHURN", 10))

    # a tight pool (3 slots x 7 pages/request nearly fills 24 pages)
    # makes the reclaim LRU evict — i.e. SPILL — under routine churn
    base = {"cfg": {"vocab_size": 256, "hidden_size": 32,
                    "num_layers": 2, "num_heads": 2, "max_seq_len": 64,
                    "dtype": "float32", "use_flash": False,
                    "remat": False},
            "seed": 0, "paged": True, "kv_handoff": True,
            "page_size": 4, "seq_buckets": [16], "batch_buckets": [1, 2],
            "max_len": 48}
    spec = dict(base, slots=3, num_pages=24, host_tier_mb=4)
    giant = dict(base, slots=6, num_pages=48, host_tier_mb=8)
    cache = _jit_cache_dir()

    arrivals = T.generate(T.TrafficSpec(
        duration_s=duration_s, base_rate=rate, seed=17, vocab=256,
        bursts=(), prompt_len=(12, 0.3, 10, 16),
        output_tokens=(8, 0.3, 6, 10), prefix_hit_rate=0.8,
        prefix_pool=2, prefix_len=8, id_prefix="kt"))
    assert len(arrivals) >= 8, "thin out BENCH_KVTIER_RATE no further"
    repeats = [a for a in arrivals if a.prefix_hit][:n_repeat] \
        or arrivals[:n_repeat]
    crng = np.random.RandomState(91)
    churn = [crng.randint(1, 256, 14) for _ in range(n_churn)]

    def run(tag, spec, replicas, env_run=None):
        fleet = ServingFleet(
            spec, replicas=replicas, env_base=env_run or env,
            jit_cache_dir=cache,
            log_dir=os.path.join(work, tag, "logs"),
            heartbeat_s=30, restart_backoff_s=0.2)
        try:
            assert fleet.await_healthy(timeout=180) == replicas
            # wave A: shared-prefix traffic at recorded offsets
            T.replay(arrivals, lambda a: fleet.submit(
                a.prompt, a.max_new_tokens, request_id=a.request_id),
                speed=2.0)
            fleet.drain(timeout=180)
            # steady-state compile attestation baseline: every
            # executable the remaining waves touch has now run
            warm = {r.id: dict(r.last_stats)
                    for r in fleet._replicas if r.last_stats}
            # churn wave: unique prompts force the wave-A chains off
            # the device pool (reclaim evictions -> host-tier spills)
            for i, p in enumerate(churn):
                fleet.submit(p, 8, request_id=f"{tag}-churn{i}")
            fleet.drain(timeout=180)
            # repeat wave: exact wave-A prompts, fresh ids — sticky
            # routing returns each to its chain's owner, where the
            # spilled pages fault back (no re-prefill).  Lightly paced:
            # a single burst would exhaust the owner's slots and force
            # least-loaded fallbacks that are pure routing noise
            for j, a in enumerate(repeats):
                fleet.submit(a.prompt, a.max_new_tokens,
                             request_id=f"{tag}-rep{j}")
                time.sleep(0.08)
            done, failed = fleet.drain(timeout=180)
            assert not failed, (tag,
                                {k: v.error for k, v in failed.items()})
            reps = {r.id: dict(r.last_stats)
                    for r in fleet._replicas if r.last_stats}
            fstats = fleet.stats()
        finally:
            fleet.close()
        assert len(reps) == replicas, (
            f"{tag}: only {len(reps)}/{replicas} replicas ever "
            "reported stats")
        for rid, st in reps.items():
            assert st.get("decode_compiles") == 1, (tag, rid, st)
            base_st = warm.get(rid) or {}
            for k in ("prefill_compiles", "decode_compiles",
                      "handoff_compiles"):
                assert st.get(k) == base_st.get(k), (
                    f"{tag} replica {rid}: {k} moved "
                    f"{base_st.get(k)} -> {st.get(k)} after warm "
                    "traffic — a steady-state XLA compile")
        if os.environ.get("BENCH_KVTIER_DEBUG"):
            for rid, st in sorted(reps.items()):
                print(f"# kvtier-debug {tag} r{rid}: "
                      + " ".join(f"{k}={st.get(k)}" for k in (
                          "prefix_page_hits", "prefix_page_misses",
                          "pages_spilled", "fault_backs",
                          "fault_back_rejects", "requests_admitted",
                          "prefill_calls", "preemptions")),
                      file=sys.stderr)
        hits = sum(int(st.get("prefix_page_hits") or 0)
                   for st in reps.values())
        misses = sum(int(st.get("prefix_page_misses") or 0)
                     for st in reps.values())
        agg = {k: sum(int(st.get(k) or 0) for st in reps.values())
               for k in ("pages_spilled", "spill_bytes", "fault_backs",
                         "pages_faulted_back", "fault_back_rejects")}
        toks = {rid: list(r.tokens) for rid, r in done.items()}
        return hits / max(hits + misses, 1), agg, fstats, toks

    # the fleet run is traced end to end; restore before the giant
    # baseline so its boot stays an untraced control
    tel = os.path.join(work, "kvtier", "telemetry")
    trace_prev = os.environ.get("PADDLE_TRACE")
    os.environ["PADDLE_TRACE"] = "1"
    timeline.configure(tel)
    try:
        fleet_rate, agg, fstats, fleet_toks = run(
            "kvtier", spec, 2,
            env_run=dict(env, PADDLE_TELEMETRY_DIR=tel,
                         PADDLE_TRACE="1"))
    finally:
        if trace_prev is None:
            os.environ.pop("PADDLE_TRACE", None)
        else:
            os.environ["PADDLE_TRACE"] = trace_prev
        timeline.configure(None)
    giant_rate, _g_agg, _g_fs, giant_toks = run("giant", giant, 1)
    tsum = aggregate.trace_summary(tel)
    assert tsum["traces"] >= len(fleet_toks), (
        "kvtier lifecycles missing from trace assembly", tsum)
    assert tsum["negative_spans"] == 0, tsum

    # token-exact parity across the two runs: same params + greedy =>
    # any served-from-tier byte corruption or misroute breaks this.
    # churn/repeat ids carry the run tag — strip it so the same
    # logical request lines up across runs
    def _norm(toks, tag):
        return {(i[len(tag) + 1:] if i.startswith(tag + "-") else i): v
                for i, v in toks.items()}

    fleet_toks = _norm(fleet_toks, "kvtier")
    giant_toks = _norm(giant_toks, "giant")
    joint = set(fleet_toks) & set(giant_toks)
    assert len(joint) == len(fleet_toks) == len(giant_toks)
    mismatched = [i for i in joint if fleet_toks[i] != giant_toks[i]]
    assert not mismatched, f"token mismatch vs giant: {mismatched[:8]}"

    ratio = giant_rate / max(fleet_rate, 1e-9)
    assert ratio <= ratio_bound, (
        f"fleet prefix hit-rate {fleet_rate:.3f} is {ratio:.2f}x off "
        f"the giant replica's {giant_rate:.3f} (bound {ratio_bound}x) "
        "— sticky routing is not keeping chains with their owners")
    assert agg["pages_spilled"] >= 1, agg
    assert agg["fault_backs"] >= 1 and agg["pages_faulted_back"] >= 1, (
        "no spill-then-fault-back happened — the repeat wave "
        f"re-prefilled instead: {agg}")
    assert agg["fault_back_rejects"] == 0, agg
    assert fstats["prefix_routed"] >= 1, fstats

    print(json.dumps({
        "metric": "fleet_prefix_hit_rate",
        "value": round(fleet_rate, 4),
        "unit": "fraction",
        "giant_baseline": round(giant_rate, 4),
        "ratio_vs_giant": round(ratio, 3),
        "ratio_bound": ratio_bound,
        "pages_spilled": agg["pages_spilled"],
        "spill_bytes": agg["spill_bytes"],
        "fault_backs": agg["fault_backs"],
        "pages_faulted_back": agg["pages_faulted_back"],
        "fault_back_rejects": 0,
        "prefix_routed": fstats["prefix_routed"],
        "prefix_fallbacks": fstats["prefix_fallbacks"],
        "prefix_migrations": fstats["prefix_migrations"],
        "requests": len(fleet_toks),
        "lost_requests": 0,
        "trace": tsum,
    }), flush=True)
    print(f"# kvtier: sticky routing held {fstats['prefix_routed']} "
          f"dispatches for their prefix owner "
          f"({fstats['prefix_fallbacks']} least-loaded fallbacks)",
          file=sys.stderr)
    print(f"# kvtier: {agg['pages_spilled']} pages spilled to the host "
          f"tier ({agg['spill_bytes'] / 1024:.0f}KB), "
          f"{agg['fault_backs']} hash-verified fault-backs "
          f"({agg['pages_faulted_back']} pages, 0 rejects, 0 "
          "re-prefills)", file=sys.stderr)
    print(f"# kvtier: hit-rate {fleet_rate:.3f} vs giant "
          f"{giant_rate:.3f} ({ratio:.2f}x <= {ratio_bound}x); "
          f"token-exact on {len(joint)} requests; decode_compiles==1 "
          "and zero steady-state compiles per replica, 0 lost",
          file=sys.stderr)


def _fleet_routerchaos_phase(work, env):
    """ISSUE 18: the router is as killable as any replica.  Three runs
    over identical traffic on a 1-prefill + 1-decode journaled fleet:

    * *ref* — in-process, ``journal_dir=None``: reference tokens +
      baseline tokens/s.
    * *journal* — in-process, journal ON: token parity + write
      overhead (BENCH_RC_OVERHEAD=0 skips it — the smoke's budget).
    * *chaos* — the supervised router (``fleet_supervisor``) serving
      the same traffic through a :class:`FleetClient`; SIGKILLed the
      moment it holds in-flight work AND at least one KV handoff has
      crossed it, then relaunched by the supervisor against the same
      journal.  The surviving workers are re-adopted: zero admitted
      requests lost, token-exact vs ref, worker pids unchanged, zero
      replica restarts, per-worker cumulative compile counts unchanged
      across the kill (no XLA compiles during re-adoption)."""
    import signal as _signal
    import socket as _socket
    import threading

    import numpy as np
    from paddle_tpu.inference.fleet import ServingFleet
    from paddle_tpu.inference.fleet_supervisor import (FleetClient,
                                                       supervise_router)

    n_requests = int(os.environ.get("BENCH_RC_REQUESTS", 16))
    gen_tokens = int(os.environ.get("BENCH_RC_TOKENS", 24))
    run_overhead = os.environ.get("BENCH_RC_OVERHEAD", "1") != "0"
    # a hard 0.95 gate would fail on box-speed weather, not on a real
    # regression — loose CI backstop, measured value reported
    min_ratio = float(os.environ.get("BENCH_RC_MIN_RATIO", 0.6))

    spec = {"cfg": {"vocab_size": 256, "hidden_size": 32,
                    "num_layers": 2, "num_heads": 2, "max_seq_len": 64,
                    "dtype": "float32", "use_flash": False,
                    "remat": False},
            "seed": 0, "paged": True, "slots": 2,
            "max_len": 8 + gen_tokens + 8, "page_size": 8,
            "seq_buckets": [8], "batch_buckets": [1]}
    roles = ["prefill", "decode"]
    rng = np.random.RandomState(31)
    prompts = [rng.randint(1, 256, int(rng.randint(4, 8)))
               for _ in range(n_requests)]
    reqs = [{"id": f"rc{i}", "prompt": [int(t) for t in p],
             "max_new_tokens": gen_tokens} for i, p in
            enumerate(prompts)]
    cache = _jit_cache_dir()

    def run_inproc(tag, journal_dir):
        fleet = ServingFleet(
            spec, roles=roles, env_base=env, jit_cache_dir=cache,
            journal_dir=journal_dir,
            log_dir=os.path.join(work, tag, "logs"),
            heartbeat_s=30, restart_backoff_s=0.2)
        try:
            assert fleet.await_healthy(timeout=180) == 2
            t0 = time.perf_counter()
            for i, p in enumerate(prompts):
                fleet.submit(p, gen_tokens, request_id=f"rc{i}")
            done, failed = fleet.drain(timeout=240)
            wall = time.perf_counter() - t0
            assert not failed and len(done) == n_requests, (
                tag, len(done), failed)
            st = fleet.stats()
            assert st["kv_handoffs"] > 0, (tag, st)
        finally:
            fleet.close()
        toks = {rid: [int(t) for t in r.tokens]
                for rid, r in done.items()}
        tps = sum(len(t) for t in toks.values()) / max(wall, 1e-9)
        return toks, tps

    # ---- ref: journal off (also warms the shared jit cache) ----
    ref_tokens, ref_tps = run_inproc("rc_ref", None)

    # ---- journal on: parity + write overhead ----
    overhead = None
    if run_overhead:
        j_tokens, j_tps = run_inproc(
            "rc_journal", os.path.join(work, "rc_journal_wal"))
        assert j_tokens == ref_tokens, \
            "journaling changed decode output — it must be pure WAL"
        overhead = {"ref_tps": round(ref_tps, 2),
                    "journal_tps": round(j_tps, 2),
                    "ratio": round(j_tps / max(ref_tps, 1e-9), 4)}
        assert overhead["ratio"] >= min_ratio, (
            f"journal write overhead past the CI backstop: {overhead} "
            f"(min ratio {min_ratio})")

    # ---- chaos: supervised router, SIGKILL mid-traffic ----
    probe = _socket.socket()
    probe.bind(("127.0.0.1", 0))
    control_port = probe.getsockname()[1]
    probe.close()
    renv = dict(env)
    renv.update(
        PADDLE_FLEET_MODEL=json.dumps(spec),
        PADDLE_FLEET_ROLES=json.dumps(roles),
        PADDLE_FLEET_CONTROL_PORT=str(control_port),
        PADDLE_FLEET_JOURNAL_DIR=os.path.join(work, "rc_wal"),
        PADDLE_FLEET_LOG_DIR=os.path.join(work, "rc_chaos", "logs"),
        PADDLE_JIT_CACHE_DIR=cache,
        PADDLE_FLEET_HEARTBEAT_S="30")
    stop_sup = threading.Event()
    sup_out = {}

    def sup():
        try:
            sup_out["incidents"] = supervise_router(
                renv, backoff=0.3,
                log_dir=os.path.join(work, "rc_chaos"),
                stop_event=stop_sup)
        except Exception as e:                             # noqa: BLE001
            sup_out["error"] = f"{type(e).__name__}: {e}"
    sup_th = threading.Thread(target=sup, daemon=True)
    sup_th.start()
    client = FleetClient(control_port, retry_window_s=180.0)
    try:
        head, tail = reqs[: n_requests // 2], reqs[n_requests // 2:]
        t0 = time.perf_counter()
        resp = client.submit(head)
        assert not resp["rejected"], resp
        pid0 = client.poll()["pid"]
        # kill only once the router really holds the state the journal
        # must reconstruct: in-flight work and >= 1 crossed handoff
        killed_at = None
        deadline = time.time() + 120
        while time.time() < deadline:
            p = client.poll()
            stc = p["stats"]
            comp = {str(k): v
                    for k, v in p["replica_compiles"].items()}
            # every replica must have REPORTED a compile count before
            # the kill — a None baseline can't attest 0 readopt compiles
            if p["pending"] > 0 and stc.get("kv_handoffs", 0) >= 1 \
                    and all(v is not None for v in comp.values()):
                killed_at = {"pending": p["pending"],
                             "kv_handoffs": stc["kv_handoffs"]}
                pids_before = {str(k): v
                               for k, v in p["replica_pids"].items()}
                compiles_before = comp
                break
            time.sleep(0.02)
        assert killed_at, "router never held in-flight+handoff state"
        os.kill(pid0, _signal.SIGKILL)
        # the client rides through the death: the rest of the traffic
        # and every poll retry until the relaunched generation answers
        resp = client.submit(tail)
        assert not resp["rejected"], resp
        n_done = 0
        deadline = time.time() + 240
        while time.time() < deadline:
            p = client.poll()
            n_done = len(p["done"]) + len(p["failed"])
            if p["pending"] == 0 and n_done >= n_requests:
                break
            time.sleep(0.05)
        wall = time.perf_counter() - t0
        st = p["stats"]
        pid1 = p["pid"]
        assert pid1 != pid0, "router was never actually replaced"
        # ---- the certification ----
        assert not p["failed"], f"requests LOST across the router " \
                                f"death: {p['failed']}"
        assert len(p["done"]) == n_requests, (len(p["done"]),
                                              n_requests)
        mismatch = [r["id"] for r in reqs
                    if p["done"][r["id"]]["tokens"]
                    != ref_tokens[r["id"]]]
        assert not mismatch, (
            f"token parity lost across router death: {mismatch}")
        pids_after = {str(k): v for k, v in p["replica_pids"].items()}
        assert pids_after == pids_before, (
            f"worker pids changed — replicas restarted instead of "
            f"re-adopted: {pids_before} -> {pids_after}")
        assert st.get("replica_restarts", 0) == 0, st
        assert st["readopts"] == len(roles), st
        compiles_after = {str(k): v
                          for k, v in p["replica_compiles"].items()}
        assert compiles_after == compiles_before, (
            f"XLA compiles during re-adoption: {compiles_before} -> "
            f"{compiles_after}")
        rec_s = st.get("router_recovery_s")
        assert rec_s is not None, \
            "fleet_router_recovery_s never stamped"
    finally:
        try:
            client.shutdown()
        except Exception:                                  # noqa: BLE001
            pass
        stop_sup.set()
        sup_th.join(timeout=30)
    assert "error" not in sup_out, sup_out
    assert len(sup_out.get("incidents") or []) == 1, sup_out

    print(json.dumps({
        "metric": "fleet_router_recovery_s",
        "value": round(rec_s, 3),
        "unit": "s",
        "requests": n_requests,
        "lost_requests": 0,
        "killed_at": killed_at,
        "router_pids": [pid0, pid1],
        "readopts": st["readopts"],
        "readopt_events": st["readopt_events"],
        "recovery_requeues": st.get("recovery_requeues", 0),
        "recovery_rehandoffs": st.get("recovery_rehandoffs", 0),
        "replica_restarts": 0,
        "journal_size_bytes": st.get("journal_size_bytes"),
        "wall_s": round(wall, 2),
        "journal_overhead": overhead,
    }), flush=True)
    if overhead:
        print(json.dumps({
            "metric": "fleet_journal_overhead",
            "value": overhead["ratio"], "unit": "ratio",
            **overhead}), flush=True)
    print(f"# routerchaos: router pid {pid0} SIGKILLed holding "
          f"{killed_at['pending']} in-flight "
          f"({killed_at['kv_handoffs']} handoffs crossed) -> "
          f"relaunched as pid {pid1}, {st['readopts']} workers "
          f"re-adopted (pids unchanged, 0 compiles), "
          f"{n_requests} requests, 0 lost, token-exact, "
          f"recovery {rec_s:.2f}s", file=sys.stderr)


def _reexec_cpu_mesh():
    """``--cpu-mesh N``: re-exec once with ``JAX_PLATFORMS=cpu`` and N
    forced host devices.  ``XLA_FLAGS`` is read when the backend starts,
    so it has to be in the environment BEFORE anything touches jax —
    the one reason this file ever starts a second interpreter (same
    dance as tests/conftest.py)."""
    if "--cpu-mesh" not in sys.argv \
            or os.environ.get("BENCH_CPU_MESH_CHILD") == "1":
        return
    try:
        n = int(sys.argv[sys.argv.index("--cpu-mesh") + 1])
    except (IndexError, ValueError):
        sys.exit("usage: bench.py [--dp-overlap|--faults|--serving|"
                 "--fleet|--model-parallel] --cpu-mesh N  "
                 "(N = forced host-platform device count)")
    # (private copy of paddle_tpu.testing.env.clean_cpu_env: importing
    # the package first would cost the import twice — keep the two in
    # sync)
    env = dict(os.environ)
    env["BENCH_CPU_MESH_CHILD"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n}"
                        ).strip()
    repo = os.path.dirname(os.path.abspath(__file__))
    kept = [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
            if p and p != repo]
    env["PYTHONPATH"] = os.pathsep.join([repo] + kept)
    sys.stdout.flush()
    sys.stderr.flush()
    os.execve(sys.executable,
              [sys.executable, "-u", os.path.abspath(__file__)]
              + sys.argv[1:], env)


MODES = {"--eager-micro": "eager_micro", "--dp-overlap": "dp_overlap",
         "--serving": "serving_bench",
         "--model-parallel": "model_parallel_bench",
         "--faults": "faults_bench", "--fleet": "fleet_bench"}

if __name__ == "__main__":
    _reexec_cpu_mesh()
    chosen = [fn for flag, fn in MODES.items() if flag in sys.argv]
    unknown = [a for a in sys.argv[1:] if a.startswith("--")
               and a not in MODES and a != "--cpu-mesh"]
    if unknown or len(chosen) > 1:
        sys.exit(f"usage: bench.py [{'|'.join(MODES)}] [--cpu-mesh N]  "
                 "(no argument: the flagship run on the chip)")
    globals()[chosen[0] if chosen else "run"]()
