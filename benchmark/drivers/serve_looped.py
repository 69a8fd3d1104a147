"""Requests through ``PagedServingEngine.submit`` / ``step`` for a LOOPED
dense family that the configuration file names (``model_type``; today
``ouro``): the stacked layers run ``total_ut_steps`` times over shared
weights, and the page pool holds a layer's K/V once for every pass.  The
client, stamps, phases and record are ``serve_engine``'s and
``serve_family``'s (imported, not copied): ramp (set-up) -> window ->
with ``--trace 1`` a few seconds more under the profiler -> the
reference check, with the engine freed.

What differs from ``serve_family`` (which reads the expert layer's
counters and the latent kernel's name):

* the record carries ``loop`` — the two counters the decode step hands
  back with the sampled tokens, over the window: ``loop_tokens`` (active
  slots a step, summed) and ``loop_passes`` (the sum over passes of the
  slots the published exit rule kept running) — and, traced, the
  ``paged_attn_decode`` kernel's own events (``kernel``) and the samples
  of the traced tail alone (``tail_samples``), for the family's readers
  (``benchmark/metrics/loop.passes_per_token.py``,
  ``decode_step_roofline.looped.py``, ``paged_attn_decode_roofline.py``);
* ``correct`` is decided as for the GPT cell, because the model is
  dense: one decode executable, no compile in the window, the paged
  kernel engaged, no failed request, and — teacher-forced on the
  engine's own tokens — how far the emitted token's float32-reference
  logit sits below the reference row's maximum, at EVERY generated row
  of each sampled request: the mean within ``EMITTED_GAP_MEAN_TOL``
  row-deviations and the worst row within ``EMITTED_GAP_MAX_TOL`` (two
  limits, because the loop amplifies bf16 rounding: see the constants).
  The float32 weights do not fit beside the bf16 ones (10.7 GB): the
  reference upcasts one layer at a time.
"""
import contextlib
import dataclasses
import gc
import math
import tempfile
import time

import numpy as np

from ..lib import probe, spans, stats, traffic, xplane
from .serve_engine import Client, Item, build_engine, hist_summary
from .serve_family import (build_config, emitted_logit_gaps, family_modules,
                           kernel_events)

# How far below the float32 reference row's maximum the emitted token's
# reference logit sits, in row-deviations, over every generated row of
# the sampled requests.  The loop amplifies rounding: 4 x 48 bf16 layer
# applications, each sublayer's output re-normalised to unit size,
# leave the bf16 deployment's logits some tenths of a row-deviation from
# float32's, so with 49,152 near-tied candidates two to six rows in ten
# emit another token than the reference's best — by a little.  GPT's
# 0.10 on the worst row cannot hold; two limits do, each set between
# two readings through THIS comparison at the cell's size (PERF.md
# section 6, my chip runs, PR 33; 16 runs of the bf16 deployment, each
# control on 3 seeds):
#   mean over the rows   bf16 0.03-0.17   int8 weights 0.51-0.97   shared pages 1.91-2.37
#   worst row            bf16 0.65-1.44   int8 weights 2.05-2.57   shared pages 3.67-4.55
# The MEAN tells the stated precision from the nearest one below (the
# reference with weights rounded to int8 per output channel) and stands
# between them; the WORST ROW stands between the deployment's and where
# an unrelated token lands (every pass sharing pass 1's pages, the
# looped family's own fault).
EMITTED_GAP_MEAN_TOL = 0.30
EMITTED_GAP_MAX_TOL = 2.5
# A request of 1,536 positions is about 33 TFLOP in float32 at
# ``highest`` through 4 x 48 layers (2.3 s on the chip): the reference
# runs after the window, and the run has to end inside the time a warm
# run is allowed (PERF.md section 2).  Three requests are 900-1,900
# rows.
CHECKED_REQUESTS = 3
# 25 decode steps, 4,800 kernel calls: every small fusion of 192 layer
# applications is an event, and reducing 3 s of them took 25 s.
TRACED_S = 1.0
KERNEL = "paged_attn_decode"    # the decode kernel's ``name=``
TOP_OPS = 40                    # rows of breakdown.device_ops
LOOP_COUNTERS = ("loop_tokens", "loop_passes")


@contextlib.contextmanager
def traced(record, on_chip):
    """``probe.traced`` with more rows kept and the decode kernel's own
    events counted before the trace is thrown away."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()
        planes = xplane.load(d)
    record["trace"] = xplane.reduce(planes, on_chip, top=TOP_OPS)
    record["kernel"] = kernel_events(planes, on_chip, prefix=KERNEL)


def run(ctx):
    import jax
    from paddle_tpu.observability import metrics

    arch, mix = ctx.config, ctx.traffic
    model, reference, config_cls = family_modules(arch["model_type"])
    cfg = build_config(config_cls, arch)
    hp = dataclasses.asdict(cfg)
    ramp_s = float(mix["ramp_s"])
    tail_s = TRACED_S if ctx.trace else 0.0

    # ---- everything the run will send, from the seed, before any clock
    n = int(math.ceil(mix["max_requests_per_s"]
                      * (ramp_s + ctx.seconds + tail_s)))
    reqs = traffic.requests(mix, cfg.vocab_size, n, ctx.seed)
    engine_args = arch["engine"]
    longest = max(len(p) for p, _ in reqs)
    if longest > max(engine_args["seq_buckets"]):
        raise ValueError(f"a prompt of {longest} tokens fits no prefill "
                         f"bucket {engine_args['seq_buckets']}")
    # the reference's one shape: the laws' own limits, so every seed and
    # every run compiles (and then finds in the cache) the same programs
    most = int(mix["output_len"]["max"])
    width = int(mix["prompt_len"]["max"]) + most
    if width > engine_args["max_len"]:
        raise ValueError(f"a request may need {width} positions, more "
                         f"than max_len {engine_args['max_len']}")

    params = jax.block_until_ready(jax.jit(
        lambda k: model.init_params(cfg, k))(jax.random.PRNGKey(ctx.seed)))
    eng = build_engine(ctx, params, cfg)
    eng.warmup()
    ctx.note(phase="warm", compile=probe.compile_counters())

    items = [Item(i, p, m) for i, (p, m) in enumerate(reqs)]
    client = Client(eng, items, int(mix["backlog_depth"]), sample=ctx.trace)

    # ---- ramp: the same traffic until the house is in its steady state
    client.pump(time.perf_counter() + ramp_s)
    for name in ("serving.decode_step_s", "serving.prefill_s"):
        metrics.histogram(name).reset()
    before = eng.stats()
    c0 = probe.compile_counters()
    client.step_s.clear()
    client.samples.clear()

    # ---- the window
    t0 = ctx.open_window()
    client.pump(t0 + ctx.seconds)
    t1 = time.perf_counter()
    after = eng.stats()
    in_window = probe.delta(probe.compile_counters(), c0)
    gaps = [g for it in items
            for g in stats.gaps_ending_in(it.stamps, t0, t1)]
    ctx.note(phase="window_closed", window_s=t1 - t0,
             steps=len(client.step_s), submitted=client.next_i,
             token_gap_p50_s=stats.percentile(gaps, 50),
             token_gap_p95_s=stats.percentile(gaps, 95),
             **spans.ring_use(t0, t1))
    pool_positions = after["num_pages"] * after["page_size"]
    record = {
        "t0": t0, "t1": t1, "window_s": t1 - t0,
        "step_s": list(client.step_s),
        "hist": {"decode": hist_summary("serving.decode_step_s"),
                 "prefill": hist_summary("serving.prefill_s")},
        "counters": {k: after[k] - before[k] for k in (
            "decode_steps", "prefill_calls", "requests_completed",
            "preemptions", "prefix_page_hits", "prefix_page_misses",
            "cow_copies", "tokens_generated")},
        "loop": {k: after[k] - before[k] for k in LOOP_COUNTERS},
        "samples": list(client.samples),
        "compiles_in_window": in_window,
        "num_pages": after["num_pages"], "slots": eng.slots,
        "page_size": after["page_size"], "arch": arch,
        "weight_itemsize": params["embed"].dtype.itemsize,
        "kv_bytes_per_position": after["kv_bytes_per_position"],
        "kv_itemsize": (after["kv_bytes_per_position"]
                        / model.kv_bytes_per_position(cfg, 1)),
        "kv_bytes_held_per_position": (after["kv_bytes_total"]
                                       / pool_positions),
    }

    # ---- a few seconds more under the profiler, same traffic
    if ctx.trace:
        tail_from = len(client.samples)
        with traced(record, ctx.on_chip):
            client.pump(time.perf_counter() + TRACED_S)
        record["tail_samples"] = list(client.samples[tail_from:])
        trace, kernel = record["trace"], record["kernel"]
        if trace and kernel and not any(
                name.startswith(KERNEL) for name, _ in trace["device_ops"]):
            trace["device_ops"].append([KERNEL, kernel["seconds"]])

    done = [it for it in items if it.done_t and t0 <= it.done_t <= t1]
    bad = [it for it in items
           if it.rejected or (it.req is not None and it.req.failed)]
    final = eng.stats()
    record["stamps"] = [it.stamps for it in items if it.req is not None]

    # ---- correctness, with the engine and its pool freed: a seeded
    # sample of the requests that finished after the window opened
    finished = [it for it in items if it.done_t and t0 <= it.done_t
                and len(it.req.tokens) > 1]
    pick = traffic.stream_rng(ctx.seed, traffic.S_SAMPLE).permutation(
        len(finished))[:CHECKED_REQUESTS]
    samples = []
    for j in pick:
        it = finished[j]
        toks = np.asarray(it.req.tokens, np.int32)
        samples.append((np.concatenate([it.prompt, toks]), toks))
    paged_calls = metrics.counter("serving.paged_kernel_calls").value
    del eng, client
    gc.collect()
    t_ref = time.perf_counter()
    gaps, took = emitted_logit_gaps(reference, params, hp, samples, width,
                                    most)
    gap_mean = sum(gaps) / max(1, len(gaps))
    ctx.note(phase="reference", emitted_logit_gap_max=max(gaps, default=None),
             emitted_logit_gap_mean=gap_mean,
             rows_off_the_argmax=sum(g > 0 for g in gaps),
             rows_checked=len(gaps), requests_checked=len(samples),
             gap_mean_tol=EMITTED_GAP_MEAN_TOL,
             gap_max_tol=EMITTED_GAP_MAX_TOL,
             reference_s=time.perf_counter() - t_ref, seconds_of_each=took)

    record.update(
        attempted=len(done) + len(bad), failed=len(bad),
        checks={
            "one_decode_executable": final["decode_compiles"] == 1,
            "no_compile_in_window": (
                in_window["count"] == 0
                and in_window["persistent_cache_requests"] == 0),
            "paged_kernel_engaged": paged_calls >= 1 or not ctx.on_chip,
            "emitted_tokens_near_reference_argmax": (
                len(gaps) > 0 and gap_mean <= EMITTED_GAP_MEAN_TOL),
            "no_emitted_token_far_from_reference": (
                len(gaps) > 0 and max(gaps) <= EMITTED_GAP_MAX_TOL),
            "no_request_failed": not bad,
        })
    return record
