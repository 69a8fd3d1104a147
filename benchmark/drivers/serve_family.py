"""Requests through ``PagedServingEngine.submit`` / ``step`` for a model
family that the configuration file names (``model_type``), as
``serve_engine`` drives GPT: the same client, stamps, phases and record
— ramp (set-up) -> window -> with ``--trace 1`` a few seconds more under
the profiler -> the reference check, with the engine freed.

What differs from ``serve_engine`` (which is GPT by construction):

* the model, its config class and its plain reference are looked up by
  the family's name: ``paddle_tpu/models/<model_type>.py``,
  ``benchmark/lib/reference_<model_type>.py``;
* the float32 copy of the weights does not fit (5.07B parameters are
  10 GB in bf16): the reference upcasts ONE LAYER AT A TIME from the
  bf16 weights, inside its jitted layer function;
* the record also carries the expert layer's counters over the window
  (``moe``), the bytes a cached position needs, and — traced — the
  latent kernel's own events (``kernel``) and the samples of the traced
  tail alone (``tail_samples``), for the family's readers
  (``benchmark/metrics/moe.*.py``, ``decode_step_roofline.moe_mla.py``,
  ``paged_mla_decode_roofline.py``), which ``BENCHMARK.json`` lists for
  the cell and ``run.py`` calls like every other.

``correct`` is decided as for the GPT cell — one decode executable, no
compile in the window, the latent kernel engaged, no failed request, and
the emitted tokens held to the plain float32 reference, teacher-forced
on the engine's own tokens — with one difference that the expert layer
forces.  Top-6 of 128 is a DISCRETE choice: where the sixth and seventh
scores are a near-tie, bf16 rounding of the router's input picks another
expert than float32 does, the layer's output moves by a whole expert,
and the row's logits by up to a row-deviation (PERF.md section 6: about
a third of the rows, on the chip and in a float32-against-bf16 run on
the CPU alike; the other rows read 0.06-0.09).  No limit on the WORST
row can tell that from a lower precision.  So every generated position
of each sampled request is checked (they cost one head product more,
the forward is the same), and two limits decide:

* the SHARE of rows whose emitted token's reference logit sits more
  than ``GAP_OFF`` row-deviations below the row's maximum is at most
  ``OFF_SHARE_TOL`` — what a lower precision or a dropped expert moves;
* no row sits more than ``GAP_MAX_TOL`` below — a paging, masking or
  position fault reads another token's latent and lands where an
  unrelated token would (4 to 5 row-deviations down).
"""
import contextlib
import dataclasses
import gc
import importlib
import math
import tempfile
import time

import numpy as np

from ..lib import probe, spans, stats, traffic, xplane
from .serve_engine import Client, Item, build_engine, hist_summary

# A row is OFF when the emitted token's float32-reference logit sits
# more than GAP_OFF row-deviations below the reference row's maximum.
# The two limits lie between two readings each (PERF.md section 6, my
# chip runs, PR 28): what the bf16 deployment gave at most over its
# seeds, and what the reference gives when computed in the nearest
# precision below (its weights rounded to int8 per output channel) or
# with an expert dropped from every token's six.
GAP_OFF = 0.10
OFF_SHARE_TOL = 0.22
GAP_MAX_TOL = 3.0
# 6 requests, every generated position (about 1,800 rows): a request
# costs one float32 forward over its history through 7 x 128 experts,
# and the run has to end inside the 111 s a warm run is allowed
# (PERF.md section 2).
CHECKED_REQUESTS = 6
TRACED_S = 3.0
KERNEL = "paged_mla_decode"     # the latent kernel's ``name=``
TOP_OPS = 40                    # rows of breakdown.device_ops


def family_modules(model_type):
    """(model module, reference module) of the family called
    ``model_type``; the config class is the module's one dataclass."""
    model = importlib.import_module("paddle_tpu.models." + model_type)
    reference = importlib.import_module(
        "benchmark.lib.reference_" + model_type)
    config_cls = next(
        v for v in vars(model).values()
        if dataclasses.is_dataclass(v) and isinstance(v, type)
        and v.__module__ == model.__name__)
    return model, reference, config_cls


def build_config(config_cls, arch):
    fields = {f.name for f in dataclasses.fields(config_cls)}
    return config_cls(**{k: v for k, v in arch.items() if k in fields})


def kernel_events(planes, on_chip, prefix=KERNEL):
    """(calls, seconds) of the device ops whose name starts with
    ``prefix`` — a Pallas kernel's ``name=`` is its ``XLA Ops`` event's
    name.  None off the chip or where there is none."""
    if not on_chip:
        return None
    durations = [d for p in planes or []
                 if p["name"].startswith(xplane.DEVICE_PLANE)
                 for ln in p["lines"] if ln["name"] == xplane.OPS_LINE
                 for name, _, d in ln["events"] if name.startswith(prefix)]
    if not durations:
        return None
    return {"calls": len(durations), "seconds": sum(durations) / 1e9}


@contextlib.contextmanager
def traced(record, on_chip):
    """``probe.traced`` with more rows kept and the latent kernel's own
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
    record["kernel"] = kernel_events(planes, on_chip)


def emitted_logit_gaps(reference, params, hp, samples, width, most):
    """How far below the reference's best logit each EMITTED token sits,
    in row-deviations, for EVERY generated position of each sample
    ``(history, emitted)`` (``lib/reference.py::emitted_logit_gaps``
    checks three rows a request).  The float32 weights do not fit
    whole: the reference's own ``layer_at_a_time`` upcasts one layer's
    bf16 leaves at a time.  One padded ``width`` (causal: padding
    behind a row cannot reach it) and ``most`` rows, so one set of
    compiles serves every seed; the gaps are reduced on the device
    ([most, V] float32 is half a GB)."""
    import jax
    import jax.numpy as jnp
    rows_of = reference.layer_at_a_time(hp)

    @jax.jit
    def gaps_of(logits, emitted):
        at = jnp.take_along_axis(logits, emitted[:, None], -1)[:, 0]
        return (logits.max(-1) - at) / logits.std(-1)

    gaps, took = [], []
    with jax.default_matmul_precision("highest"):
        for history, emitted in samples:
            t = time.perf_counter()
            n = len(emitted)
            seq = np.zeros((width,), np.int32)
            seq[:len(history)] = history
            first = len(history) - n - 1     # the row that emitted tok 0
            rows = np.minimum(first + np.arange(most), len(history) - 1)
            toks = np.zeros((most,), np.int32)
            toks[:n] = emitted
            ref = rows_of(params, jnp.asarray(seq),
                          jnp.asarray(rows, jnp.int32))
            gaps.extend(np.asarray(gaps_of(ref, jnp.asarray(toks)))[:n]
                        .tolist())
            took.append(round(time.perf_counter() - t, 3))
    return gaps, took


MOE_COUNTERS = ("moe_assignments", "moe_experts_touched",
                "moe_max_expert_load")


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
        "moe": {k: after[k] - before[k] for k in MOE_COUNTERS},
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

    # ---- correctness, with the engine and its pool freed
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
    off_share = sum(g > GAP_OFF for g in gaps) / max(1, len(gaps))
    ctx.note(phase="reference", emitted_logit_gap_max=max(gaps, default=None),
             rows_off_share=off_share, gap_off=GAP_OFF,
             rows_off_the_argmax=sum(g > 0 for g in gaps),
             emitted_logit_gap_mean=sum(gaps) / max(1, len(gaps)),
             rows_checked=len(gaps), requests_checked=len(samples),
             off_share_tol=OFF_SHARE_TOL, gap_max_tol=GAP_MAX_TOL,
             reference_s=time.perf_counter() - t_ref,
             seconds_of_each=took)

    record.update(
        attempted=len(done) + len(bad), failed=len(bad),
        checks={
            "one_decode_executable": final["decode_compiles"] == 1,
            "no_compile_in_window": (
                in_window["count"] == 0
                and in_window["persistent_cache_requests"] == 0),
            "paged_kernel_engaged": paged_calls >= 1 or not ctx.on_chip,
            "emitted_tokens_near_reference_argmax": (
                len(gaps) > 0 and off_share <= OFF_SHARE_TOL),
            "no_emitted_token_far_from_reference": (
                len(gaps) > 0 and max(gaps) <= GAP_MAX_TOL),
            "no_request_failed": not bad,
        })
    return record
