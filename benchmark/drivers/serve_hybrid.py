"""Requests through ``PagedServingEngine.submit`` / ``step`` for a HYBRID
family that the configuration file names (``model_type``; today
``phi4flash``): state-space layers, window layers and layers that read
one layer's cache in one model, so that most of a sequence's state lives
in arrays indexed by SLOT (rings, recurrent state) beside a one-layer
page pool.  The client, stamps, phases and record are ``serve_engine``'s
and ``serve_family``'s (imported, not copied): ramp (set-up) -> window
-> with ``--trace 1`` a second more under the profiler -> the reference
check, with the engine freed.

What differs from ``serve_looped``:

* the record carries ``hybrid`` — the family's counters over the window:
  ``state_steps`` (slots a decode step ran, summed) and
  ``window_rows_read`` (the ring rows each window layer read), which the
  decode step hands back with the sampled tokens, and ``prefill_rows`` /
  ``prefill_cross_rows`` (rows a prefill wave put through the stateful
  layers, and through the stateless ones) — and ``slot_state_bytes``;
  traced, the ``paged_diff_attn_decode`` kernel's own events
  (``kernel``) and the samples of the traced tail alone
  (``tail_samples``), for the family's readers
  (``benchmark/metrics/decode_step_roofline.hybrid.py``,
  ``paged_diff_attn_decode_roofline.py``,
  ``prefill.cross_rows_share.py``);
* the requests held to the reference all END PAST THE WINDOW (so the
  ring has wrapped under every checked row's later positions), and one
  of them at least ran on a slot that an earlier request had used (so
  the state's reset is under the comparison);
* ``correct``: one decode executable, no compile in the window, the
  differential kernel engaged, no failed request, and — teacher-forced
  on the engine's own tokens — how far the emitted token's
  float32-reference logit sits below the reference row's maximum, at
  EVERY generated row of each sampled request: the mean within
  ``EMITTED_GAP_MEAN_TOL`` row-deviations and the worst row within
  ``EMITTED_GAP_MAX_TOL`` (see the constants); and, because a token
  cannot tell a state-space state kept in lower precision from the
  float32 one the configuration states, the state itself: what the
  engine holds in the first state-space layer's ``S`` for the requests
  that have run longest when the run ends
  (``PagedServingEngine.slot_state``), against the reference's state
  after the same positions, within ``STATE_ERR_TOL``.  The float32
  weights do not fit beside the bf16 ones (15.4 GB): the reference
  upcasts one pair of layers at a time.
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
# the sampled requests (3,000-5,400 rows a run).  The model is dense and
# 32 layers deep: the bf16 deployment emits another token than the
# float32 reference's best in one row of eight, by a hair.  Two limits,
# each between two readings through THIS comparison at the cell's size
# (PERF.md section 6, my chip runs, PR 35; the deployment on 12 seeds,
# each control on 2 or 3):
#   mean over the rows   bf16 0.0046-0.0057   zero memory 0.057-0.058   int8 weights 0.065-0.073   window 256 2.10-2.17
#   worst row            bf16 0.152-0.219     zero memory 0.68-0.74     int8 weights 0.78-0.88     window 256 5.4-6.0
# The MEAN tells the stated precision from the nearest one below (the
# reference with matmul weights rounded to int8 per output channel) and
# from a fault in every row (the memory units reading a zero memory, the
# window layers a window of 256): 0.02 is 3.5 times the deployment's
# worst and a third of the lowest control.  The WORST ROW is for a fault
# in few rows (a wrong page, a ring row out of place), which lands where
# an unrelated token does, 2 to 6 down: 0.45 is twice the deployment's
# worst and two thirds of the lowest control's.  A state-space state kept
# in bf16 reads as the deployment does (mean 0.0047-0.0053, worst row
# 0.22-0.25): the scan's output is rounded to bf16 on its way out
# anyway, so no limit of THIS comparison can tell it; the next one does.
EMITTED_GAP_MEAN_TOL = 0.02
EMITTED_GAP_MAX_TOL = 0.45
# ``|S - S_ref| / |S_ref|`` of the FIRST state-space layer, the worst of
# the ``CHECKED_STATES`` running requests with the most positions, S_ref
# the float32 reference's state after the same positions.  The first
# layer, because only the embedding is upstream of it: the deeper
# layers' states sit 1.4-5.6% off the reference through the bf16 layers
# under them (layer 2 1.4-1.8%, layer 16 4.2-5.6%), with ``S`` in bf16 as
# without (all on the note line, ``state_err_by_layer``).  Readings at
# the cell's size (PERF.md section 6, my chip runs, PR 35):
#   first layer, a request   float32 S 0.0039-0.0053 (40 requests, 10 seeds, 563-2,943 positions, flat in them)
#                            S rounded to bf16 after every update 0.0070-0.0337 (16 requests, 4 seeds; rising to ~2,000 positions)
#   worst of the four        float32 S 0.0041-0.0053 (six seeds through this very statistic)
#                            through bf16 0.0094 / 0.0139 (two seeds through it; 0.0102 / 0.0337 on two more, any four requests)
#                            the reference's weights rounded to int8 0.0200
# 0.007 is 1.3 times the deployment's worst and three quarters of the
# control's lowest.
STATE_ERR_TOL = 0.007
CHECKED_STATES = 4
# A request of 4,096 positions is about 33 TFLOP in float32 at
# ``highest`` and 9 x 4,096 sequential state updates: the reference runs
# after the window, and the run has to end inside the time a warm run is
# allowed (PERF.md section 2).  Four requests are 2,500-6,000 rows.
CHECKED_REQUESTS = 4
# a decode step is 16 kernel calls and some hundreds of small fusions
TRACED_S = 1.0
KERNEL = "paged_diff_attn_decode"   # the decode kernel's ``name=``
TOP_OPS = 40                        # rows of breakdown.device_ops
HYBRID_COUNTERS = ("state_steps", "window_rows_read", "prefill_rows",
                   "prefill_cross_rows")


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


def checked_sample(finished, slots, window, seed, how_many):
    """``how_many`` of the finished items in a seeded order: only
    requests whose final length passed the window, and, where one
    exists, at least one that was submitted after the first ``slots``
    requests (the closed loop fills every slot first, so it ran on a
    slot another request had left)."""
    long = [it for it in finished
            if len(it.prompt) + len(it.req.tokens) > window]
    order = traffic.stream_rng(seed, traffic.S_SAMPLE).permutation(len(long))
    picked = [long[j] for j in order[:how_many]]
    reused = [long[j] for j in order if long[j].idx >= slots]
    if picked and reused and not any(it.idx >= slots for it in picked):
        picked[-1] = reused[0]
    return picked


def held_states(eng, live, window, how_many):
    """``(history, S)`` of the ``how_many`` requests that are running
    now, past the window, and have run LONGEST (what rounding does to a
    state builds up over the slowest channel's memory, a thousand
    positions and more): the positions the engine has folded into the
    slot's state-space state, and that state (host float32 [n, di, N]).
    Call it between two ``step()``s."""
    long = [it for it in live
            if len(it.prompt) + len(it.req.tokens) > window]
    long.sort(key=lambda it: (-len(it.prompt) - len(it.req.tokens), it.idx))
    out = []
    for it in long:
        if len(out) == how_many:
            break
        if it.req.slot is None or it.req.done:
            continue            # queued, or ended while we looked
        folded, state = eng.slot_state(it.req.slot)
        if it.req.done:
            continue            # its last token was still in flight
        history = np.concatenate([it.prompt,
                                  np.asarray(it.req.tokens, np.int32)])
        if folded != len(history) - 1:
            raise RuntimeError(f"request {it.idx}: the state holds {folded} "
                               f"positions, the host {len(history)} tokens")
        out.append((history[:folded], state["ssm_state"]))
    return out


def state_errors(reference, params, hp, held, width):
    """For each ``(history, S)``: ``|S - S_ref| / |S_ref|`` (Frobenius)
    of every state-space layer, in layer order, with ``S_ref`` the
    float32 reference's state after the same positions.  One padded
    ``width``, so one set of compiles serves every seed."""
    import jax
    import jax.numpy as jnp
    states_of = reference.states_at_a_time(hp)

    @jax.jit
    def errors_of(got, want):
        return (jnp.sqrt(jnp.sum(jnp.square(got - want), (1, 2)))
                / jnp.sqrt(jnp.sum(jnp.square(want), (1, 2))))

    out = []
    with jax.default_matmul_precision("highest"):
        for history, got in held:
            seq = np.zeros((width,), np.int32)
            seq[:len(history)] = history
            want = states_of(params, jnp.asarray(seq), len(history))
            out.append(np.asarray(errors_of(jnp.asarray(got), want)).tolist())
    return out


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
    ctx.note(phase="weights")
    eng = build_engine(ctx, params, cfg)
    ctx.note(phase="engine")
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
        "hybrid": {k: after[k] - before[k] for k in HYBRID_COUNTERS},
        "slot_state_bytes": after["slot_state_bytes"],
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

    # ---- a second more under the profiler, same traffic
    if ctx.trace:
        tail_from = len(client.samples)
        tail_before = eng.stats()
        with traced(record, ctx.on_chip):
            client.pump(time.perf_counter() + TRACED_S)
        tail_after = eng.stats()
        record["tail_samples"] = list(client.samples[tail_from:])
        record["tail_hybrid"] = {
            k: tail_after[k] - tail_before[k]
            for k in HYBRID_COUNTERS + ("decode_steps",)}
        trace, kernel = record["trace"], record["kernel"]
        if trace and kernel and not any(
                name.startswith(KERNEL) for name, _ in trace["device_ops"]):
            trace["device_ops"].append([KERNEL, kernel["seconds"]])

    done = [it for it in items if it.done_t and t0 <= it.done_t <= t1]
    bad = [it for it in items
           if it.rejected or (it.req is not None and it.req.failed)]
    final = eng.stats()
    record["stamps"] = [it.stamps for it in items if it.req is not None]

    # ---- correctness, with the engine, its pool and its slots freed
    finished = [it for it in items if it.done_t and t0 <= it.done_t
                and len(it.req.tokens) > 1]
    picked = checked_sample(finished, eng.slots, cfg.sliding_window,
                            ctx.seed, CHECKED_REQUESTS)
    samples = []
    for it in picked:
        toks = np.asarray(it.req.tokens, np.int32)
        samples.append((np.concatenate([it.prompt, toks]), toks))
    held = held_states(eng, client.live.values(), cfg.sliding_window,
                       CHECKED_STATES)
    kernel_calls = metrics.counter("serving.paged_diff_kernel_calls").value
    del eng, client
    gc.collect()
    t_ref = time.perf_counter()
    gaps, took = emitted_logit_gaps(reference, params, hp, samples, width,
                                    most)
    gap_mean = sum(gaps) / max(1, len(gaps))
    errs = state_errors(reference, params, hp, held, width)
    state_err = max((e[0] for e in errs), default=None)
    ctx.note(phase="reference", emitted_logit_gap_max=max(gaps, default=None),
             emitted_logit_gap_mean=gap_mean,
             rows_off_the_argmax=sum(g > 0 for g in gaps),
             rows_checked=len(gaps), requests_checked=len(samples),
             final_lengths=[len(h) for h, _ in samples],
             on_a_reused_slot=sum(it.idx >= record["slots"]
                                  for it in picked),
             gap_mean_tol=EMITTED_GAP_MEAN_TOL,
             gap_max_tol=EMITTED_GAP_MAX_TOL,
             state_err=state_err, state_err_tol=STATE_ERR_TOL,
             state_err_by_layer=[[round(x, 6) for x in e] for e in errs],
             state_positions=[len(h) for h, _ in held],
             reference_s=time.perf_counter() - t_ref, seconds_of_each=took)

    record.update(
        attempted=len(done) + len(bad), failed=len(bad),
        checks={
            "one_decode_executable": final["decode_compiles"] == 1,
            "no_compile_in_window": (
                in_window["count"] == 0
                and in_window["persistent_cache_requests"] == 0),
            "paged_kernel_engaged": kernel_calls >= 1 or not ctx.on_chip,
            "emitted_tokens_near_reference_argmax": (
                len(gaps) > 0 and gap_mean <= EMITTED_GAP_MEAN_TOL),
            "no_emitted_token_far_from_reference": (
                len(gaps) > 0 and max(gaps) <= EMITTED_GAP_MAX_TOL),
            # the limit was read at the cell's size: a rehearsal's toy (a
            # state of a thousand numbers after a dozen positions, 0.3-
            # 1.2%) reports the number and is held to having one
            "ssm_state_near_reference": state_err is not None and (
                state_err <= STATE_ERR_TOL or not ctx.on_chip),
            "no_request_failed": not bad,
        })
    return record
