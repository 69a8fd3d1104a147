"""Requests through ``PagedServingEngine.submit`` / ``step`` for a family
whose window layers keep per-slot RINGS of rotated keys beside a full
layer's page pool, its query heads in groups, and one chip's SHARE of
its routed experts beside averaged shared ones (``model_type``
``cohere2_moe``), as ``serve_scmoe`` drives the latent expert-share
family: the same client, stamps, phases and record
(``serve_family``'s and ``serve_engine``'s helpers, imported), with what
the family adds.

* The record's ``moe`` carries the expert share's counters
  (``deepseek_v3``'s names and sense) and ``attn`` the K/V rows the
  decode steps' window layers and full layers read
  (``attn_window_rows``, ``attn_full_rows``), for
  ``attn.window_skipped_share``, ``decode_step_roofline.cohere2`` and
  the ``moe.*.cohere2`` readers.
* Traced, the record carries the grouped-query kernel's own events
  (``kernel``: ``paged_attn_decode``, window and full calls alike), the
  tail's own counters (``tail_attn``), for
  ``paged_attn_decode_roofline.gqa``, and the experts' grouped matmul's
  calls in the decode programs (``grouped_matmul``), for
  ``moe_grouped_matmul_roofline.cohere2``.
* ``correct``: against the plain float32 reference given the same
  expert share (``benchmark/lib/reference_cohere2_moe.py``, one
  sublayer at a time), with limits of this driver's own:

  - the emitted tokens, over every generated row of
    ``CHECKED_REQUESTS`` sampled requests, ``PAST_WINDOW`` of them
    ending past the window (``serve_family``'s rows-off share);
  - the program's attention sublayer of every layer
    (``cohere2_moe.attention_sublayer``: the decode kernel over a ring,
    or over the pool) at a decode step's shape, fed what the reference
    gives each layer at the sampled requests' decode rows PAST THE
    WINDOW (``attention_check``);
  - the program's expert layer of every layer
    (``cohere2_moe.expert_sublayer``: ``moe_ffn`` over the share and
    the shared experts, as the walk runs them) at a decode step's
    shape, fed what the reference gives each layer at the sampled
    requests' decode rows (``expert_check``): the share of rows whose
    router chose other experts than the reference's, and the worst
    row of those it did not;
  - the K/V the engine itself keeps — every ring row and every pool row
    of ``KV_SLOTS`` live slots past the window, read out of its arrays
    after the window (``live_kv``) — against the reference's K/V at the
    positions those rows should hold (``kv_readings``).

  The limits were set from what the bf16 deployment gave and what
  planted controls gave on the chip (``reference_cohere2_moe.CONTROLS``
  and this driver's ``STORE_CONTROLS``; PERF.md section 6).
  ``CONTROLS`` names the controls a run also reads (the benchmark's
  own runs read none).
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
from .serve_family import (GAP_OFF, TOP_OPS, build_config, family_modules,
                           kernel_events)
from .serve_scmoe import decode_calls, decode_rows, device_bytes

# The limits, each between two readings (PERF.md section 6: on one TPU
# v5 lite, the seeds of the bf16 deployment and one seed of each planted
# control).  Four layers of random weights move a row's logits
# little: a token limit tells a fault that reads the wrong rows, not a
# precision.  Rows off: bf16 0-0.025% (3 of 12,402 rows at most), the
# window layers reading the whole history 0.17-0.23%.
OFF_SHARE_TOL = 0.001
# The attention sublayer past the window, the worst row's RMS difference
# over every layer: bf16 0.0021-0.0027, the reference's weights rounded
# to int8 (the nearest precision below the bf16 the configuration
# states) 0.0111-0.0119, the whole history 0.068-0.346.  The limit sits
# 2.0x over the deployment's worst and 2.0x under int8's least.
ATTN_TOL = 0.0055
# The router: the share of (row, layer) whose eight chosen experts
# differ from the reference's.  bf16 1.30-5.66% (its input rounded to
# bf16 moves the 8th and 9th scores past each other), the reference in
# bf16 11.9% (its sigmoid's scores on bf16's grid).  1.8x over the
# deployment's worst, 1.2x under the bf16 router.
ROUTER_TOL = 0.10
# The expert layer, the worst row's RMS difference over the rows routed
# alike, every layer: bf16 0.00106-0.00108, int8 weights 0.00412, the
# reference in bf16 0.00111.  1.85x over the deployment's worst, 2.1x
# under int8.
EXPERT_TOL = 0.002
# The stored K/V, a row a position's [K | V] in one layer.  The first
# window layer's rows depend on nothing the program computed before it
# but the embedding and its norm: their worst row tells the ring's
# store, bf16 0.00356-0.00358, an int8 store 0.0099, int8 weights
# 0.0121, the reference in bf16 0.0067, a ring row written one on 1.91
# (1.7x over the deployment, 1.6x under the int8 store).  Deeper rows
# carry the deployment's own drift (a row routed otherwise in an earlier
# layer moves a position's K by up to 0.47), so every layer is read by
# its rows' mean: bf16 0.0095-0.0100, the reference in bf16 0.022, int8
# weights 0.032, the whole history 0.150, a ring row written one on
# 0.370 (1.6x over the deployment, 1.4x under bf16).
KV_FIRST_TOL = 0.006
KV_MEAN_TOL = 0.016
CHECKED_REQUESTS = 6
PAST_WINDOW = 3         # of them at least, where the window holds them
KV_SLOTS = 2            # live slots past the window whose K/V are read
S_ROWS = 4              # traffic.stream_rng's stream of the check's rows
S_EXPERT_ROWS = 64      # of the expert check's rows
TRACED_S = 3.0
KERNEL = "paged_attn_decode"     # the grouped-query kernel's ``name=``
MOE_COUNTERS = ("moe_assignments", "moe_experts_touched",
                "moe_max_expert_load", "moe_remote_assignments")
ATTN_COUNTERS = ("attn_window_rows", "attn_full_rows")
# faults of the store, planted on the reference's side of the K/V
# comparison: K and V rounded to int8 per position and head (a store of
# lower precision), a ring row written one row on (a misplaced write)
STORE_CONTROLS = ("kv_int8", "ring_shift")
CONTROLS = ()


@contextlib.contextmanager
def traced(record, on_chip, rows):
    """``serve_family.traced`` for the grouped-query kernel, with the
    grouped matmul's calls in the decode programs (``rows`` of them a
    call: ``decode_rows``) counted beside it."""
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
    record["grouped_matmul"] = decode_calls(planes, on_chip, rows)


def checked_sample(finished, window, seed, how_many, past):
    """``how_many`` of the finished items in a seeded order, ``past`` of
    them (where there are so many) among those whose final length
    passed the window."""
    order = traffic.stream_rng(seed, traffic.S_SAMPLE).permutation(
        len(finished))
    ranked = [finished[j] for j in order]
    long = [it for it in ranked
            if len(it.prompt) + len(it.req.tokens) > window][:past]
    taken = {id(it) for it in long}
    rest = [it for it in ranked if id(it) not in taken]
    return long + rest[:how_many - len(long)]


def live_kv(eng, items, window, how_many):
    """Of the requests in a slot now, past the window, the ``how_many``
    that have run longest: ``(history, rings K, rings V, pool K, pool
    V)`` — the positions the engine has folded into the slot, each
    window layer's ring [3 P, W, C] as it stands (row ``j`` the last
    position ``= j mod W``) and the full layers' pool rows of every
    position [P, n, C], host arrays read out of the engine's own
    arrays.  Call it between two ``step()``s."""
    import jax
    long = [it for it in items
            if it.req is not None and it.req.slot is not None
            and not it.req.done
            and len(it.prompt) + len(it.req.tokens) > window]
    long.sort(key=lambda it: (-len(it.prompt) - len(it.req.tokens), it.idx))
    out = []
    for it in long:
        if len(out) == how_many:
            break
        if it.req.slot is None or it.req.done:
            continue            # ended while we looked
        slot = it.req.slot
        folded, rings = eng.slot_state(slot)
        if it.req.done:
            continue            # its last token was still in flight
        history = np.concatenate([it.prompt,
                                  np.asarray(it.req.tokens, np.int32)])
        if folded != len(history) - 1:
            raise RuntimeError(f"request {it.idx}: the slot holds {folded} "
                               f"positions, the host {len(history)} tokens")
        ps = eng._page_size
        pages = np.asarray(eng._tables_np[slot][:-(-folded // ps)])
        pool = jax.device_get(tuple(p[:, pages]
                                    for p in eng._page_pools()))
        pool = [p.reshape(p.shape[0], -1, p.shape[-1])[:, :folded]
                for p in pool]
        out.append((history[:folded], rings["ring_k"], rings["ring_v"],
                    *pool))
    return out


def ring_positions(folded, window):
    """The position ring row ``j`` holds after ``folded`` positions: the
    last one before ``folded`` that is ``j mod W``."""
    rows = np.arange(window)
    return folded - 1 - np.mod(folded - 1 - rows, window)


def int8_rows(x, heads):
    """``x`` [R, heads * d] rounded to int8 per row and head."""
    import jax.numpy as jnp
    r = x.reshape(x.shape[0], heads, -1)
    scale = jnp.max(jnp.abs(r), -1, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (jnp.round(r / scale) * scale).reshape(x.shape)


def kv_readings(reference, params, hp, held, width, control=None,
                store=None, forward=None):
    """{the first window layer's worst row's RMS difference, the rows'
    mean over every layer, both by layer} between the K/V the engine keeps (``live_kv``) and the
    reference's (or one of its controls') at the positions each row
    should hold, a row being a position's [K | V] in one layer; ``store``
    one of ``STORE_CONTROLS``, planted on the reference's side.  One
    padded ``width``, and ``forward`` the gap check's
    ``run_at_a_time`` where it is given: one set of compiles."""
    import jax
    import jax.numpy as jnp
    if not held:
        return None
    if store:
        control = None
    run = forward or reference.run_at_a_time(hp, control)
    kv = reference.kv_of(hp, control)
    W, L = hp["sliding_window"], hp["num_hidden_layers"]
    heads = hp["num_key_value_heads"]

    @jax.jit
    def errors(got, want):
        return jnp.sqrt(jnp.mean(jnp.square(got - want), -1))

    worst, total, rows = [0.0] * L, [0.0] * L, [0] * L
    for history, ring_k, ring_v, pool_k, pool_v in held:
        folded = len(history)
        seq = np.zeros((width,), np.int32)
        seq[:folded] = history
        with jax.default_matmul_precision("highest"):
            _, h, _ = run(params, jnp.asarray(seq), jnp.zeros((1,), jnp.int32))
            for layer in range(L):
                name, i = reference.stack_of(layer)
                rotary = reference.sliding(layer)
                k, v = kv(h[layer], params[name]["wqkv"], jnp.int32(i),
                          rotary)
                want = jnp.concatenate([k, v], -1).astype(jnp.float32)
                if rotary:
                    pos = ring_positions(folded, W)
                    if store == "ring_shift":
                        pos = np.roll(pos, 1)
                    got = np.concatenate([ring_k[i], ring_v[i]], -1)
                else:
                    pos = np.arange(folded)
                    got = np.concatenate([pool_k[i], pool_v[i]], -1)
                want = want[jnp.asarray(pos)]
                if store == "kv_int8":
                    want = int8_rows(want, 2 * heads)
                err = errors(jnp.asarray(got, jnp.float32), want)
                worst[layer] = max(worst[layer], float(err.max()))
                total[layer] += float(err.sum())
                rows[layer] += int(err.shape[0])
        del h
    return {"kv_err_first": worst[0], "kv_err_mean": sum(total) / sum(rows),
            "kv_err_by_layer": worst,
            "kv_err_mean_by_layer": [t / r for t, r in zip(total, rows)],
            "kv_rows_checked": sum(rows)}


def reference_readings(reference, params, hp, samples, width, most, slots,
                       seed, attn_check, expert_check, control=None,
                       forward=None):
    """Through the reference (or one of its controls), for every sample
    ``(history, emitted, prompt length)``: how far below the reference's
    best logit each EMITTED token sits, in row-deviations, at every
    generated position; ``attn_check(rows, h [L, width, H], a [L, S,
    H])`` of the check rows of each (``slots`` of the decode rows past
    the window, in a seeded order, wrapping round), each layer's normed
    input at every position and its attention sublayer's output at those
    rows; and ``expert_check(h [L, S, H], fn)`` of ``slots`` of its
    decode rows (any, in a seeded order), ``fn`` the reference's expert
    layer (one sample's at a time: a sample's ``h`` is half a GB).  One
    padded ``width`` and ``most + slots`` rows, so one set of compiles
    serves every seed; ``forward`` the control's ``run_at_a_time``, if
    built already.  Returns (gaps, seconds a request, the attention
    checks' results, the expert checks')."""
    import jax
    import jax.numpy as jnp
    run = forward or reference.run_at_a_time(hp, control)
    experts_of = reference.expert_layer(hp, control)
    W = hp["sliding_window"]

    @jax.jit
    def gaps_of(logits, emitted):
        at = jnp.take_along_axis(logits, emitted[:, None], -1)[:, 0]
        return (logits.max(-1) - at) / logits.std(-1)

    gaps, took, checks, expert = [], [], [], []
    for i, (history, emitted, n_prompt) in enumerate(samples):
        t = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            n = len(emitted)
            seq = np.zeros((width,), np.int32)
            seq[:len(history)] = history
            first = len(history) - n - 1     # the row that emitted tok 0
            rows = np.minimum(first + np.arange(most), len(history) - 1)
            # a decode step took position p in and emitted p + 1
            decode = np.arange(max(n_prompt, W), len(history) - 1)
            check = (np.resize(traffic.stream_rng(seed, S_ROWS + i)
                               .permutation(decode), slots)
                     if len(decode) else np.zeros((0,), np.int64))
            every = np.arange(n_prompt, len(history) - 1)
            by_expert = (np.resize(traffic.stream_rng(
                seed, S_EXPERT_ROWS + i).permutation(every), slots)
                if len(every) else np.zeros((0,), np.int64))
            toks = np.zeros((most,), np.int32)
            toks[:n] = emitted
            logits, h, a = run(params, jnp.asarray(seq), jnp.asarray(
                np.concatenate([rows, check]), jnp.int32))
            gaps.extend(np.asarray(gaps_of(logits[:most],
                                           jnp.asarray(toks)))[:n].tolist())
        # the program's side at the program's own precision
        if len(check):
            checks.append(attn_check(check, h, a[:, most:]))
        if len(by_expert):
            expert.append(expert_check(h[:, jnp.asarray(by_expert)],
                                       experts_of))
        del h, a
        took.append(round(time.perf_counter() - t, 3))
    return gaps, took, checks, expert


def attention_check(model, cfg, params, page_size):
    """``check(rows, h, a)``: the worst row's RMS difference between the
    program's attention sublayer (``attention_sublayer``, at a decode
    step's shape: the check's ``slots`` rows a call) and the
    reference's ``a``, over every layer, both sides fed the reference's
    normed inputs ``h`` (rounded to the compute dtype for the
    program)."""
    import jax
    import jax.numpy as jnp
    cd = jnp.dtype(cfg.dtype)
    fns = {layer: jax.jit(lambda p, h, rows, layer=layer:
                          model.attention_sublayer(p, cfg, h, layer, rows,
                                                   page_size))
           for layer in range(cfg.num_hidden_layers)}

    def check(rows, h, a):
        rows = jnp.asarray(rows, jnp.int32)
        worst = 0.0
        for layer, fn in fns.items():
            got = fn(params, h[layer].astype(cd), rows).astype(jnp.float32)
            err = jnp.sqrt(jnp.mean((got - a[layer]) ** 2, -1)).max()
            worst = max(worst, float(err))
        return worst
    return check


def expert_check(model, reference, cfg, params):
    """``check(h [L, S, H], fn) -> (rows routed otherwise, rows, the worst
    row's RMS difference over the rows routed alike, the worst over
    every row)``: the program's expert layer of every layer
    (``expert_sublayer``, at a decode step's shape: ``S = slots`` rows a
    call) against the reference's ``fn`` (``reference.expert_layer``),
    both fed the reference's normed inputs (rounded to the compute
    dtype for the program).  A row is routed alike where the program's
    eight chosen experts are the reference's."""
    import jax
    import jax.numpy as jnp
    cd = jnp.dtype(cfg.dtype)
    fns = {layer: jax.jit(lambda p, h, layer=layer:
                          model.expert_sublayer(p, cfg, h, layer))
           for layer in range(cfg.num_hidden_layers)}

    @jax.jit
    def compare(got, chosen, want, want_chosen):
        err = jnp.sqrt(jnp.mean(jnp.square(got.astype(jnp.float32)
                                           - want.astype(jnp.float32)), -1))
        alike = jnp.all(jnp.sort(chosen, -1) == jnp.sort(want_chosen, -1),
                        -1)
        return (jnp.sum(~alike), jnp.max(jnp.where(alike, err, 0.0)),
                jnp.max(err))

    def check(h, fn):
        off = rows = 0
        worst = worst_any = 0.0
        for layer, program in fns.items():
            got, chosen = program(params, h[layer].astype(cd))
            name, i = reference.stack_of(layer)
            stack = {k: params[name][k] for k in reference.EXPERT_LEAVES}
            with jax.default_matmul_precision("highest"):
                want, want_chosen = fn(h[layer], stack, jnp.int32(i))
            n_off, w, w_any = compare(got, chosen, want, want_chosen)
            off += int(n_off)
            rows += int(h.shape[1])
            worst = max(worst, float(w))
            worst_any = max(worst_any, float(w_any))
        return off, rows, worst, worst_any
    return check


def summary(gaps, took, attn, experts, kv, seconds):
    """One control's (or the reference proper's) readings, for the
    notes and the checks."""
    off = sum(e[0] for e in experts)
    rows = sum(e[1] for e in experts)
    out = {
        "rows_off_share": sum(g > GAP_OFF for g in gaps) / max(1, len(gaps)),
        "emitted_logit_gap_max": max(gaps, default=None),
        "emitted_logit_gap_mean": sum(gaps) / max(1, len(gaps)),
        "attn_err_max": max(attn, default=None),
        "attn_err_by_request": attn,
        "router_off_share": off / rows if rows else None,
        "expert_err_max": max((e[2] for e in experts), default=None),
        "expert_err_max_any_row": max((e[3] for e in experts),
                                      default=None),
        "expert_rows_checked": rows,
        "rows_checked": len(gaps),
        "reference_s": seconds,
        "seconds_of_each": took}
    out.update(kv or {"kv_err_first": None, "kv_err_mean": None})
    return out


def refused(c):
    """Whether a reading passes a limit (a control must)."""
    return bool(c["rows_off_share"] > OFF_SHARE_TOL
                or (c["attn_err_max"] or 0) > ATTN_TOL
                or (c["router_off_share"] or 0) > ROUTER_TOL
                or (c["expert_err_max"] or 0) > EXPERT_TOL
                or (c["kv_err_first"] or 0) > KV_FIRST_TOL
                or (c["kv_err_mean"] or 0) > KV_MEAN_TOL)


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
             device_bytes=device_bytes(ctx.devices),
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
        "moe": {k: after.get(k, 0) - before.get(k, 0) for k in MOE_COUNTERS},
        "attn": {k: after.get(k, 0) - before.get(k, 0)
                 for k in ATTN_COUNTERS},
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

    # ---- a few seconds more under the profiler, same traffic
    if ctx.trace:
        tail_from = len(client.samples)
        tail_before = eng.stats()
        with traced(record, ctx.on_chip,
                    decode_rows(eng.slots * cfg.num_experts_per_tok)):
            client.pump(time.perf_counter() + TRACED_S)
        tail_after = eng.stats()
        record["tail_samples"] = list(client.samples[tail_from:])
        record["tail_attn"] = {
            k: tail_after.get(k, 0) - tail_before.get(k, 0)
            for k in ATTN_COUNTERS + ("decode_steps",)}
        trace, kernel = record["trace"], record["kernel"]
        if trace and kernel and not any(
                name.startswith(KERNEL) for name, _ in trace["device_ops"]):
            trace["device_ops"].append([KERNEL, kernel["seconds"]])

    done = [it for it in items if it.done_t and t0 <= it.done_t <= t1]
    bad = [it for it in items
           if it.rejected or (it.req is not None and it.req.failed)]
    final = eng.stats()
    record["stamps"] = [it.stamps for it in items if it.req is not None]
    held_kv = live_kv(eng, items, cfg.sliding_window, KV_SLOTS)

    # ---- correctness, with the engine, its pool and its rings freed
    finished = [it for it in items if it.done_t and t0 <= it.done_t
                and len(it.req.tokens) > 1]
    picked = checked_sample(finished, cfg.sliding_window, ctx.seed,
                            CHECKED_REQUESTS, PAST_WINDOW)
    samples = []
    for it in picked:
        toks = np.asarray(it.req.tokens, np.int32)
        samples.append((np.concatenate([it.prompt, toks]), toks,
                        len(it.prompt)))
    paged_calls = metrics.counter("serving.paged_kernel_calls").value
    slots, ps = eng.slots, eng._page_size
    del eng, client
    gc.collect()
    held = device_bytes(ctx.devices)
    attn = attention_check(model, cfg, params, ps)
    experts = expert_check(model, reference, cfg, params)
    readings = {}
    for control in (None,) + tuple(c for c in CONTROLS
                                   if c not in STORE_CONTROLS):
        t_ref = time.perf_counter()
        forward = reference.run_at_a_time(hp, control)
        c_gaps, took, errs, exp = reference_readings(
            reference, params, hp, samples, width, most, slots, ctx.seed,
            attn, experts, control, forward)
        kv = kv_readings(reference, params, hp, held_kv, width, control,
                         forward=forward)
        readings[control] = summary(c_gaps, took, errs, exp, kv,
                                    time.perf_counter() - t_ref)
        if control is None:
            forward_proper = forward
    proper = readings[None]
    for store in (c for c in CONTROLS if c in STORE_CONTROLS):
        readings[store] = dict(proper, **(kv_readings(
            reference, params, hp, held_kv, width, store=store,
            forward=forward_proper) or {}))
    ctx.note(phase="reference", gap_off=GAP_OFF,
             off_share_tol=OFF_SHARE_TOL, attn_tol=ATTN_TOL,
             router_tol=ROUTER_TOL, expert_tol=EXPERT_TOL,
             kv_first_tol=KV_FIRST_TOL, kv_mean_tol=KV_MEAN_TOL,
             requests_checked=len(samples),
             final_lengths=[len(h) for h, _, _ in samples],
             kv_lengths=[len(h[0]) for h in held_kv],
             attn_rows_checked=slots * len(proper["attn_err_by_request"]),
             device_bytes_before=held, device_bytes=device_bytes(ctx.devices),
             **proper)
    for control in CONTROLS:
        c = readings[control]
        ctx.note(phase="control", control=control, **c, refused=refused(c))

    def within(value, tol):
        # a rehearsal's toy may end no request past its window
        return value <= tol if value is not None else not ctx.on_chip

    record.update(
        attempted=len(done) + len(bad), failed=len(bad),
        checks={
            "one_decode_executable": final["decode_compiles"] == 1,
            "no_compile_in_window": (
                in_window["count"] == 0
                and in_window["persistent_cache_requests"] == 0),
            "paged_kernel_engaged": paged_calls >= 1 or not ctx.on_chip,
            "emitted_tokens_near_reference_argmax": (
                proper["rows_checked"] > 0
                and proper["rows_off_share"] <= OFF_SHARE_TOL),
            "attention_near_reference_past_the_window": within(
                proper["attn_err_max"], ATTN_TOL),
            "router_near_reference": (
                proper["router_off_share"] is not None
                and proper["router_off_share"] <= ROUTER_TOL),
            "expert_layer_near_reference": (
                proper["expert_err_max"] is not None
                and proper["expert_err_max"] <= EXPERT_TOL),
            "stored_kv_near_reference": (
                within(proper["kv_err_first"], KV_FIRST_TOL)
                and within(proper["kv_err_mean"], KV_MEAN_TOL)),
            "no_request_failed": not bad,
        })
    return record
