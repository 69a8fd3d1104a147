"""Requests through ``PagedServingEngine.submit`` / ``step``, as a client
in the engine's own process would send them.

The queue is never empty: the next seeded request is submitted whenever
fewer than ``slots + backlog_depth`` are outstanding, so every slot is
busy.  Tokens per second is judged.

Phases: ramp (same traffic, counted as set-up) -> window -> with
``--trace 1`` a few seconds more under the profiler -> the reference
check, with the engine freed.

A token is stamped when the engine appends it to ``Request.tokens``: the
harness hands each request a list that stamps on ``append``.  The append
follows the host's readback of the sampled token directly, so the stamp
is when a streaming client could have been sent the token.  One
``eng.step()`` can hold several prefill waves and a decode step; a stamp
taken when ``step()`` returns would be late by up to all of that.

The record it returns (what the metric readers read):

  window_s, t0, t1              the measured window
  stamps                        per submitted request: its token stamps
  step_s                        wall time of every eng.step() in the window
  hist                          the engine's decode/prefill histograms
                                over the window (count, sum, p50)
  counters                      engine counters' deltas over the window
  samples                       --trace 1: per step occupancy, pages in
                                use, live KV tokens (eng.stats())
"""
import gc
import math
import time

import numpy as np

from ..lib import flops_bytes, probe, reference, spans, stats, traffic

# The emitted token's float32-reference logit may sit this many
# row-standard-deviations below the reference row's maximum.  Set from
# what the chip gave for the bf16 deployment this cell states: 0 in 294
# of 304 rows and 0.036 at worst (my chip runs, PR 24), so about three
# times the worst.  A fault in paging, masking, positions or the pool
# reads another token's K/V and lands of the order of 1 down or more; a
# lower precision than the stated one (int8 logits were off by 0.11-0.14
# row-std, CHANGES.md PR 21) flips near-ties that far apart.
EMITTED_GAP_TOL = 0.10
CHECKED_REQUESTS = 12
LATER_ROW = 16      # a decode row just past the first page boundary; the
                    # prefill row and the LAST generated row are compared too
TRACED_S = 3.0      # seconds under the profiler, after the window


class Stamped(list):
    """``Request.tokens`` that notes when each NEW position arrived.
    After a preemption the engine regenerates positions the client
    already has; only a position beyond them is news."""

    def __init__(self, stamps):
        super().__init__()
        self.stamps = stamps

    def append(self, tok):
        super().append(tok)
        if len(self) > len(self.stamps):
            self.stamps.append(time.perf_counter())


class Item:
    """One request as the client sees it."""
    __slots__ = ("idx", "prompt", "max_new", "req", "stamps", "done_t",
                 "rejected")

    def __init__(self, idx, prompt, max_new):
        self.idx, self.prompt, self.max_new = idx, prompt, max_new
        self.req, self.stamps = None, []
        self.done_t = None
        self.rejected = False


class Client:
    """Feeds one engine and steps it; owns every clock reading."""

    def __init__(self, eng, items, depth, sample):
        import jax
        from paddle_tpu.inference import serving
        self._span = jax.profiler.TraceAnnotation
        self._Request, self._Full = serving.Request, serving.ServingQueueFull
        self.eng, self.items = eng, items
        self.cap = eng.slots + depth
        self.sample = sample
        self.next_i = 0
        self.live = {}
        self.step_s = []        # wall seconds of every eng.step()
        self.samples = []       # (occupancy, pages_in_use, kv_tokens) a step

    def _submit(self, item):
        req = self._Request(item.prompt, item.max_new, request_id=item.idx)
        req.tokens = Stamped(item.stamps)
        item.req = req
        try:
            self.eng.submit(req)
        except self._Full:
            item.rejected = True
            return
        self.live[item.idx] = item

    def feed(self):
        with self._span("bench.submit"):
            while (len(self.live) < self.cap
                   and self.next_i < len(self.items)):
                self._submit(self.items[self.next_i])
                self.next_i += 1
        if self.next_i >= len(self.items):
            raise RuntimeError(
                "the backlog ran out of generated requests — raise "
                "max_requests_per_s in the traffic file")

    def step(self):
        t = time.perf_counter()
        with self._span("bench.engine_step"):
            finished = self.eng.step()
        now = time.perf_counter()
        self.step_s.append(now - t)
        for req in finished:
            item = self.live.pop(req.id, None)
            if item is not None:
                item.done_t = now
        for item in self.live.values():
            # a preempted request was scrubbed back to a plain list
            if type(item.req.tokens) is list:
                fresh = Stamped(item.stamps)
                fresh.extend(item.req.tokens)
                item.req.tokens = fresh
        if self.sample:
            st = self.eng.stats()
            self.samples.append((st["slot_occupancy"], st["pages_in_use"],
                                 st["kv_tokens_held"]))

    def pump(self, until):
        """Feed and step until the clock reads ``until``."""
        while time.perf_counter() < until:
            self.feed()
            self.step()


def build_engine(ctx, params, cfg):
    from paddle_tpu.inference.serving import PagedServingEngine
    e = dict(ctx.config["engine"])
    for k in ("seq_buckets", "batch_buckets"):
        e[k] = tuple(e[k])
    return PagedServingEngine((params, cfg), capture_logits=False, **e)


def hist_summary(name):
    from paddle_tpu.observability import metrics
    h = metrics.histogram(name)
    return {"count": h.count, "sum": h.sum, "p50": h.percentile(50)}


def run(ctx):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import gpt
    from paddle_tpu.observability import metrics

    arch, mix = ctx.config, ctx.traffic
    cfg = gpt.GPTConfig(
        **{k: arch[k] for k in ("vocab_size", "hidden_size", "num_layers",
                                "num_heads", "ffn_size", "max_seq_len",
                                "dtype", "param_dtype")})
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
    # every run compiles (and then finds in the cache) the same program
    width = int(mix["prompt_len"]["max"] + mix["output_len"]["max"])
    if width > engine_args["max_len"]:
        raise ValueError(f"a request may need {width} positions, more "
                         f"than max_len {engine_args['max_len']}")

    params = jax.block_until_ready(jax.jit(
        lambda k: gpt.init_params(cfg, k))(jax.random.PRNGKey(ctx.seed)))
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
    # Not judged: with every slot busy a gap is a decode step plus the
    # prefill waves the same eng.step() held, so the 95th percentile sits
    # on the edge between one wave and two and flips with the order of
    # the requests (PERF.md section 2).
    gaps = [g for it in items
            for g in stats.gaps_ending_in(it.stamps, t0, t1)]
    ctx.note(phase="window_closed", window_s=t1 - t0,
             steps=len(client.step_s), submitted=client.next_i,
             token_gap_p50_s=stats.percentile(gaps, 50),
             token_gap_p95_s=stats.percentile(gaps, 95),
             **spans.ring_use(t0, t1))
    record = {
        "t0": t0, "t1": t1, "window_s": t1 - t0,
        "step_s": list(client.step_s),
        "hist": {"decode": hist_summary("serving.decode_step_s"),
                 "prefill": hist_summary("serving.prefill_s")},
        "counters": {k: after[k] - before[k] for k in (
            "decode_steps", "prefill_calls", "requests_completed",
            "preemptions", "prefix_page_hits", "prefix_page_misses",
            "cow_copies", "tokens_generated")},
        "samples": list(client.samples),
        "compiles_in_window": in_window,
        "num_pages": after["num_pages"], "slots": eng.slots,
        "page_size": after["page_size"], "arch": arch,
        "weight_itemsize": params["wte"].dtype.itemsize,
        "kv_itemsize": after["kv_bytes_total"] / (
            after["num_pages"] * after["page_size"]
            * flops_bytes.kv_bytes_per_token(arch, 1)),
    }

    # ---- a few seconds more under the profiler, same traffic
    if ctx.trace:
        with probe.traced(record, ctx.on_chip):
            client.pump(time.perf_counter() + TRACED_S)

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
        toks = list(it.req.tokens)
        rows = sorted({0, min(LATER_ROW, len(toks) - 1), len(toks) - 1})
        history = np.concatenate([it.prompt, np.asarray(toks, np.int32)])
        samples.append((history, [len(it.prompt) - 1 + r for r in rows],
                        [toks[r] for r in rows]))
    paged_calls = metrics.counter("serving.paged_kernel_calls").value
    del eng, client
    gc.collect()
    params_f32 = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), params)
    gaps = reference.emitted_logit_gaps(
        params_f32, cfg.num_heads, cfg.layer_norm_eps, samples, width)
    ctx.note(phase="reference", emitted_logit_gap_max=max(gaps, default=None),
             rows_off_the_argmax=sum(g > 0 for g in gaps),
             rows_checked=len(gaps), requests_checked=len(samples),
             tol=EMITTED_GAP_TOL)

    record.update(
        attempted=len(done) + len(bad), failed=len(bad),
        checks={
            "one_decode_executable": final["decode_compiles"] == 1,
            "no_compile_in_window": (
                in_window["count"] == 0
                and in_window["persistent_cache_requests"] == 0),
            "paged_kernel_engaged": paged_calls >= 1 or not ctx.on_chip,
            "emitted_tokens_near_reference_argmax": (
                len(gaps) > 0 and max(gaps) <= EMITTED_GAP_TOL),
            "no_request_failed": not bad,
        })
    return record
