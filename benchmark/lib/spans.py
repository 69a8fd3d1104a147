"""The program's own spans, reduced to what each layer spent per engine
step inside the measured window.

``paddle_tpu.observability.timeline`` records every span the program
closes — ``(id, parent, name, t0, t1, attrs)`` on ``time.perf_counter()``,
the clock ``run["t0"]`` and ``run["t1"]`` were read from — into a bounded
ring, profiler or not.  ``window(run)`` keeps the ``serving.step`` trees
that START in ``[t0, t1)`` and sums, per span name, the time in such
spans and their SELF time: a span's duration minus the part of it that
its child spans cover.

The four name sets below partition the tree that one
``PagedServingEngine.step()`` makes, so by construction
scheduler + pager + dispatch + readback == the summed ``serving.step``
durations; a span of any other name inside a step (a compile, a chunked
prefill) breaks that identity, which is the point.

A program without the ring (the parent of the PR that added it) gives
None and every reader built on this reports nothing.  A ring that
wrapped past the window's start raises: a number over part of a window
is not reported under the name of the whole.
"""

ROOT = "serving.step"
# self time of the spans that hold the engine's own bookkeeping, plus the
# per-slot commit loop (a leaf, so its self time is all of it)
SCHEDULER = ("serving.step", "serving.admit", "serving.prefill_wave",
             "serving.decode", "serving.decode.commit")
PAGER = ("serving.pager.admit", "serving.pager.ensure")
# host time before the device has the work: operands to the device and
# the executable's lookup, then the enqueue
DISPATCH = ("serving.prefill_operands", "serving.prefill_wave.dispatch",
            "serving.decode_operands", "serving.decode.dispatch")
# the host blocked on the device for the sampled tokens
READBACK = ("serving.prefill_wave.readback", "serving.decode.readback")


def reduce(spans, dropped, t0, t1):
    """``{"steps", "step_s", "total_s": {name: s}, "self_s": {name: s}}``
    over the ``serving.step`` trees starting in ``[t0, t1)``, or None
    where there is none.  ``spans`` is the ring, oldest first (a child
    closes, and so comes, before its parent); ``dropped`` how many it has
    evicted."""
    if not spans:
        return None
    if dropped and spans[0][4] > t0:
        # the ring is in closing order, so everything evicted closed
        # before its oldest survivor did: after the window opened, that
        # may have been part of a step the window counts
        raise RuntimeError(
            f"the span ring wrapped inside the window: it evicted "
            f"{dropped} spans and its oldest closed {spans[0][4] - t0:.3f} s "
            f"after the window opened")
    covered, inside = {}, set()
    total, own = {}, {}
    for sid, parent, name, s, e, _ in spans:
        if parent is not None:
            covered[parent] = covered.get(parent, 0.0) + (e - s)
    steps, step_s = 0, 0.0
    for sid, parent, name, s, e, _ in reversed(spans):
        if name == ROOT and t0 <= s < t1:
            steps += 1
            step_s += e - s
        elif parent not in inside:
            continue
        inside.add(sid)
        total[name] = total.get(name, 0.0) + (e - s)
        own[name] = own.get(name, 0.0) + (e - s) - covered.get(sid, 0.0)
    if not steps:
        return None
    return {"steps": steps, "step_s": step_s, "total_s": total,
            "self_s": own}


def ring_use(t0, t1):
    """``{"spans_in_window", "ring_spans"}``: how many of the spans the
    ring holds START in ``[t0, t1)``, beside the ring's depth — how far a
    window is from wrapping it (``reduce`` raises when it does).  A note,
    not a metric; ``{}`` for a program without the ring."""
    from paddle_tpu.observability import timeline
    ring = getattr(timeline, "spans", None)
    if ring is None:
        return {}
    return {"spans_in_window": sum(t0 <= s[3] < t1 for s in ring()),
            "ring_spans": timeline.RING_SPANS}


def window(run):
    """``reduce`` of the program's ring over the run's window, made once
    and kept on the record (``run["program_spans"]``)."""
    if "program_spans" not in run:
        from paddle_tpu.observability import timeline
        ring = getattr(timeline, "spans", None)
        run["program_spans"] = None if ring is None else reduce(
            ring(), timeline.spans_dropped(), run["t0"], run["t1"])
    return run["program_spans"]


def ms_per_step(run, names, table="total_s"):
    """Milliseconds per engine step in the spans called ``names``."""
    w = window(run)
    if w is None:
        return None
    return 1e3 * sum(w[table].get(n, 0.0) for n in names) / w["steps"]


def share_of_step(run, names):
    """Percent of the time in ``serving.step`` spent in ``names``."""
    w = window(run)
    if w is None or w["step_s"] <= 0:
        return None
    return 100.0 * sum(w["total_s"].get(n, 0.0) for n in names) / w["step_s"]
