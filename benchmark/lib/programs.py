"""The programs the engine read back inside the measured window, one
record each, from the attributes of the spans the program already makes.

``PagedServingEngine`` runs a step ahead of its readbacks, so a program —
a prefill wave or a decode step — has two spans of its name in the ring of
``paddle_tpu.observability.timeline``: one where it is dispatched and one
where its tokens are read back and committed.  The second names the first
(``dispatch_span``) and carries ``device_s``, the time the device had the
program at the head of its queue: the very number the engine's
``serving.prefill_s`` / ``serving.decode_step_s`` histograms observe.  A
wave's spans carry besides what it was given — ``requests``, ``tokens``
(prompt tokens), ``hit_tokens`` (positions the prefix cache supplied) —
and what it paid for: ``rows`` = ``batch`` x ``seq``, what its program
runs whatever is in them.

``window(run)`` keeps the readback-side records whose END lies in
``[t0, t1)``: the same programs the histograms hold, since the harness
resets them as the window opens.  Same clock as ``lib/spans.py``
(``run["t0"]`` / ``run["t1"]`` are ``time.perf_counter()`` readings, as
the ring's are), same refusal of a ring that wrapped inside the window.

A program whose spans lack the attributes (the parent of the PR that
added them) gives None and every reader built on this reports nothing.
"""

WAVE = "serving.prefill_wave"
DECODE = "serving.decode"


def records(spans, dropped, t0, t1):
    """``[(name, attrs)]`` of the spans called ``WAVE`` or ``DECODE`` that
    carry ``device_s`` and closed in ``[t0, t1)``, oldest first, or None
    where there is none.  ``spans`` is the ring, oldest first;
    ``dropped`` how many it has evicted."""
    if not spans:
        return None
    if dropped and spans[0][4] > t0:
        # the ring is in closing order: everything evicted closed before
        # its oldest survivor did, and that was inside the window
        raise RuntimeError(
            f"the span ring wrapped inside the window: it evicted "
            f"{dropped} spans and its oldest closed {spans[0][4] - t0:.3f} s "
            f"after the window opened")
    found = [(name, attrs) for _, _, name, _, end, attrs in spans
             if name in (WAVE, DECODE) and attrs and "device_s" in attrs
             and t0 <= end < t1]
    return found or None


def window(run):
    """``records`` of the program's ring over the run's window, made once
    and kept on the record (``run["programs"]``)."""
    if "programs" not in run:
        from paddle_tpu.observability import timeline
        ring = getattr(timeline, "spans", None)
        run["programs"] = None if ring is None else records(
            ring(), timeline.spans_dropped(), run["t0"], run["t1"])
    return run["programs"]


def total(run, key, name=None):
    """The sum of attribute ``key`` over the window's records (those
    called ``name`` only, if given); None for a program without the
    attributes, or a window without such a record."""
    found = window(run)
    if found is None:
        return None
    values = [attrs[key] for n, attrs in found
              if (name is None or n == name) and key in attrs]
    return sum(values) if values else None


def by_bucket(run):
    """``{"<batch>x<seq>": {"waves", "requests", "tokens", "rows",
    "device_s"}}`` over the window's wave records — the table
    ``stats()["prefill_by_bucket"]`` keeps for the engine's whole life,
    here for the window; ``{}`` where there is none.  For a note line
    and for ``PERF.md``, not a metric."""
    table = {}
    for name, attrs in window(run) or ():
        if name != WAVE or "rows" not in attrs:
            continue
        row = table.setdefault(
            f"{attrs['batch']}x{attrs['seq']}",
            dict(waves=0, requests=0, tokens=0, rows=0, device_s=0.0))
        row["waves"] += 1
        for key in ("requests", "tokens", "rows", "device_s"):
            row[key] += attrs[key]
    return table
