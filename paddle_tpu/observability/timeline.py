"""Step-timeline tracing: nested spans, per-step records, and the rolling
JSON-lines event log (Dapper-style host-side tracing for the training
step; the device truth still rides jax.profiler/xprof).

Every :func:`span` is ALWAYS recorded — ``(id, parent, name, t0, t1,
attrs)`` on ``time.perf_counter()`` — into one bounded in-memory ring
(:func:`spans`), and mirrored into ``jax.profiler.TraceAnnotation`` so
that whenever any profiler session is on the program's spans sit on host
lines of the same trace as the device's ``XLA Ops``, on one clock.  That
costs two clock readings, a tuple and a deque append.

Three more sinks, all optional, all fed the same record:

* **chrome trace** — every span mirrors into ``paddle_tpu.profiler``'s
  event buffer (when a profiler session is active), so the existing
  ``export_chrome_tracing`` shows nested forward / backward / allreduce /
  optimizer / checkpoint spans with real step boundaries.
* **JSON-lines event log** — with ``PADDLE_TELEMETRY_DIR`` set (or
  :func:`configure` called), spans, per-step records, compile events and
  scalars append to ``events_rank<R>.jsonl`` in that directory, rotated
  at ``PADDLE_TELEMETRY_MAX_MB`` (default 64).  This is the artifact
  ``tools/telemetry_report.py`` and the launcher's ``--telemetry`` merge
  read, and what the fault supervisor's exit summary points into.
* **metrics registry** — step wall times, compile counts/seconds and
  collective-wait seconds land in ``observability.metrics`` counters and
  histograms, so ``metrics.snapshot()`` carries p50/p95 step times.

:class:`StepTimer` is the weave point: the training loop wraps each step
in ``timer.step()``; framework layers (reducer, optimizer, dataloader,
checkpoint) open :func:`span`\\ s that attribute their time to the active
step's phase breakdown.  XLA compile count+seconds come from the
``framework/jax_compat.py`` compile hook (one event per retrace); live
device memory from ``jax.local_devices()[*].memory_stats()`` where the
backend reports it (TPU yes, CPU no).
"""
from __future__ import annotations

import collections
import itertools
import json
import os
import sys
import threading
import time

from . import metrics

_ENV_DIR = "PADDLE_TELEMETRY_DIR"
_ENV_MAX_MB = "PADDLE_TELEMETRY_MAX_MB"
_ENV_INTERVAL = "PADDLE_TELEMETRY_INTERVAL"


_rank_override = [None]


def set_rank_override(rank):
    """Pin this process's event-log rank (file name + stamped ``rank``).
    The fleet router calls this with its utility rank (1000) so its
    events land in ``events_rank1000.jsonl`` instead of colliding with
    replica 0's file when both share a telemetry dir — two processes
    appending and rotating one JSONL is how lines get torn.  ``None``
    reverts to the env knob."""
    with _writer_lock:
        _rank_override[0] = rank
        if _writer["file"] is not None:
            _writer["file"].close()
        _writer.update(dir=None, path=None, file=None, bytes=0)


def _rank():
    if _rank_override[0] is not None:
        return int(_rank_override[0])
    try:
        return int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    except ValueError:
        return 0


# --------------------------------------------------------------------------
# JSON-lines event writer (rolling)
# --------------------------------------------------------------------------

_writer_lock = threading.Lock()
_configured_dir = [None]        # programmatic override of the env knob
_writer = {"dir": None, "path": None, "file": None, "bytes": 0}


def telemetry_dir():
    """The active telemetry directory (``configure()`` override first,
    then ``PADDLE_TELEMETRY_DIR``), or None when telemetry is off."""
    return _configured_dir[0] or os.environ.get(_ENV_DIR) or None


def configure(directory):
    """Point the event log at ``directory`` (None reverts to the env
    knob).  Closes any open log file so the next emit reopens there."""
    with _writer_lock:
        _configured_dir[0] = directory
        if _writer["file"] is not None:
            _writer["file"].close()
        _writer.update(dir=None, path=None, file=None, bytes=0)


def _max_bytes():
    try:
        return int(float(os.environ.get(_ENV_MAX_MB, "64")) * (1 << 20))
    except ValueError:
        return 64 << 20


def _open_writer(d):
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, f"events_rank{_rank()}.jsonl")
    f = open(path, "a", encoding="utf-8")
    _writer.update(dir=d, path=path, file=f,
                   bytes=os.path.getsize(path))


def emit(record):
    """Append one structured event to the rolling JSONL log (no-op when
    telemetry is off).  ``time`` and ``rank`` are stamped here."""
    d = telemetry_dir()
    if not d:
        return False
    rec = {"time": round(time.time(), 6), "rank": _rank()}
    rec.update(record)
    line = json.dumps(rec, sort_keys=True) + "\n"
    with _writer_lock:
        if _writer["dir"] != d or _writer["file"] is None:
            if _writer["file"] is not None:
                _writer["file"].close()
            _open_writer(d)
        f = _writer["file"]
        f.write(line)
        f.flush()
        _writer["bytes"] += len(line)
        if _writer["bytes"] > _max_bytes():
            # roll: current log becomes .1 (one generation kept), fresh file
            f.close()
            os.replace(_writer["path"], _writer["path"] + ".1")
            _open_writer(d)
    return True


# --------------------------------------------------------------------------
# spans
# --------------------------------------------------------------------------

_tls = threading.local()

# The in-memory ring every span lands in, oldest first.  Sized from the
# heaviest traffic the repo measures.  A PagedServingEngine step closes
# 9 spans and a prefill wave 5 more and one a request, so the GPT
# backlog cell (32 slots; 52 steps and 19 waves a second on a v5e chip)
# closes ~590 a second: 29,986 start in its 51 s window, 26,351 in the
# kanana2 cell's, 13,870 in the phi4flash cell's, 11,703 in the ouro
# cell's (PERF.md section 5, ``spans_in_window``).  The benchmark's
# readers run after the traced tail, so the ring has to hold the window
# and 3 s more: at the GPT cell's 11.2 spans a step it wraps past
# 65,536 / 54 s / 11.2 = 108 steps a second, twice what that cell runs
# today — and the readers raise when it has (benchmark/lib/spans.py).
RING_SPANS = 1 << 16
_ring = collections.deque(maxlen=RING_SPANS)
_ids = itertools.count(1)
_dropped = [0]


def spans():
    """A copy of the ring, oldest first: ``(id, parent, name, t0, t1,
    attrs)`` per closed span.  ``parent`` is the id of the span that was
    open on the same thread when this one began (None at the top),
    ``t0``/``t1`` are ``time.perf_counter()`` readings, ``attrs`` the
    span's keyword attributes (None when it had none).  A span enters
    the ring when it CLOSES, so a child precedes its parent."""
    return list(_ring)


def spans_dropped():
    """How many spans the ring has evicted since the process started
    (or :func:`reset_spans`)."""
    return _dropped[0]


def reset_spans():
    """Empty the ring and zero the eviction count."""
    _ring.clear()
    _dropped[0] = 0


def _record(rec):
    if len(_ring) == _ring.maxlen:
        _dropped[0] += 1
    _ring.append(rec)


def _span_stack():
    st = getattr(_tls, "spans", None)
    if st is None:
        st = _tls.spans = []
    return st


def _profiler_mod():
    return sys.modules.get("paddle_tpu.profiler")


def _jax_profiler():
    """``jax.profiler`` once somebody has imported jax, else None: this
    module must stay importable (and usable) before jax, and with no jax
    in the process no profiler session can be on."""
    jax = sys.modules.get("jax")
    return getattr(jax, "profiler", None)


class _Span:
    __slots__ = ("name", "attrs", "id", "parent", "t0", "t1", "_ann")

    def __init__(self, name, attrs):
        self.name = name
        self.attrs = attrs or None

    @property
    def dur(self):
        """Seconds from enter to exit, once the span has closed."""
        return self.t1 - self.t0

    def __enter__(self):
        st = _span_stack()
        self.parent = st[-1].id if st else None
        self.id = next(_ids)
        st.append(self)
        jp = _jax_profiler()
        if jp is not None:
            self._ann = jp.TraceAnnotation(self.name, **(self.attrs or {}))
            self._ann.__enter__()
        else:
            self._ann = None
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*a)
        st = _span_stack()
        depth = len(st) - 1
        if st and st[-1] is self:
            st.pop()
        _record((self.id, self.parent, self.name, self.t0, self.t1,
                 self.attrs))
        _feed_sinks(self, depth)
        return False


def _feed_sinks(sp, depth):
    """The optional sinks, each given the span the ring just took."""
    dur = sp.t1 - sp.t0
    prof = _profiler_mod()
    if prof is not None and prof.is_enabled():
        prof.record_op(sp.name, dur, t_start=sp.t0)
    ctx = current_step()
    if ctx is not None:
        ctx._add_phase(sp.name, dur)
    if telemetry_dir():
        rec = {"event": "span", "name": sp.name, "depth": depth,
               "id": sp.id, "parent": sp.parent, "t0": round(sp.t0, 6),
               "dur_s": round(dur, 6)}
        if sp.attrs:
            rec.update(sp.attrs)
        emit(rec)


def span(name, **attrs):
    """Nested timing span: always lands in the ring (:func:`spans`) and
    on the profiler's trace when a session is on; ``with span(...) as
    sp`` gives ``sp.id`` (what a later span names to say which span
    caused it), ``sp.t0``, ``sp.t1`` and ``sp.dur`` so a histogram fed
    by the span observes the span's own clock readings.

    Attributes known at entry are given at entry: the profiler's
    annotation is built from the attributes the span has THEN, so only
    those are on the trace's host line.  One set inside the block
    (``sp.attrs["key"] = value``; the span must have been given at
    least one attribute, or ``sp.attrs`` is None) reaches the ring and
    the event log alone — ``hits`` on ``serving.pager.admit`` and
    ``device_s`` on a readback span are such, by design: a trace has
    the device's own line."""
    return _Span(name, attrs)


# --------------------------------------------------------------------------
# compile hook + collective wait (feed both the registry and step records)
# --------------------------------------------------------------------------

_compile_hook_done = [False]


def install_compile_hook():
    """Route XLA compile events (one per retrace, via the jax.monitoring
    listener in framework/jax_compat.py) into the registry, the chrome
    trace and the event log.  Idempotent."""
    if _compile_hook_done[0]:
        return False
    _compile_hook_done[0] = True
    from ..framework import jax_compat
    return jax_compat.install_compile_hook(_on_compile)


def _on_compile(kind, seconds):
    metrics.counter("compile.count").inc()
    metrics.counter("compile.seconds").inc(seconds)
    metrics.histogram("compile.duration_s").observe(seconds)
    # a span after the fact (the hook hears of a compile when it ends):
    # its parent is the innermost span open on this thread, so a compile
    # inside a window names the step that caused it
    t1 = time.perf_counter()
    st = _span_stack()
    _record((next(_ids), st[-1].id if st else None, "xla_compile",
             t1 - seconds, t1, {"kind": kind}))
    prof = _profiler_mod()
    if prof is not None and prof.is_enabled():
        prof.record_op("xla_compile", seconds, t_start=t1 - seconds)
    if telemetry_dir():
        emit({"event": "compile", "kind": kind,
              "dur_s": round(seconds, 6)})


def record_collective_wait(seconds, op=None):
    """Called by the eager cross-process collective transport with the
    time this rank spent blocked at the rendezvous (NOT the time it
    spent producing its contribution).  A straggler therefore shows the
    LOWEST wait — everyone else was waiting on it — which is exactly
    what the cross-rank merge's straggler detector keys on."""
    metrics.counter("collective.wait_s").inc(seconds)
    metrics.counter("collective.waits").inc()
    metrics.histogram("collective.wait_duration_s",
                      op=op or "unknown").observe(seconds)


def device_memory():
    """Per-device live memory, where the backend reports it
    ({device: {bytes_in_use, peak_bytes_in_use, ...}}); None on backends
    without memory_stats (CPU)."""
    try:
        import jax
        out = {}
        for d in jax.local_devices():
            st = d.memory_stats()
            if st:
                out[str(d.id)] = {
                    k: st[k] for k in ("bytes_in_use", "peak_bytes_in_use",
                                       "bytes_limit") if k in st}
        return out or None
    except Exception:                                      # noqa: BLE001
        return None


# --------------------------------------------------------------------------
# StepTimer
# --------------------------------------------------------------------------

_active_timers = []          # innermost-last; step() attaches to [-1]


def current_timer():
    return _active_timers[-1] if _active_timers else None


def current_step():
    t = current_timer()
    return t._current if t is not None else None


class _StepCtx:
    def __init__(self, timer, tokens):
        self.timer = timer
        self.tokens = tokens
        self.phases = {}
        self._lock = threading.Lock()

    def _add_phase(self, name, dur):
        with self._lock:
            self.phases[name] = self.phases.get(name, 0.0) + dur

    def __enter__(self):
        self.timer._current = self
        self._t0 = time.perf_counter()
        self._compiles0 = metrics.counter("compile.count").value
        self._compile_s0 = metrics.counter("compile.seconds").value
        self._wait0 = metrics.counter("collective.wait_s").value
        return self

    def __exit__(self, exc_type, *a):
        dur = time.perf_counter() - self._t0
        timer = self.timer
        timer._current = None
        if exc_type is not None:
            return False
        timer._step_idx += 1
        timer.step_times.append(dur)
        timer._hist.observe(dur)
        metrics.counter("step.count").inc()
        metrics.gauge("step.last_wall_s").set(round(dur, 6))
        tokens = self.tokens if self.tokens is not None \
            else timer.tokens_per_step
        tps = (tokens / dur) if tokens and dur > 0 else None
        if tps is not None:
            metrics.gauge("step.tokens_per_s").set(round(tps, 3))
        prof = _profiler_mod()
        if prof is not None and prof.is_enabled():
            prof.record_op("step", dur, t_start=self._t0)
        record = {
            "event": "step", "name": timer.name, "step": timer._step_idx,
            "wall_s": round(dur, 6),
            "tokens": tokens, "tokens_per_s":
                round(tps, 3) if tps is not None else None,
            "compiles":
                metrics.counter("compile.count").value - self._compiles0,
            "compile_s": round(
                metrics.counter("compile.seconds").value
                - self._compile_s0, 6),
            "collective_wait_s": round(
                metrics.counter("collective.wait_s").value - self._wait0, 6),
            "phases": {k: round(v, 6) for k, v in self.phases.items()},
        }
        # device memory is only worth its per-step host query when the
        # record actually lands somewhere (the JSONL log) — a StepTimer
        # wrapped around a microbenchmark hot loop with telemetry off
        # must not pay jax.local_devices()+memory_stats() every step
        if telemetry_dir():
            mem = device_memory()
            if mem:
                record["device_mem"] = mem
        timer.last_record = record
        emit(record)
        timer._maybe_publish()
        return False


class StepTimer:
    """Per-step wall-clock timeline for a training loop.

    >>> with StepTimer(tokens_per_step=batch * seq) as timer:
    ...     for batch in loader:
    ...         with timer.step():
    ...             with timer.span("forward"):
    ...                 loss = net(x)
    ...             with timer.span("backward"):
    ...                 loss.backward()
    ...             opt.step()          # spans itself via the framework

    Each step emits one structured record (wall time, tokens/s, XLA
    compile count+seconds, collective wait, phase breakdown, device
    memory) into the JSONL event log, observes the ``step.wall_s``
    histogram (p50/p95 in ``metrics.snapshot()``), and — every
    ``PADDLE_TELEMETRY_INTERVAL`` seconds (default 10) in a
    multi-process run — publishes this rank's snapshot for the
    cross-rank aggregator."""

    def __init__(self, name="train", tokens_per_step=None,
                 publish_interval=None, start_step=0):
        self.name = name
        self.tokens_per_step = tokens_per_step
        self.step_times = []
        self.last_record = None
        # a resumed worker passes its restored step so records carry TRUE
        # training-step numbers (the offline merge dedupes replays on
        # them; an incarnation-local 1..k numbering would double-count)
        self._step_idx = int(start_step)
        self._current = None
        self._hist = metrics.histogram("step.wall_s")
        if publish_interval is None:
            try:
                publish_interval = float(
                    os.environ.get(_ENV_INTERVAL, "10"))
            except ValueError:
                publish_interval = 10.0
        self.publish_interval = publish_interval
        self._last_publish = time.monotonic()
        install_compile_hook()

    # ------------------------------------------------------------ session
    def __enter__(self):
        _active_timers.append(self)
        return self

    def __exit__(self, *a):
        if self in _active_timers:
            _active_timers.remove(self)
        return False

    def step(self, tokens=None):
        """Context manager timing ONE training step."""
        return _StepCtx(self, tokens)

    def span(self, name, **attrs):
        return span(name, **attrs)

    @property
    def steps(self):
        return self._step_idx

    # ------------------------------------------------------------- stats
    def percentiles(self):
        """{"mean","p50","p95"} seconds over this timer's own steps."""
        if not self.step_times:
            return {"mean": None, "p50": None, "p95": None}
        data = sorted(self.step_times)

        def pct(p):
            rank = max(int(-(-p / 100.0 * len(data) // 1)), 1)
            return data[min(rank, len(data)) - 1]

        return {"mean": sum(data) / len(data), "p50": pct(50),
                "p95": pct(95)}

    def throughput(self, window=20):
        """(steps/s, tokens/s or None) over the last ``window`` steps."""
        recent = self.step_times[-window:]
        if not recent:
            return 0.0, None
        dt = sum(recent)
        sps = len(recent) / dt if dt > 0 else 0.0
        tps = sps * self.tokens_per_step if self.tokens_per_step else None
        return sps, tps

    # ----------------------------------------------------------- publish
    def _maybe_publish(self):
        if self.publish_interval <= 0:
            return
        now = time.monotonic()
        if now - self._last_publish < self.publish_interval:
            return
        self._last_publish = now
        try:
            from . import aggregate
            aggregate.publish(step=self._step_idx)
        except Exception:                                  # noqa: BLE001
            pass                # telemetry must never kill a training loop
