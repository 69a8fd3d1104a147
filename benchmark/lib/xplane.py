"""From a profiler trace to the device's busy and idle time.

``load(dir)`` reads the newest ``*.xplane.pb`` under a
``jax.profiler`` trace directory with ``jax.profiler.ProfileData`` into
plain data — ``[{"name", "lines": [{"name", "events": [[name, start_ns,
duration_ns], ...]}]}]`` — and ``reduce(planes)`` works on that alone, so
it is checked on a small recorded trace (``xplane_fixture.json``) with no
profiler at hand, and every PR reduces a trace the same way.

What counts:

* a DEVICE OP is an event on the ``XLA Ops`` line of a ``/device:TPU:n``
  plane (what ran on the core; ``XLA Modules`` and ``Steps`` only wrap
  them).  In a CPU rehearsal (``on_chip`` false), and only there, events
  of the host plane's ``tf_XLAPjRtCpuClient`` lines whose name is no
  bookkeeping marker stand in, so the control flow is walked.  A chip
  run whose trace holds no ``XLA Ops`` line reduces to None — host-thread
  time is never reported as the device's.
* BUSY is the union of the device ops' intervals, clipped to the window
  and averaged over the device planes.
* the WINDOW is the host span named ``bench.trace_window`` where the
  trace holds it (host and device lines share one clock), else first
  device op to last.
* an IDLE GAP is a maximal interval of the window in which no device op
  ran.  It is attributed to the ``bench.*`` host span that covers most of
  it (what the host was doing), else to ``(no host span)``.
"""
import bisect
import glob
import os

WINDOW_SPAN = "bench.trace_window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
CPU_LINE = "tf_XLAPjRtCpuClient"
_CPU_NOISE = ("ThreadpoolListener", "SlinkyThreadPool", "end: ")


def load(trace_dir):
    """Plain data of the newest trace under ``trace_dir`` (None if there
    is none).  Keeps device planes whole and, of the host planes, the
    ``bench.*`` spans and the CPU stand-in ops."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return None
    planes = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        lines = []
        for line in plane.lines:
            cpu_ops = line.name.startswith(CPU_LINE)
            events = [[label(e.name), float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or e.name.startswith(SPAN_PREFIX)
                      or (cpu_ops and not e.name.startswith(_CPU_NOISE))]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def label(name):
    """A device op's event name is its whole HLO instruction, operands and
    layouts included; keep ``name shape opcode`` and, of a custom call,
    its target (``tpu_custom_call`` is a Pallas kernel)."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    depth, end = 0, len(rest)
    for i, ch in enumerate(rest):       # the type ends at a depth-0 space
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == " " and depth == 0:
            end = i
            break
    shape = rest[:end].split("{")[0]
    if shape.startswith("("):           # a tuple: its first member stands
        shape += ", ...)"
    opcode = rest[end + 1:].split("(")[0]
    target = rest.partition('custom_call_target="')[2].split('"')[0]
    return " ".join(filter(None, [head.lstrip("%"), shape, opcode,
                                  target and f"[{target}]"]))


def _union(intervals):
    """Sorted, merged ``[start, end]`` intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _device_op_lines(planes, on_chip):
    """One list of op events per device (the stand-in: one list in all)."""
    if on_chip:
        per_device = [[ev for ln in p["lines"] if ln["name"] == OPS_LINE
                       for ev in ln["events"]]
                      for p in planes if p["name"].startswith(DEVICE_PLANE)]
        return [evs for evs in per_device if evs]
    stand_in = [ev for p in planes for ln in p["lines"]
                if ln["name"].startswith(CPU_LINE) for ev in ln["events"]
                if not ev[0].startswith(SPAN_PREFIX)]
    return [stand_in] if stand_in else []


def _host_spans(planes):
    return [ev for p in planes if not p["name"].startswith(DEVICE_PLANE)
            for ln in p["lines"] for ev in ln["events"]
            if ev[0].startswith(SPAN_PREFIX)]


def reduce(planes, on_chip, top=10):
    """``{"busy_s", "window_s", "device_ops", "idle_gaps"}`` of a loaded
    trace, or None where no device op ran (the harness then reports no
    device time, and a traced run without one is refused).  ``on_chip``
    says whether the run is on a TPU (device planes only) or a CPU
    rehearsal (the stand-in only)."""
    devices = _device_op_lines(planes or [], on_chip)
    if not devices:
        return None
    spans = sorted(_host_spans(planes), key=lambda ev: ev[1])
    window = [[s, s + d] for n, s, d in spans if n == WINDOW_SPAN]
    spans = [ev for ev in spans if ev[0] != WINDOW_SPAN]
    starts = [s for _, s, _ in spans]
    longest = max((d for _, _, d in spans), default=0.0)
    if window:
        lo, hi = min(w[0] for w in window), max(w[1] for w in window)
    else:
        lo = min(s for evs in devices for _, s, _ in evs)
        hi = max(s + d for evs in devices for _, s, d in evs)
    if hi <= lo:
        return None

    busy_ns, op_ns, gap_ns = 0.0, {}, {}
    for evs in devices:
        busy = _clip(_union([s, s + d] for _, s, d in evs), lo, hi)
        busy_ns += sum(e - s for s, e in busy)
        for name, ns in _self_times(evs, lo, hi):
            op_ns[name] = op_ns.get(name, 0.0) + ns
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                near = spans[bisect.bisect_left(starts, gs - longest):
                             bisect.bisect_left(starts, ge)]
                who = _covering_span(near, gs, ge)
                gap_ns[who] = gap_ns.get(who, 0.0) + (ge - gs)
    n = len(devices)

    def ranked(table):
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:top]
        return [[name, ns / n / 1e9] for name, ns in rows]

    return {"busy_s": busy_ns / n / 1e9, "window_s": (hi - lo) / 1e9,
            "device_ops": ranked(op_ns), "idle_gaps": ranked(gap_ns)}


def _self_times(evs, lo, hi):
    """``(name, ns)`` of every op's OWN time inside [lo, hi]: an op that
    wraps others on the same line (a ``while`` around a layer scan's
    body) is charged only what its children leave."""
    out, stack = [], []             # stack rows: [end, name, self_ns]
    for name, s, d in sorted(evs, key=lambda ev: (ev[1], -ev[2])):
        s, e = max(s, lo), min(s + d, hi)
        if e <= s:
            continue
        while stack and stack[-1][0] <= s:
            out.append(tuple(stack.pop()[1:]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    out.extend(tuple(row[1:]) for row in stack)
    return out


def _covering_span(spans, gs, ge):
    """The ``bench.*`` span that covers most of [gs, ge]."""
    best, best_cover = "(no host span)", 0.0
    for name, s, d in spans:
        cover = min(s + d, ge) - max(s, gs)
        if cover > best_cover:
            best, best_cover = name, cover
    return best
