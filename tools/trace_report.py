#!/usr/bin/env python
"""Render assembled request traces from a telemetry directory.

Reads the ``trace`` events that a traced fleet run (``PADDLE_TRACE=1``)
wrote into the per-rank JSONL logs under ``PADDLE_TELEMETRY_DIR``,
stitches them into causally-ordered request lifecycles
(observability/aggregate.py: clock-skew-corrected across router and
replica processes), and prints the per-phase latency attribution
rollup — p50/p95/p99 in queue / prefill / parked / inject / decode /
ack, per priority class, with the owning role per phase.

Usage:
    python tools/trace_report.py <telemetry_dir> [--json]
        [--lifecycles N] [--chrome OUT.json] [--fail-on-negative]

``--chrome`` exports the lifecycles as a chrome-trace file (load in
chrome://tracing or Perfetto): one process row per role, one thread
row per request, complete events per phase and instants per hop.

Exit code 0 on success; pass --fail-on-negative to CI-gate on
negative spans (exit 2) — a negative span means clock correction
failed to keep causality, which the tier-1 bar forbids.
"""
import argparse
import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_aggregate():
    """Load paddle_tpu/observability standalone — WITHOUT importing the
    paddle_tpu package (whose __init__ imports jax and the whole
    framework).  The observability modules are stdlib-only at import
    time by design, so this tool stays usable on a box where jax cannot
    start or another process holds the chip — the exact postmortem
    scenario it exists for."""
    pkg_dir = os.path.join(REPO, "paddle_tpu", "observability")
    name = "_ptpu_observability"
    if name in sys.modules:
        return sys.modules[name].aggregate
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(pkg_dir, "__init__.py"),
        submodule_search_locations=[pkg_dir])
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod.aggregate


# the same boundary pairs _trace_phases telescopes over; spelled out
# here because chrome complete-events need the START of each span, not
# just its duration
_PHASE_BOUNDS = (
    ("queue", "admit", "dispatch"),
    ("prefill", "dispatch", "park"),
    ("parked", "park", "ship"),
    ("inject", "ship", "inject"),
    ("decode", "inject", "completion"),
    ("service", "dispatch", "completion"),
    ("ack", "completion", "ack"),
)


def _boundaries(lc):
    t = {}
    for ev in lc["events"]:
        name = ev.get("name")
        if name not in t:
            t[name] = ev.get("t_corrected", ev.get("t"))
    return t


def chrome_trace(lifecycles, phase_roles):
    """Lifecycles -> chrome-trace ``traceEvents`` list.  Rows: one
    process per role (router / prefill / decode / ...), one thread per
    request; each phase a complete ("X") event on the owning role's
    row, each hop an instant ("i") on the row of the process that
    emitted it."""
    out = []
    pids, tids = {}, {}
    t0 = min((lc["t0"] for lc in lifecycles), default=0.0)

    def _pid(role):
        role = role or "?"
        if role not in pids:
            pids[role] = len(pids) + 1
            out.append({"name": "process_name", "ph": "M",
                        "pid": pids[role], "tid": 0,
                        "args": {"name": role}})
        return pids[role]

    def _tid(pid, rid):
        key = (pid, rid)
        if key not in tids:
            tids[key] = len(tids) + 1
            out.append({"name": "thread_name", "ph": "M", "pid": pid,
                        "tid": tids[key], "args": {"name": rid}})
        return tids[key]

    for lc in lifecycles:
        rid = lc.get("request_id") or lc["trace_id"]
        bounds = _boundaries(lc)
        for phase, dur in (lc.get("phases") or {}).items():
            start = next((bounds[a] for p, a, b in _PHASE_BOUNDS
                          if p == phase and a in bounds), None)
            if start is None:
                continue
            pid = _pid(phase_roles.get(phase, "?"))
            out.append({"name": phase, "ph": "X", "cat": "phase",
                        "ts": round((start - t0) * 1e6, 1),
                        "dur": round(max(dur, 0.0) * 1e6, 1),
                        "pid": pid, "tid": _tid(pid, rid),
                        "args": {"trace_id": lc["trace_id"],
                                 "priority": lc.get("priority")}})
        for ev in lc["events"]:
            pid = _pid(ev.get("role"))
            t = ev.get("t_corrected", ev.get("t"))
            out.append({"name": ev["name"], "ph": "i", "cat": "hop",
                        "ts": round((t - t0) * 1e6, 1),
                        "pid": pid, "tid": _tid(pid, rid), "s": "t",
                        "args": {k: v for k, v in ev.items()
                                 if k not in ("event", "name", "t",
                                              "t_corrected")}})
    return out


def _lifecycle_lines(lifecycles, limit):
    """The ``limit`` slowest lifecycles, one line each."""
    lines = []
    for lc in sorted(lifecycles, key=lambda x: -x["e2e_s"])[:limit]:
        phases = " ".join(f"{p}={v * 1e3:.1f}ms"
                          for p, v in lc["phases"].items())
        lines.append(
            f"  {lc.get('request_id') or lc['trace_id']:<20} "
            f"e2e={lc['e2e_s'] * 1e3:8.1f}ms  {phases}")
        lines.append(f"    hops: {' -> '.join(lc['hops'])}")
    return lines


def main(argv=None):
    parser = argparse.ArgumentParser("trace_report")
    parser.add_argument("telemetry_dir",
                        help="directory holding events_rank*.jsonl "
                             "written by a PADDLE_TRACE=1 run")
    parser.add_argument("--json", action="store_true",
                        help="emit the attribution rollup as JSON "
                             "instead of text")
    parser.add_argument("--lifecycles", type=int, default=0,
                        metavar="N",
                        help="also print the N slowest lifecycles "
                             "with their hop chains")
    parser.add_argument("--chrome", metavar="OUT.json",
                        help="write a chrome-trace export of every "
                             "lifecycle to OUT.json")
    parser.add_argument("--fail-on-negative", action="store_true",
                        help="exit 2 when any negative span survives "
                             "clock correction")
    args = parser.parse_args(argv)

    if not os.path.isdir(args.telemetry_dir):
        print(f"trace_report: no such directory: {args.telemetry_dir}",
              file=sys.stderr)
        return 1

    aggregate = _load_aggregate()
    events = aggregate.trace_events_from_dir(args.telemetry_dir)
    lifecycles = aggregate.assemble_traces(events=events)
    if not lifecycles:
        if events:
            print(f"trace_report: {len(events)} trace events under "
                  f"{args.telemetry_dir} but none carry a trace_id — "
                  f"nothing to assemble (ids are minted at submit "
                  f"time, so PADDLE_TRACE=1 must be set when requests "
                  f"enter, not only when they finish)", file=sys.stderr)
        else:
            print(f"trace_report: no trace events under "
                  f"{args.telemetry_dir} (was the run PADDLE_TRACE=1?)",
                  file=sys.stderr)
        return 1
    attr = aggregate.trace_attribution(lifecycles)

    if args.chrome:
        events = chrome_trace(lifecycles, aggregate.PHASE_ROLES)
        with open(args.chrome, "w", encoding="utf-8") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)
        print(f"# trace_report: wrote {len(events)} chrome-trace "
              f"events -> {args.chrome}", file=sys.stderr)

    if args.json:
        print(json.dumps(attr, indent=1, sort_keys=True))
    else:
        print(aggregate.format_trace_report(attr))
        if args.lifecycles > 0:
            print("\n".join(_lifecycle_lines(lifecycles,
                                             args.lifecycles)))

    if args.fail_on_negative and attr.get("negative_spans"):
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
