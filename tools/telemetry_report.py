#!/usr/bin/env python
"""Render a cross-rank telemetry report from a telemetry directory.

Reads the per-rank JSONL event logs (``events_rank<R>.jsonl``) and
published snapshots (``snapshot_rank<R>.json``) that a training run wrote
under ``PADDLE_TELEMETRY_DIR`` (or that ``launch.py --telemetry`` pointed
workers at), merges them (observability/aggregate.py), and prints the
group-wide view: per-rank step counts and step-time mean/p50/p95, XLA
compile counts, collective-wait totals, step skew, straggler flags and
per-rank fault counters.

Usage:
    python tools/telemetry_report.py <telemetry_dir> [--json]
        [--straggler-gap SECONDS] [--step-lag N]

Exit code 0 on success (stragglers flagged in the report do NOT fail the
tool; pass --fail-on-straggler to CI-gate on them).
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


def main(argv=None):
    parser = argparse.ArgumentParser("telemetry_report")
    parser.add_argument("telemetry_dir",
                        help="directory holding events_rank*.jsonl / "
                             "snapshot_rank*.json")
    parser.add_argument("--json", action="store_true",
                        help="emit the merged report as JSON instead of "
                             "text")
    parser.add_argument("--straggler-gap", type=float, default=None,
                        help="collective-wait asymmetry threshold in "
                             "seconds (default: "
                             "PADDLE_TELEMETRY_STRAGGLER or 0.2)")
    parser.add_argument("--step-lag", type=int, default=None,
                        help="steps behind the group frontier before a "
                             "rank is flagged (default: "
                             "PADDLE_TELEMETRY_STEP_LAG or 2)")
    parser.add_argument("--fail-on-straggler", action="store_true",
                        help="exit 2 when any straggler is flagged")
    parser.add_argument("--traces", action="store_true",
                        help="append the distributed-trace summary "
                             "(lifecycles, negative spans, dominant "
                             "phase, flight dumps) from the same dir")
    args = parser.parse_args(argv)

    if not os.path.isdir(args.telemetry_dir):
        print(f"telemetry_report: no such directory: "
              f"{args.telemetry_dir}", file=sys.stderr)
        return 1

    aggregate = _load_aggregate()

    report = aggregate.merge_from_dir(
        args.telemetry_dir, straggler_gap_s=args.straggler_gap,
        step_lag=args.step_lag)
    if args.traces:
        report["traces"] = aggregate.trace_summary(args.telemetry_dir)
    if not report["nranks_seen"] and not (
            args.traces and report["traces"]["trace_events"]):
        # a serving-only dir has no step/snapshot records; with
        # --traces it is still a renderable artifact
        print(f"telemetry_report: no events_rank*.jsonl or "
              f"snapshot_rank*.json under {args.telemetry_dir}",
              file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(report, indent=1, sort_keys=True))
    else:
        print(aggregate.format_report(report))
        if args.traces:
            t = report["traces"]
            print(f"traces: {t['traces']} lifecycles / "
                  f"{t['trace_events']} events, "
                  f"negative spans: {t['negative_spans']}, "
                  f"dominant phase: {t['dominant_phase'] or '-'}, "
                  f"flight dumps: {t['flight_dumps']}"
                  + ("" if t["traces"] else
                     "  (none assembled; trace with PADDLE_TRACE=1)"))
    if args.fail_on_straggler and report["stragglers"]:
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
