#!/usr/bin/env python3
"""Run ONE cell of the benchmark once, in one process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up every shape (set-up), measures for ``--seconds``, checks
the outputs, and prints as the LAST line of stdout one JSON object:
``correct, attempted, failed, metrics, device`` (and ``breakdown`` with
``--trace 1``).  ``--trace 0`` reports the cell's end-to-end metrics with
no profiler and no sampling; ``--trace 1`` reports its per-layer metrics.
Earlier lines are notes, one JSON object each.

The runner knows no cell, configuration or metric by name.  Everything is
found by the names in the manifest:

    configs[].file                     the configuration as it is run
    benchmark/traffic/<traffic>.json   the traffic mix; names its driver
    benchmark/drivers/<driver>.py      run(ctx) -> record
    benchmark/metrics/<metric>.py      read(run) -> number, or None
    benchmark/rehearse/manifest.json, manifest.*.json
                                       the rehearsal cells, one file or
                                       many (``rehearsal_manifest``)

A cell of ``BENCHMARK.json`` needs a TPU and as many chips as it asks
for; without them the run exits non-zero and prints no result.  There is
no CPU branch under a device metric's name.  The rehearsal cells are the
opposite: tiny, for walking the control flow with ``JAX_PLATFORMS=cpu``,
and refused on a TPU.  They are listed in
``benchmark/rehearse/manifest.json`` and in every fragment
``benchmark/rehearse/manifest.<anything>.json`` beside it, read in sorted
order as one list: a PR that adds a cell adds its rehearsal as a fragment
of its own and edits no file that is there.  A cell or configuration
name that two of those files give is refused, both files named.
"""
import time

T_START = time.perf_counter()       # as near to process start as we get

import argparse                     # noqa: E402
import glob                         # noqa: E402
import importlib                    # noqa: E402
import importlib.util               # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MANIFEST = "BENCHMARK.json"
REHEARSE = os.path.join("benchmark", "rehearse")
REHEARSAL = os.path.join(REHEARSE, "manifest.json")
# keyed by "is a rehearsal"
TRAFFIC_DIR = {False: os.path.join("benchmark", "traffic"),
               True: os.path.join(REHEARSE, "traffic")}


def load_json(*parts, root=ROOT):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def rehearsal_files(root=ROOT):
    """``manifest.json`` and then every ``manifest.*.json`` fragment in
    sorted order, as paths relative to ``root``."""
    found = glob.glob(os.path.join(root, REHEARSE, "manifest.*.json"))
    return [REHEARSAL] + sorted(os.path.relpath(p, root) for p in found)


def rehearsal_manifest(root=ROOT):
    """The rehearsal cells of every file of ``rehearsal_files`` as one
    manifest: ``{"configs": [...], "workloads": [...]}``, in the files'
    order.  A name that two files give is refused."""
    merged = {"configs": [], "workloads": []}
    seen = {key: {} for key in merged}      # name -> the file that gave it
    for path in rehearsal_files(root):
        part = load_json(path, root=root)
        for key, entries in merged.items():
            for entry in part[key]:
                first = seen[key].setdefault(entry["name"], path)
                if first != path:
                    sys.exit(f"benchmark: {key} name {entry['name']!r} is "
                             f"given by both {first} and {path}")
                entries.append(entry)
    return merged


def find_cell(name):
    """(is a rehearsal, manifest, cell) of the cell called ``name``:
    ``BENCHMARK.json`` first, then the rehearsal files."""
    for rehearsal in (False, True):
        manifest = rehearsal_manifest() if rehearsal else load_json(MANIFEST)
        for cell in manifest["workloads"]:
            if cell["name"] == name:
                return rehearsal, manifest, cell
    sys.exit(f"benchmark: no cell named {name!r} in {MANIFEST} or "
             f"{', '.join(rehearsal_files())}")


def metrics_of(kind, cell):
    """The ``kind`` metrics of ``BENCHMARK.json`` that this cell reports
    (a rehearsal cell: those of the cell it stands for)."""
    name = cell.get("stands_for", cell["name"])
    return [m for m in load_json(MANIFEST)[kind]
            if "workloads" not in m or name in m["workloads"]]


def load_reader(name):
    """``benchmark/metrics/<name>.py``, by its path: a metric's name may
    hold dots, which a module name may not."""
    path = os.path.join(ROOT, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Ctx:
    """What a driver is given."""

    def __init__(self, cell, config, traffic, args, devices, on_chip):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds = args.seed, args.seconds
        self.trace = bool(args.trace)
        self.devices, self.on_chip = devices, on_chip
        self.setup_s = None
        self.setup_compile = None

    def note(self, **fields):
        """An earlier line of stdout; ``t`` is seconds since the start."""
        print(json.dumps(dict(fields, t=time.perf_counter() - T_START),
                         default=float), flush=True)

    def open_window(self):
        """Set-up ends here: process start to window start, compilation,
        warm-up and ramp included."""
        from benchmark.lib import probe
        self.setup_compile = probe.compile_counters()
        now = time.perf_counter()
        self.setup_s = now - T_START
        self.note(phase="window_open", setup_s=self.setup_s,
                  compile=self.setup_compile)
        return now


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        sys.exit("benchmark: --seed must be >= 0")

    rehearsal, manifest, cell = find_cell(args.workload)
    config_entry = next(c for c in manifest["configs"]
                        if c["name"] == cell["config"])
    config = load_json(config_entry["file"])
    traffic = load_json(TRAFFIC_DIR[rehearsal], cell["traffic"] + ".json")

    sys.path.insert(0, ROOT)
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if rehearsal and platform == "tpu":
        sys.exit(f"benchmark: {cell['name']} is a CPU rehearsal; on a TPU "
                 f"run a cell of {MANIFEST}")
    if not rehearsal and platform != "tpu":
        sys.exit(f"benchmark: {cell['name']} needs a TPU, jax found "
                 f"platform {platform!r} — there is no CPU fallback (the "
                 f"rehearse-* cells of {REHEARSE} rehearse the control flow)")
    if len(devices) < cell["chips"]:
        sys.exit(f"benchmark: {cell['name']} needs {cell['chips']} chip(s), "
                 f"jax found {len(devices)}")

    from paddle_tpu.framework import jax_compat
    from paddle_tpu.observability import timeline
    from benchmark.lib import probe
    cache_dir = jax_compat.enable_persistent_cache(
        jax_compat.checkout_cache_dir())
    timeline.install_compile_hook()     # compile.count, from the start
    ctx = Ctx(cell, config, traffic, args, devices[:cell["chips"]],
              on_chip=not rehearsal)
    ctx.note(phase="start", cell=cell["name"], seed=args.seed,
             seconds=args.seconds, trace=args.trace, platform=platform,
             device_kind=devices[0].device_kind, devices=len(devices),
             compile_cache_dir=cache_dir, rehearsal=rehearsal)

    driver = importlib.import_module(
        "benchmark.drivers." + traffic["driver"])
    run = driver.run(ctx)
    peak_bytes = probe.memory_peak_bytes(ctx.devices)
    run.update(setup_s=ctx.setup_s, setup_compile=ctx.setup_compile,
               on_chip=ctx.on_chip, device_kind=devices[0].device_kind,
               chips=cell["chips"])

    kind = "per_layer" if args.trace else "end_to_end"
    values = {}
    for m in metrics_of(kind, cell):
        value = load_reader(m["name"]).read(run)
        if value is not None:
            values[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": all(run["checks"].values()),
              "attempted": run["attempted"], "failed": run["failed"],
              "metrics": values, "device": device}
    trace = run.get("trace")
    if args.trace:
        if not trace or trace["busy_s"] <= 0:
            sys.exit("benchmark: the traced window holds no device "
                     "operation — nothing ran on the device")
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    ctx.note(phase="checks", **run["checks"])
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
