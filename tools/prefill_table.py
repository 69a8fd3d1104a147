#!/usr/bin/env python3
"""One cell of the benchmark, run as ``benchmark/run.py`` runs it, with
one note line more: what the window's prefill waves were given and what
they paid for, by bucket.

    python3 tools/prefill_table.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The arguments are ``benchmark/run.py``'s and go to it untouched; so does
everything it prints, its result line last.  Before that line this
prints ``{"phase": "prefill_programs", ...}`` with

  by_bucket   ``benchmark/lib/programs.py::by_bucket`` over the window:
              ``{"<batch>x<seq>": {waves, requests, tokens, rows,
              device_s}}`` — the table of ``PERF.md`` section 5
  readings    the three ``prefill.*`` readers on the same record, for a
              cell ``BENCHMARK.json`` does not list them for as well
  hist        the two histograms' count, sum and p50 over the window
  hist_prefill_share
              100 x hist.prefill.sum / (hist.prefill.sum + hist.decode.sum):
              what ``prefill.device_share`` must agree with

It edits nothing: the cell's driver is found as ``run.py`` finds it and
its ``run`` is wrapped for this process.  A cell of ``BENCHMARK.json``
needs a TPU, a ``rehearse-*`` cell the CPU, as ``run.py`` says.
"""
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run as runner                     # noqa: E402

READERS = ("prefill.device_share", "prefill.padded_rows_share",
           "prefill.device_us_per_prompt_token")


def summary(record):
    """The note line's fields, from a serving driver's record."""
    from benchmark.lib import programs
    hist = record["hist"]
    both = hist["prefill"]["sum"] + hist["decode"]["sum"]
    return {
        "by_bucket": programs.by_bucket(record),
        "readings": {name: runner.load_reader(name).read(record)
                     for name in READERS},
        "hist": hist,
        "hist_prefill_share": (100.0 * hist["prefill"]["sum"] / both
                               if both else None)}


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    name = argv[argv.index("--workload") + 1]
    rehearsal, _, cell = runner.find_cell(name)
    mix = runner.load_json(runner.TRAFFIC_DIR[rehearsal],
                           cell["traffic"] + ".json")
    driver = importlib.import_module("benchmark.drivers." + mix["driver"])
    run = driver.run

    def run_and_note(ctx):
        record = run(ctx)
        if "hist" in record:            # a serving cell's record
            ctx.note(phase="prefill_programs", **summary(record))
        return record

    driver.run = run_and_note
    runner.main(argv)


if __name__ == "__main__":
    main()
