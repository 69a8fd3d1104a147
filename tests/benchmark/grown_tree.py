"""The tree the NEXT cell-adding PR leaves, built in a throw-away copy.

The rule the benchmark keeps: a later PR adds a cell, its configuration,
its rehearsal and its per-layer metrics by ADDING files and APPENDING
entries to ``BENCHMARK.json``; it edits no file under ``benchmark/`` or
``tests/benchmark/``, and ``pytest tests/benchmark`` stays green.
``build`` does that to the letter to a copy of the checkout's
``BENCHMARK.json``, ``benchmark/`` and ``tests/benchmark/``; the fixture
``tree`` hands every manifest test the checkout and then the grown copy,
so each of them holds as the manifest grows, and
``test_benchmark_harness.py::test_cell_config_and_metric_added_as_files_only``
proves the rest.  Import the two fixtures by name::

    from grown_tree import grown_root, tree  # noqa: F401
"""
import json
import os
import shutil

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = "BENCHMARK.json"
PATHS = ("benchmark", os.path.join("tests", "benchmark"))
LIKE = "serve-1.3b-backlog"     # the cell whose drivers the new one shares
CELL, REHEARSAL, METRIC = "added-cell", "rehearse-added", "added.steps"


def load(root, *parts):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def dump(value, root, *parts):
    """A NEW file: an existing one is never written over."""
    path = os.path.join(root, *parts)
    assert not os.path.exists(path), path
    with open(path, "w") as f:
        json.dump(value, f, indent=1)


def build(dest):
    """Copy the three paths to ``dest`` and do there what a PR that adds
    the serving cell ``added-cell`` does.  Returns ``dest``."""
    for path in PATHS:
        shutil.copytree(os.path.join(ROOT, path), os.path.join(dest, path),
                        ignore=shutil.ignore_patterns("__pycache__"))

    # ---- files added: a configuration and a traffic mix ...
    cfg = load(ROOT, "benchmark", "configs", "gpt3-1.3b-serve.json")
    cfg["engine"]["slots"] = 16
    dump(cfg, dest, "benchmark", "configs", "added.json")
    mix = load(ROOT, "benchmark", "traffic", "backlog.json")
    mix["output_len"]["median"] = 96
    dump(mix, dest, "benchmark", "traffic", "added-mix.json")
    # ... their rehearsal, in a fragment of its own ...
    tiny = load(ROOT, "benchmark", "rehearse", "configs", "tiny-serve.json")
    tiny["num_layers"] = 3
    tiny["engine"]["slots"] = 8
    dump(tiny, dest, "benchmark", "rehearse", "configs", "tiny-added.json")
    tiny_mix = load(ROOT, "benchmark", "rehearse", "traffic",
                    "tiny-backlog.json")
    tiny_mix["output_len"]["median"] = 6
    dump(tiny_mix, dest, "benchmark", "rehearse", "traffic",
         "tiny-added-mix.json")
    dump({"about": "the rehearsal of added-cell",
          "configs": [{"name": "tiny-added", "file":
                       "benchmark/rehearse/configs/tiny-added.json"}],
          "workloads": [{"name": REHEARSAL, "config": "tiny-added",
                         "traffic": "tiny-added-mix", "chips": 1,
                         "stands_for": CELL}]},
         dest, "benchmark", "rehearse", "manifest.added.json")
    # ... and a per-layer metric's reader
    path = os.path.join(dest, "benchmark", "metrics", METRIC + ".py")
    assert not os.path.exists(path)
    with open(path, "w") as f:
        f.write("def read(run):\n    return len(run['step_s'])\n")

    # ---- entries appended to BENCHMARK.json, nothing else touched
    manifest = load(ROOT, MANIFEST)
    manifest["configs"].append(
        {"name": "added", "source": cfg["source"] + " (16 slots)",
         "file": "benchmark/configs/added.json", "reduced": cfg["reduced"],
         "why": "what a PR that adds a configuration appends"})
    manifest["workloads"].append(
        {"name": CELL, "config": "added", "traffic": "added-mix",
         "chips": 1, "why": "what a PR that adds a serving cell appends"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if LIKE in m.get("workloads", ()):
            m["workloads"].append(CELL)
    manifest["per_layer"].append(
        {"name": METRIC, "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "scheduler",
         "moves": "serve_tokens_per_s", "workloads": [CELL]})
    with open(os.path.join(dest, MANIFEST), "w") as f:
        json.dump(manifest, f, indent=1)
    return dest


_BUILT = {}     # one build a process, whichever module asks first


@pytest.fixture(scope="session")
def grown_root(tmp_path_factory):
    if "root" not in _BUILT:
        _BUILT["root"] = build(str(tmp_path_factory.mktemp("grown")))
    return _BUILT["root"]


@pytest.fixture(params=["checkout", "grown"])
def tree(request):
    """The root a manifest test loads from."""
    if request.param == "checkout":
        return ROOT
    return request.getfixturevalue("grown_root")
