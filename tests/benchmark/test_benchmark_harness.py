"""Fast CPU tests of the benchmark harness (BENCHMARK.json, benchmark/).

No chip, no topology call: the yardstick's arithmetic on hand-made
inputs, the seeded generators, the manifest's own rules, the trace
reducer on a recorded trace, and the runner walked end to end on the
tiny rehearsal cells — in subprocesses, because the runner turns on the
persistent compile cache and a tracer, which this process must not get.
"""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as runner                     # noqa: E402
from benchmark.lib import (flops_bytes, peaks, reference, stats,  # noqa: E402
                           traffic, xplane)
from grown_tree import grown_root, tree                 # noqa: E402,F401
import grown_tree                                       # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load(*parts, root=ROOT):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


MANIFEST = load("BENCHMARK.json")
CELLS = [c["name"] for c in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


# --------------------------------------------------------------------------
# seeded traffic
# --------------------------------------------------------------------------

MIX = {"prompt_len": {"law": "lognormal", "median": 512, "sigma": 0.6,
                       "min": 64, "max": 1024},
        "output_len": {"law": "lognormal", "median": 64, "sigma": 0.7,
                       "min": 8, "max": 256},
        "token_ids": {"law": "uniform"}, "block": 32}


def schedule(seed, n=96):
    return traffic.requests(MIX, 50304, n, seed)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_same_seed_same_bytes(seed):
    assert (traffic.schedule_bytes(schedule(seed))
            == traffic.schedule_bytes(schedule(seed)))


def test_another_seed_another_order_same_work():
    ra, rb = schedule(1), schedule(2)
    assert traffic.schedule_bytes(ra) != traffic.schedule_bytes(rb)
    # the same multiset of lengths: only the order is the seed's
    assert sorted(len(p) for p, _ in ra) == sorted(len(p) for p, _ in rb)
    assert sorted(m for _, m in ra) == sorted(m for _, m in rb)


def test_lengths_follow_the_law_and_blocks_are_balanced():
    lens = traffic.stratified_stream(MIX["prompt_len"], 320, 32,
                                     traffic.stream_rng(3, 0))
    assert lens.min() >= 64 and lens.max() <= 1024
    assert np.median(lens) == pytest.approx(512, rel=0.05)
    block_means = lens.reshape(10, 32).mean(1)
    # every block holds one value of each of 32 strata, so block means
    # agree far better than 32 free draws would (sd/sqrt(32) ~ 9%)
    assert block_means.std() / block_means.mean() < 0.02


def test_a_law_the_generator_does_not_know_is_refused():
    with pytest.raises(ValueError, match="pareto"):
        traffic.law_quantiles({"law": "pareto", "alpha": 2.0}, 8)
    with pytest.raises(ValueError, match="bigram"):
        traffic.token_ids({"law": "bigram"}, 100, 8, traffic.stream_rng(0, 0))


def test_zipf_tokens_are_skewed_and_in_range():
    ids = traffic.token_ids({"law": "zipf", "exponent": 1.0}, 50304, 20000,
                            traffic.stream_rng(5, traffic.S_TOKENS))
    assert ids.dtype == np.int32 and ids.min() >= 0 and ids.max() < 50304
    assert (ids < 10).mean() > 0.2          # H(10)/H(50304) = 0.26


# --------------------------------------------------------------------------
# arithmetic
# --------------------------------------------------------------------------

def test_percentile_is_nearest_rank():
    data = list(range(1, 101))
    assert stats.percentile(data, 95) == 95
    assert stats.percentile(data, 50) == 50
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([5, 1, 9], 95) == 9
    assert stats.percentile([], 95) is None


def test_gaps_and_rates():
    stamps = [10.0, 10.5, 10.6, 12.0]
    assert stats.gaps_ending_in(stamps, 0, 99) == pytest.approx(
        [0.5, 0.1, 1.4])
    # a gap belongs to the window that saw it END
    assert stats.gaps_ending_in(stamps, 10.55, 11.0) == pytest.approx([0.1])
    assert stats.count_in(stamps, 10.5, 10.6) == 2
    assert stats.rate(300, 40.0) == 7.5
    with pytest.raises(ValueError):
        stats.rate(1, 0)


def test_spread_is_the_contracts_quartile_distance():
    values = [100, 101, 102, 103, 104, 105]
    # statistics.quantiles(n=4), exclusive method: Q1 100.75, Q3 104.25
    assert stats.iqr_share(values) == pytest.approx(3.5 / 102.5)


TINY = {"hidden_size": 8, "ffn_size": 32, "num_layers": 2, "vocab_size": 100}


def test_train_flops_count_causal_attention_and_no_lookups():
    dense = 2 * (4 * 8 * 8 + 2 * 8 * 32) + 100 * 8      # 2336 weights
    assert flops_bytes.matmul_params(TINY) == dense
    attn_fwd = 2 * (2 * 2 * 8 * (16 + 1) / 2)            # 2 layers, seq 16
    assert flops_bytes.train_flops_per_token(TINY, 16) == pytest.approx(
        3 * (2 * dense + attn_fwd))


def test_gpt3_xl_needs_about_8_5_gflop_a_token():
    cfg = load("benchmark", "configs", "gpt3-1.3b-train.json")
    assert flops_bytes.train_flops_per_token(cfg, 2048) == pytest.approx(
        8.47e9, rel=0.005)


def test_decode_bytes_and_roofline():
    assert flops_bytes.kv_bytes_per_token(TINY, 2) == 2 * 2 * 8 * 2
    nbytes = flops_bytes.decode_step_bytes(TINY, active=4, live_tokens=96,
                                           weight_itemsize=2, kv_itemsize=2)
    assert nbytes == 2336 * 2 + (96 + 4) * 64
    flops = flops_bytes.decode_step_flops(TINY, active=4, live_tokens=96)
    assert flops == 2 * 2336 * 4 + 2 * 2 * 2 * 8 * 96
    table = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}
    assert flops_bytes.roofline_seconds(5000, 1000, table) == (5.0, "compute")
    assert flops_bytes.roofline_seconds(1000, 5000, table) == (5.0, "memory")


def test_peaks_known_chip_and_unknown_kind():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="TPU v9"):
        peaks.peaks_for("TPU v9")


def test_losses_learned_rule():
    falling = [11.0 - 0.05 * i + 0.1 * (i % 2) for i in range(40)]
    assert reference.losses_learned(falling)
    assert not reference.losses_learned([11.0] * 40)
    assert not reference.losses_learned(falling[:-1] + [float("nan")])


def test_plain_reference_matches_the_programs_forward():
    """The benchmark's own float32 forward against ``gpt.forward`` on
    seeded weights: the reference is independent code, so it is checked
    against the program here, at a tiny size."""
    import jax
    from paddle_tpu.models import gpt
    cfg = gpt.gpt_tiny()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    toks = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 24))
    want = np.asarray(gpt.forward(params, toks, cfg))
    got = np.asarray(reference.gpt_logits(params, toks, cfg.num_heads,
                                          cfg.layer_norm_eps))
    np.testing.assert_allclose(got, want, atol=2e-4 * want.std())
    labels = np.roll(toks, -1, 1)
    assert float(reference.gpt_loss(
        params, toks, labels, cfg.num_heads, cfg.layer_norm_eps)) == \
        pytest.approx(float(gpt.loss_fn(params, toks, labels, cfg)),
                      abs=1e-4)


def test_stamps_mark_only_new_positions():
    from benchmark.drivers.serve_engine import Stamped
    stamps = []
    toks = Stamped(stamps)
    for t in (5, 6, 7):
        toks.append(t)
    assert list(toks) == [5, 6, 7] and len(stamps) == 3
    assert stamps == sorted(stamps)
    # preempted: the engine starts the request over on a fresh list; the
    # client already has three tokens, so only the fourth is news
    again = Stamped(stamps)
    for t in (5, 6, 7, 8):
        again.append(t)
    assert len(stamps) == 4 and stamps[3] >= stamps[2]


# --------------------------------------------------------------------------
# the trace reducer on a recorded trace
# --------------------------------------------------------------------------

def test_xplane_reduce_on_the_recorded_trace():
    fixture = load("benchmark", "lib", "xplane_fixture.json")
    out = xplane.reduce(fixture["planes"], on_chip=True)
    want = fixture["expect"]
    assert out["window_s"] == pytest.approx(want["window_s"])
    assert out["busy_s"] == pytest.approx(want["busy_s"])
    assert 0 < out["busy_s"] < out["window_s"]
    idle = dict(out["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(
        out["window_s"] - out["busy_s"])
    assert idle[want["gap_span"]] == pytest.approx(want["gap_s"])
    ops = dict(out["device_ops"])
    assert ops[want["top_op"]] == pytest.approx(want["top_op_s"])
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10


def test_xplane_union_self_time_and_gap_attribution():
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["while", 100, 800], ["fusion.1", 100, 300],
                ["fusion.2", 500, 300], ["copy", 1000, 100]]},
            {"name": "Steps", "events": [["step", 0, 2000]]}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench.trace_window", 0, 1500], ["bench.engine_step", 0, 950],
            ["bench.submit", 950, 550]]}]}]
    out = xplane.reduce(planes, on_chip=True)
    assert out["window_s"] == pytest.approx(1500e-9)
    assert out["busy_s"] == pytest.approx(900e-9)     # union, not the sum
    assert dict(out["device_ops"]) == pytest.approx(
        {"fusion.1": 300e-9, "fusion.2": 300e-9, "while": 200e-9,
         "copy": 100e-9})
    assert dict(out["idle_gaps"]) == pytest.approx(
        {"bench.submit": 400e-9, "bench.engine_step": 200e-9})
    assert xplane.reduce([], on_chip=True) is None


def test_xplane_host_threads_never_stand_in_for_the_chip():
    """A chip run whose profiler dropped the device plane must reduce to
    nothing (run.py then exits), not to the host's XLA-client threads."""
    planes = [{"name": "/host:CPU", "lines": [
        {"name": "tf_XLAPjRtCpuClient/1", "events": [["dot.3", 100, 400]]},
        {"name": "main", "events": [["bench.trace_window", 0, 1000]]}]}]
    assert xplane.reduce(planes, on_chip=True) is None
    rehearsal = xplane.reduce(planes, on_chip=False)
    assert rehearsal["busy_s"] == pytest.approx(400e-9)
    # and a rehearsal never reads a device plane it should not have
    device = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [["fusion", 0, 10]]}]}]
    assert xplane.reduce(device, on_chip=False) is None


# --------------------------------------------------------------------------
# the manifest
# --------------------------------------------------------------------------

# Every test that takes ``tree`` reads only BENCHMARK.json, the rehearsal
# manifests and file names, and runs twice: on the checkout and on the
# tree the next cell-adding PR leaves (grown_tree.py).  What it asserts
# has to hold as the manifest grows: properties, never today's lists.

def test_manifest_keys_sizes_and_limits(tree):
    manifest = load("BENCHMARK.json", root=tree)
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(tree, "BENCHMARK.json")) <= 64 << 10
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert len(manifest["command"]) <= 32
    for word in manifest["command"]:
        assert not word.startswith("/") and ".." not in word
    script = manifest["command"][1]
    assert any(script.startswith(p + "/") for p in manifest["paths"])
    assert os.path.isfile(os.path.join(tree, script))
    assert 1 <= len(manifest["configs"]) <= 24
    assert 1 <= len(manifest["workloads"]) <= 24
    assert 1 <= len(manifest["end_to_end"]) <= 16
    assert 1 <= len(manifest["per_layer"]) <= 128
    four = sum(c["chips"] == 4 for c in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_manifest_names_units_and_lines(tree):
    manifest = load("BENCHMARK.json", root=tree)
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            names.append((group in ("end_to_end", "per_layer"),
                          entry["name"]))
            assert NAME.match(entry["name"]), entry["name"]
            # a metric's ``source`` is one of four words, checked below
            lines = [entry.get("why"), entry.get("layer"),
                     entry.get("source") if group == "configs" else None]
            for text in filter(None, lines):
                assert 1 <= len(text) <= 200, entry
                assert "\n" not in text and "\t" not in text
    assert len(names) == len(set(names)), "a name is used twice"
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for c in manifest["workloads"]:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(c["traffic"]) and c["chips"] in (1, 4)
    pairs = [(c["config"], c["traffic"]) for c in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_manifest_names_only_files_that_exist(tree):
    manifest = load("BENCHMARK.json", root=tree)
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    used = {c["config"] for c in manifest["workloads"]}
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used, f"{c['name']} is used by no cell"
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        cfg = load(c["file"], root=tree)
        assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
    for cell in manifest["workloads"]:
        mix = load("benchmark", "traffic", cell["traffic"] + ".json",
                   root=tree)
        assert os.path.isfile(os.path.join(
            tree, "benchmark", "drivers", mix["driver"] + ".py"))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert os.path.isfile(os.path.join(
            tree, "benchmark", "metrics", m["name"] + ".py")), m["name"]
    for path in manifest["paths"]:
        for _, _, names in os.walk(os.path.join(tree, path)):
            for n in names:
                if not n.endswith(".pyc"):
                    assert re.match(r"^[A-Za-z0-9_.\-]+$", n), n


def cell_reports_what_the_contract_asks(manifest, cell):
    def reported(kind):
        return [m["name"] for m in manifest[kind]
                if "workloads" not in m or cell in m["workloads"]]
    e2e, layer = reported("end_to_end"), reported("per_layer")
    assert "setup_s" in e2e and len(e2e) >= 2 and len(layer) >= 1


def layer_metric_moves_a_metric_its_cells_report(manifest, metric):
    cells = [c["name"] for c in manifest["workloads"]]
    m = next(x for x in manifest["per_layer"] if x["name"] == metric)
    target = next(x for x in manifest["end_to_end"]
                  if x["name"] == m["moves"])
    for cell in m.get("workloads", cells):
        assert cell in cells
        assert "workloads" not in target or cell in target["workloads"], (
            f"{metric} moves {m['moves']}, which {cell} does not report")
    same_layer = {x["layer"] for x in manifest["per_layer"]
                  if x["layer"].lower() == m["layer"].lower()}
    assert len(same_layer) == 1, "one layer, one spelling"


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_what_the_contract_asks(cell):
    cell_reports_what_the_contract_asks(MANIFEST, cell)


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_layer_metric_moves_a_metric_its_cells_report(metric):
    layer_metric_moves_a_metric_its_cells_report(MANIFEST, metric)


def test_the_grown_trees_cells_and_layer_metrics_hold_too(grown_root):
    """The two tests above are parametrised from the checkout as this
    file is imported; the tree a cell-adding PR leaves is asked here."""
    manifest = load("BENCHMARK.json", root=grown_root)
    assert grown_tree.CELL in [c["name"] for c in manifest["workloads"]]
    for cell in manifest["workloads"]:
        cell_reports_what_the_contract_asks(manifest, cell["name"])
    for m in manifest["per_layer"]:
        layer_metric_moves_a_metric_its_cells_report(manifest, m["name"])


def test_rehearsal_cells_stand_for_real_ones_and_share_no_name(tree):
    """Over ``manifest.json`` and every fragment beside it, as the
    runner reads them: every real cell has a rehearsal, of another name,
    whose files are there."""
    real = [c["name"] for c in load("BENCHMARK.json", root=tree)["workloads"]]
    rehearse = runner.rehearsal_manifest(tree)
    cells = rehearse["workloads"]
    names = [c["name"] for c in cells]
    assert len(set(names)) == len(names) and not set(names) & set(real)
    for cell in cells:
        assert cell["stands_for"] in real
    assert {c["stands_for"] for c in cells} == set(real)
    configs = {c["name"]: c["file"] for c in rehearse["configs"]}
    for cell in cells:
        assert os.path.exists(os.path.join(tree, configs[cell["config"]]))
        assert os.path.exists(os.path.join(
            tree, "benchmark", "rehearse", "traffic",
            cell["traffic"] + ".json"))


def test_fragments_are_read_in_sorted_order_after_the_manifest(tree):
    files = runner.rehearsal_files(tree)
    assert files[0] == os.path.join("benchmark", "rehearse", "manifest.json")
    assert files[1:] == sorted(files[1:]) and len(files) >= 2
    assert os.path.join("benchmark", "rehearse",
                        "manifest.kanana2.json") in files
    first = load(files[0], root=tree)["workloads"]
    merged = runner.rehearsal_manifest(tree)["workloads"]
    assert merged[:len(first)] == first


@pytest.mark.parametrize("key", ["configs", "workloads"])
def test_a_name_that_two_rehearsal_files_give_is_refused(key, tmp_path):
    """The runner exits non-zero and names both files."""
    rehearse = tmp_path / "benchmark" / "rehearse"
    rehearse.mkdir(parents=True)
    shutil.copy(os.path.join(ROOT, "benchmark", "rehearse", "manifest.json"),
                rehearse)
    taken = load("benchmark", "rehearse", "manifest.json")[key][0]
    twice = {"configs": [], "workloads": []}
    twice[key] = [taken]
    (rehearse / "manifest.twice.json").write_text(json.dumps(twice))
    with pytest.raises(SystemExit) as refused:
        runner.rehearsal_manifest(str(tmp_path))
    said = str(refused.value.code)      # a string: exit code 1, on stderr
    assert taken["name"] in said
    assert os.path.join("benchmark", "rehearse", "manifest.json") in said
    assert os.path.join("benchmark", "rehearse",
                        "manifest.twice.json") in said


# --------------------------------------------------------------------------
# the runner, end to end on the CPU
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def run_cell(tmp_path_factory):
    """``run_cell(root, cell, trace) -> (returncode, stdout lines)`` in a
    subprocess that shares one throw-away compile cache."""
    cache = tmp_path_factory.mktemp("jax_cache")

    def run(root, cell, trace, seconds="1.5"):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(cache),
                   PYTHONPATH=ROOT + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        proc = subprocess.run(
            [sys.executable, os.path.join(root, "benchmark", "run.py"),
             "--workload", cell, "--seed", str(2**31 + 5), "--seconds",
             seconds, "--trace", str(trace)],
            cwd=root, env=env, capture_output=True, text=True, timeout=600)
        return proc.returncode, proc.stdout.strip().splitlines(), proc.stderr
    return run


def check_last_line(lines, trace):
    result = json.loads(lines[-1])
    assert set(result) - {"breakdown"} == {"correct", "attempted", "failed",
                                           "metrics", "device"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and UNIT.match(m["unit"])
    if trace:
        assert result["device"]["busy_s"] > 0
        assert result["device"]["window_s"] >= result["device"]["busy_s"]
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    return result


def test_rehearsal_train_traced(run_cell):
    rc, lines, err = run_cell(ROOT, "rehearse-train", 1)
    assert rc == 0, err[-2000:]
    result = check_last_line(lines, trace=1)
    assert set(result["metrics"]) == {"step.train_ms",
                                      "compile.setup_misses"}


def test_rehearsal_backlog_end_to_end(run_cell):
    rc, lines, err = run_cell(ROOT, "rehearse-backlog", 0)
    assert rc == 0, err[-2000:]
    result = check_last_line(lines, trace=0)
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    notes = {n["phase"]: n for n in map(json.loads, lines[:-1])}
    # the token gap is on a note line, not judged
    assert notes["window_closed"]["token_gap_p95_s"] > 0
    # first, a later and the last generated row of every sampled request
    ref = notes["reference"]
    assert ref["rows_checked"] > ref["requests_checked"] >= 4
    assert ref["emitted_logit_gap_max"] <= ref["tol"] == 0.10


def test_real_cell_without_a_tpu_prints_no_result(run_cell):
    rc, lines, err = run_cell(ROOT, CELLS[0], 0)
    assert rc != 0 and "needs a TPU" in err
    assert not any(x.startswith("{") and '"metrics"' in x for x in lines)


def files_under(root):
    """{relative path: bytes} of the three paths a PR may only add to."""
    found = {}
    for top in (grown_tree.MANIFEST,) + grown_tree.PATHS:
        top = os.path.join(root, top)
        walk = os.walk(top) if os.path.isdir(top) else [(root, [], [top])]
        for folder, dirs, names in walk:
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for n in names:
                path = os.path.join(folder, n)
                if not n.endswith(".pyc"):
                    with open(path, "rb") as f:
                        found[os.path.relpath(path, root)] = f.read()
    return found


def extends(new, old):
    """``new`` is ``old`` with entries appended: every list starts with
    the old one's items (themselves extended), nothing else differs."""
    if isinstance(old, dict):
        return (isinstance(new, dict) and set(new) == set(old)
                and all(extends(new[k], old[k]) for k in old))
    if isinstance(old, list):
        return (isinstance(new, list) and len(new) >= len(old)
                and all(extends(n, o) for n, o in zip(new, old)))
    return new == old


def test_extends_sees_an_edit_a_removal_and_a_reordering():
    old = {"a": [{"n": 1, "w": ["x"]}, {"n": 2}], "k": 51}
    assert extends({"a": [{"n": 1, "w": ["x", "y"]}, {"n": 2}, {"n": 3}],
                    "k": 51}, old)
    assert not extends({"a": [{"n": 1, "w": ["y", "x"]}, {"n": 2}],
                        "k": 51}, old)
    assert not extends({"a": [{"n": 2}, {"n": 1, "w": ["x"]}], "k": 51}, old)
    assert not extends({"a": [{"n": 1, "w": ["x"]}], "k": 51}, old)
    assert not extends(dict(old, k=50), old)
    assert not extends(dict(old, more=1), old)


def test_cell_config_and_metric_added_as_files_only(run_cell, grown_root):
    """What the next PR that adds a cell does, to the letter
    (grown_tree.py::build): files added, entries appended — and
    (a) no file that was there differs, but ``BENCHMARK.json`` by
    appended entries; (b) the runner finds and runs the added rehearsal
    and reports the added metric; (c) is every test of this directory
    that takes the fixture ``tree``: each passes on the grown tree."""
    before, after = files_under(ROOT), files_under(grown_root)
    assert set(before) <= set(after)
    for path, data in before.items():
        if path != grown_tree.MANIFEST:
            assert after[path] == data, f"{path} was edited"
    added = set(after) - set(before)
    assert added == {
        "benchmark/configs/added.json", "benchmark/traffic/added-mix.json",
        "benchmark/rehearse/configs/tiny-added.json",
        "benchmark/rehearse/traffic/tiny-added-mix.json",
        "benchmark/rehearse/manifest.added.json",
        "benchmark/metrics/added.steps.py"}
    grown = load("BENCHMARK.json", root=grown_root)
    assert extends(grown, MANIFEST) and grown != MANIFEST
    assert grown["per_layer"][-1]["name"] == grown_tree.METRIC
    assert grown["workloads"][-1]["name"] == grown_tree.CELL

    rc, lines, err = run_cell(grown_root, grown_tree.REHEARSAL, 1)
    assert rc == 0, err[-2000:]
    result = check_last_line(lines, trace=1)
    assert result["metrics"][grown_tree.METRIC]["value"] > 0
    assert result["metrics"][grown_tree.METRIC]["unit"] == "steps"
    # and the accepted metrics whose lists the cell was appended to
    assert {"compile.setup_misses", "sched.slot_occupancy",
            "step.decode_ms_p50",
            "sched.span_self_ms_per_step"} <= set(result["metrics"])
