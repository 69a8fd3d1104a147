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

from benchmark.lib import (flops_bytes, peaks, reference, stats,  # noqa: E402
                           traffic, xplane)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
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

def test_manifest_keys_sizes_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len(MANIFEST["command"]) <= 32
    for word in MANIFEST["command"]:
        assert not word.startswith("/") and ".." not in word
    script = MANIFEST["command"][1]
    assert any(script.startswith(p + "/") for p in MANIFEST["paths"])
    assert os.path.isfile(os.path.join(ROOT, script))
    assert 1 <= len(MANIFEST["workloads"]) <= 24
    four = sum(c["chips"] == 4 for c in MANIFEST["workloads"])
    assert four <= max(1, len(MANIFEST["workloads"]) // 4)


def test_manifest_names_units_and_lines():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[group]:
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
    for m in METRICS:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in MANIFEST["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for c in MANIFEST["workloads"]:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(c["traffic"]) and c["chips"] in (1, 4)
    pairs = [(c["config"], c["traffic"]) for c in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_manifest_names_only_files_that_exist():
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))
    used = {c["config"] for c in MANIFEST["workloads"]}
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used, f"{c['name']} is used by no cell"
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        cfg = load(c["file"])
        assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
    for cell in MANIFEST["workloads"]:
        mix = load("benchmark", "traffic", cell["traffic"] + ".json")
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "drivers", mix["driver"] + ".py"))
    for m in METRICS:
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "metrics", m["name"] + ".py")), m["name"]
    for path in MANIFEST["paths"]:
        for _, _, names in os.walk(os.path.join(ROOT, path)):
            for n in names:
                if not n.endswith(".pyc"):
                    assert re.match(r"^[A-Za-z0-9_.\-]+$", n), n


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_what_the_contract_asks(cell):
    def reported(kind):
        return [m["name"] for m in MANIFEST[kind]
                if "workloads" not in m or cell in m["workloads"]]
    e2e, layer = reported("end_to_end"), reported("per_layer")
    assert "setup_s" in e2e and len(e2e) >= 2 and len(layer) >= 1


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_layer_metric_moves_a_metric_its_cells_report(metric):
    m = next(x for x in MANIFEST["per_layer"] if x["name"] == metric)
    target = next(x for x in MANIFEST["end_to_end"]
                  if x["name"] == m["moves"])
    for cell in m.get("workloads", CELLS):
        assert cell in CELLS
        assert "workloads" not in target or cell in target["workloads"], (
            f"{metric} moves {m['moves']}, which {cell} does not report")
    same_layer = {x["layer"] for x in MANIFEST["per_layer"]
                  if x["layer"].lower() == m["layer"].lower()}
    assert len(same_layer) == 1, "one layer, one spelling"


def test_rehearsal_cells_stand_for_real_ones_and_share_no_name():
    rehearse = load("benchmark", "rehearse", "manifest.json")
    for cell in rehearse["workloads"]:
        assert cell["name"] not in CELLS
        assert cell["stands_for"] in CELLS
    assert {c["stands_for"] for c in rehearse["workloads"]} == set(CELLS)


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


def test_cell_config_and_metric_added_as_files_only(run_cell, tmp_path):
    """What a later PR does: new files and new entries, no edit to a file
    that is there — and the runner finds and runs them."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    rehearse = os.path.join(root, "benchmark", "rehearse")
    cfg = load("benchmark", "rehearse", "configs", "tiny-serve.json")
    cfg["num_layers"] = 3
    cfg["engine"]["slots"] = 8
    with open(os.path.join(rehearse, "configs", "added.json"), "w") as f:
        json.dump(cfg, f)
    mix = load("benchmark", "rehearse", "traffic", "tiny-backlog.json")
    mix["output_len"]["median"] = 6
    with open(os.path.join(rehearse, "traffic", "added-mix.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "benchmark", "metrics",
                           "added.steps.py"), "w") as f:
        f.write("def read(run):\n    return len(run['step_s'])\n")
    manifest = load("benchmark", "rehearse", "manifest.json")
    manifest["configs"].append(
        {"name": "added", "file": "benchmark/rehearse/configs/added.json"})
    manifest["workloads"].append(
        {"name": "rehearse-added", "config": "added",
         "traffic": "added-mix", "chips": 1})
    with open(os.path.join(rehearse, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    real = dict(MANIFEST)
    real["per_layer"] = MANIFEST["per_layer"] + [
        {"name": "added.steps", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "scheduler",
         "moves": "setup_s", "workloads": ["rehearse-added"]}]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(real, f)

    rc, lines, err = run_cell(root, "rehearse-added", 1)
    assert rc == 0, err[-2000:]
    result = check_last_line(lines, trace=1)
    assert result["metrics"]["added.steps"]["value"] > 0
    assert result["metrics"]["added.steps"]["unit"] == "steps"
    assert "compile.setup_misses" in result["metrics"]
