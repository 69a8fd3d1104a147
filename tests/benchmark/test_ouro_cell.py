"""CPU tests of what the ``serve-ouro-2.6b-reason-backlog`` cell adds to
the benchmark: the looped family's operation and byte counts against
hand counts, the configuration file against the published config, the
three new readers on hand-made records at the published and at the
rehearsal's size, and the rehearsal cell walked end to end and traced
(in subprocesses, as test_benchmark_harness.py does and for its
reason)."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.drivers import serve_family, serve_looped    # noqa: E402
from benchmark.lib import flops_bytes_ouro as fb            # noqa: E402
from grown_tree import grown_root, tree                     # noqa: E402,F401

CELL = "serve-ouro-2.6b-reason-backlog"
NEW_METRICS = {
    "decode_step_roofline.looped": ("%", "higher", "program_span",
                                    "kernels"),
    "paged_attn_decode_roofline": ("%", "higher", "device_trace", "kernels"),
    "loop.passes_per_token": ("passes", "lower", "program_counter",
                              "jitted steps")}
FED_METRICS = (
    "serve_tokens_per_s", "sched.slot_occupancy", "sched.host_ms_per_step",
    "pager.pool_fill_peak", "pager.preempted_share", "step.decode_ms_p50",
    "sched.span_self_ms_per_step", "pager.span_ms_per_step",
    "step.dispatch_ms_per_step", "step.prefill_share",
    "step.readback_wait_share")
ACCEPTED_BEFORE = ("train-1.3b-pretrain-2k", "serve-1.3b-backlog",
                   "serve-kanana2-30b-backlog")


def load(*parts, root=ROOT):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


ARCH = load("benchmark", "configs", "ouro-2.6b-serve.json")
TINY = load("benchmark", "rehearse", "configs", "tiny-ouro.json")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"),
        os.path.join(ROOT, "benchmark", "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# --------------------------------------------------------------------------
# counts, against the issue's hand counts
# --------------------------------------------------------------------------

def test_parameter_counts_match_the_hand_counts():
    assert fb.layer_matmul_params(ARCH) == 4 * 2048 ** 2 + 3 * 2048 * 5632
    assert fb.layer_params(ARCH) == 51_388_416          # the issue's 51.39M
    assert fb.head_params(ARCH) == 49152 * 2048
    assert fb.closing_params(ARCH) == 4097              # final norm and gate
    assert fb.total_params(ARCH) == 48 * 51_388_416 + 201_326_592 + 4097
    assert round(fb.total_params(ARCH) / 1e6) == 2668
    assert round(2 * fb.total_params(ARCH) / 1e9, 2) == 5.34    # bf16 GB


def test_a_cached_position_holds_every_pass_of_every_layer():
    assert fb.virtual_layers(ARCH) == 192
    assert 2 * fb.kv_values_per_position(ARCH) == 1_572_864
    # a 48-layer model of the same widths would hold a quarter
    once = dict(ARCH, total_ut_steps=1)
    assert fb.kv_values_per_position(ARCH) == 4 * fb.kv_values_per_position(
        once)


def test_decode_step_streams_the_layers_once_a_pass():
    w = fb.decode_step_weight_params(ARCH)
    assert w == 4 * 48 * 51_388_416 + 49152 * 2048 + 4097
    # no live position: the issue's 19.9 GB and 24.3 ms at 819 GB/s
    b = fb.decode_step_bytes(ARCH, 0, 0, 2, 2)
    assert b == 2 * w and round(b / 1e9, 1) == 19.9
    assert round(1e3 * b / 819e9, 1) == 24.3
    # 7 slots, 4,300 live positions: K/V of 192 pairs read, 7 written
    b = fb.decode_step_bytes(ARCH, 7, 4300, 2, 2)
    assert b == 2 * w + 4307 * 1_572_864
    f = fb.decode_step_flops(ARCH, 7, 4300)
    assert f == 2 * 7 * (192 * fb.layer_matmul_params(ARCH)
                         + 49152 * 2048) + 192 * 4 * 2048 * 4300
    assert f / 197e12 < b / 819e9              # memory binds


def test_kernel_counts_one_call():
    assert fb.paged_attn_decode_flops(ARCH, 1000) == 4 * 2048 * 1000
    assert fb.paged_attn_decode_bytes(ARCH, 7, 1000, 2) == 2 * 2048 * (
        2 * 1000 + 2 * 7)


# --------------------------------------------------------------------------
# the configuration file and the manifest's new entries
# --------------------------------------------------------------------------

def test_config_holds_every_published_key_unchanged():
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "model_type": "ouro", "num_attention_heads": 16,
        "num_hidden_layers": 48, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    for key, value in published.items():
        assert ARCH[key] == value, key
    assert ARCH["reduced"] == []
    assert ARCH["source"] == ("https://huggingface.co/ByteDance/Ouro-2.6B/"
                              "blob/main/config.json")
    for block in ("assumed", "deployment", "sizing", "engine"):
        assert ARCH[block], block
    for key in ("norms", "exit_gate", "rope", "attention_bias", "weights",
                "dtype"):
        assert ARCH["assumed"][key], key


def test_engine_block_is_what_the_sizing_rules_gave():
    e, sizing = ARCH["engine"], ARCH["sizing"]
    assert (e["page_size"], e["max_len"], e["prefix_cache"]) == (
        16, 1536, True)
    assert (e["seq_buckets"], e["batch_buckets"]) == ([128, 256, 512],
                                                      [1, 4])
    assert sizing["chosen"] == {"num_pages": e["num_pages"],
                                "slots": e["slots"]}
    assert "looped_pool.py" in sizing["script"]
    programs = sizing["programs_gib"]
    budget = 16_909_336_064 * 0.9 / 2 ** 30
    page = 2 * 192 * 16 * 2048 * 2 / 2 ** 30
    fullest = max(programs["decode"]["total"],
                  programs["prefill_4x512"]["total"])
    assert fullest <= budget < fullest + page       # the MOST pages
    house = sizing["house_monte_carlo"]
    assert house[f"slots_{e['slots']}"]["overflow_share"] < 0.01 \
        <= house[f"slots_{e['slots'] + 1}"]["overflow_share"]
    # the pool is the looped model's: 1.5 MiB a position
    assert sizing["kv_bytes_per_position"] == 1_572_864
    assert e["max_len"] >= 512 + 1024


def test_program_config_is_built_from_the_file_alone():
    model, reference, config_cls = serve_family.family_modules(
        ARCH["model_type"])
    cfg = serve_family.build_config(config_cls, ARCH)
    assert config_cls.__name__ == "OuroConfig"
    assert (cfg.num_hidden_layers, cfg.total_ut_steps, cfg.head_dim,
            cfg.early_exit_threshold) == (48, 4, 128, 1)
    assert cfg.layer_types == ["full_attention"] * 48
    assert model.kv_bytes_per_position(cfg, 2) == 1_572_864
    assert model.paged_pool_shapes(cfg, 10, 16)[0] == (192, 10, 16, 2048)
    assert reference.__name__.endswith("reference_ouro")
    with open(reference.__file__) as f, open(os.path.join(
            ROOT, "paddle_tpu", "testing", "reference_ouro.py")) as g:
        assert f.read() == g.read()


def test_cell_traffic_is_the_issues_letter_for_letter(tree):
    mix = load("benchmark", "traffic", "backlog-reason.json", root=tree)
    assert mix == {
        "driver": "serve_looped",
        "prompt_len": {"law": "lognormal", "median": 128, "sigma": 0.6,
                       "min": 32, "max": 512},
        "output_len": {"law": "lognormal", "median": 384, "sigma": 0.7,
                       "min": 64, "max": 1024},
        "token_ids": {"law": "uniform"}, "block": 32, "backlog_depth": 8,
        "max_requests_per_s": 2, "ramp_s": 15}
    cell = next(c for c in load("BENCHMARK.json", root=tree)["workloads"]
                if c["name"] == CELL)
    assert cell == dict(cell, config="ouro-2.6b-serve",
                        traffic="backlog-reason", chips=1)


def test_cell_is_on_every_list_its_record_feeds_and_no_other(tree):
    manifest = load("BENCHMARK.json", root=tree)
    metrics = {m["name"]: m
               for m in manifest["per_layer"] + manifest["end_to_end"]}
    for name in FED_METRICS:
        assert CELL in metrics[name]["workloads"], name
    for name, (unit, better, source, layer) in NEW_METRICS.items():
        m = metrics[name]
        assert m["workloads"] == [CELL], name
        assert m["moves"] == "serve_tokens_per_s"
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            unit, better, source, layer), name
        assert callable(reader(name))
    listed = {n for n, m in metrics.items()
              if "workloads" not in m or CELL in m["workloads"]}
    assert listed == set(FED_METRICS) | set(NEW_METRICS) | {"setup_s"}
    # GPT's step roofline counts GPT's bytes; the set-up counter keeps
    # to the cells that reported it when this one was added
    assert CELL not in metrics["decode_step_roofline"]["workloads"]
    misses = metrics["compile.setup_misses"]["workloads"]
    assert set(ACCEPTED_BEFORE) <= set(misses) and CELL not in misses
    # appended together and in this order, behind every entry that was there
    names = [m["name"] for m in manifest["per_layer"]]
    first = names.index("decode_step_roofline.looped")
    assert names[first:first + 3] == list(NEW_METRICS)
    assert first > names.index("paged_mla_decode_roofline")


# --------------------------------------------------------------------------
# the three new readers
# --------------------------------------------------------------------------

def record(on_chip, arch=ARCH, passes=4):
    """Ten decode steps of 7 slots over 4,300 live positions, hand-made."""
    return {
        "on_chip": on_chip, "device_kind": "TPU v5 lite", "arch": arch,
        "counters": {"decode_steps": 10},
        "loop": {"loop_tokens": 70, "loop_passes": 70 * passes},
        "hist": {"decode": {"p50": 0.040}},
        "samples": [(7, 280, 4300)] * 10,
        "tail_samples": [(7, 280, 4300)] * 10,
        "weight_itemsize": 2, "kv_itemsize": 2.0,
        "kernel": {"calls": 1920, "seconds": 1920 * 60e-6},
    }


def test_passes_per_token_reads_the_counters():
    assert reader("loop.passes_per_token")(record(False)) == 4.0
    assert reader("loop.passes_per_token")(record(True, passes=3)) == 3.0
    run = record(False)
    run["loop"] = {"loop_tokens": 70, "loop_passes": 250}   # some left early
    assert reader("loop.passes_per_token")(run) == pytest.approx(250 / 70)


def test_roofline_readers_on_a_hand_made_record():
    run = record(on_chip=True)
    step = reader("decode_step_roofline.looped")(run)
    least = fb.decode_step_bytes(ARCH, 7, 4300, 2, 2) / 819e9
    assert step == pytest.approx(100 * least / 0.040)
    assert 75 < step < 85                   # 26.7 GB in 40 ms
    kern = reader("paged_attn_decode_roofline")(run)
    least = fb.paged_attn_decode_bytes(ARCH, 7, 4307, 2) / 819e9
    assert kern == pytest.approx(100 * least / 60e-6)
    assert 0 < kern < 100


def test_step_roofline_against_a_hand_count_at_the_rehearsals_size():
    """``tiny-ouro.json``: 2 layers x 2 passes, hidden 128, 4 heads x
    32, MLP 192, vocabulary 512; 4 slots over 200 live positions, bf16.
    Layers: 2 passes x 2 x (4 x 128^2 + 3 x 128 x 192 + 4 x 128) =
    559,104 parameters; head 65,536; final norm and gate 257; K/V 2 x 4
    x 128 = 1,024 values a position."""
    run = record(on_chip=True, arch=TINY, passes=2)
    run["samples"] = [(4, 20, 200)] * 10
    nbytes = 2 * (559_104 + 65_536 + 257) + (200 + 4) * 1024 * 2
    assert fb.decode_step_bytes(TINY, 4, 200, 2, 2) == nbytes == 1_667_586
    assert reader("decode_step_roofline.looped")(run) == pytest.approx(
        100 * nbytes / 819e9 / 0.040)
    assert reader("loop.passes_per_token")(run) == 2.0


@pytest.mark.parametrize("name", ["decode_step_roofline.looped",
                                  "paged_attn_decode_roofline"])
def test_chip_readers_return_none_off_the_chip(name):
    """A share of a chip's peak is never computed from a CPU run."""
    assert reader(name)(record(on_chip=False)) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_readers_return_none_where_the_program_counts_nothing(name):
    """A record without ``loop`` and ``kernel`` (a program that lacks
    the family): the line leaves the metric out, and nothing raises."""
    run = record(on_chip=True)
    run.update(loop=None, kernel=None, samples=[], tail_samples=[])
    assert reader(name)(run) is None
    del run["loop"], run["kernel"]
    assert reader(name)(run) is None


def test_the_kernels_events_are_found_by_the_name_the_kernel_gives():
    with open(os.path.join(ROOT, "paddle_tpu", "ops", "pallas",
                           "paged_attn.py")) as f:
        assert f'name="{serve_looped.KERNEL}"' in f.read()
    planes = [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
               "events": [["paged_attn_decode.7 bf16[7,1,2048]", 0.0, 6e4],
                          ["paged_mla_decode.3 bf16[64,32,512]", 1e5, 2e5],
                          ["fusion.7 bf16[7,2048]", 5e5, 9e5]]}]}]
    assert serve_family.kernel_events(
        planes, True, prefix=serve_looped.KERNEL) == {
            "calls": 1, "seconds": pytest.approx(6e-5)}


# --------------------------------------------------------------------------
# the rehearsal cell, end to end on the CPU
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def run_cell(tmp_path_factory):
    """``JAX_PLATFORMS=cpu python3 benchmark/run.py --workload
    rehearse-ouro-reason-backlog`` from the checkout."""
    cache = tmp_path_factory.mktemp("jax_cache")

    def run(trace):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(cache),
                   PYTHONPATH=ROOT + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
             "--workload", "rehearse-ouro-reason-backlog", "--seed",
             str(2**31 + 11), "--seconds", "1.5", "--trace", str(trace)],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines = proc.stdout.strip().splitlines()
        return (json.loads(lines[-1]),
                {n["phase"]: n for n in map(json.loads, lines[:-1])})
    return run


def test_rehearsal_end_to_end(run_cell):
    result, notes = run_cell(0)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    # every generated row of every sampled request, two limits
    ref = notes["reference"]
    assert ref["requests_checked"] == serve_looped.CHECKED_REQUESTS
    assert ref["rows_checked"] > 3 * ref["requests_checked"]
    assert ref["emitted_logit_gap_mean"] <= ref["gap_mean_tol"] \
        == serve_looped.EMITTED_GAP_MEAN_TOL
    assert ref["emitted_logit_gap_max"] <= ref["gap_max_tol"] \
        == serve_looped.EMITTED_GAP_MAX_TOL
    assert all(v for k, v in notes["checks"].items()
               if k not in ("phase", "t"))


def test_rehearsal_traced_reports_the_loop_and_no_chip_share(run_cell):
    """Every per-layer metric BENCHMARK.json lists for the cell but the
    two shares of a chip's peak, which are never computed from a CPU
    run; the gate's counters read the rehearsal's two passes."""
    result, notes = run_cell(1)
    assert result["correct"] is True
    off_chip = {"decode_step_roofline.looped", "paged_attn_decode_roofline"}
    got = result["metrics"]
    assert set(got) == (set(FED_METRICS) | set(NEW_METRICS)) - off_chip - {
        "serve_tokens_per_s"}
    assert got["loop.passes_per_token"] == {"value": 2.0, "unit": "passes"}
    assert result["device"]["busy_s"] > 0
    closed = notes["window_closed"]
    assert 0 < closed["spans_in_window"] < closed["ring_spans"]
