"""CPU tests of what the ``serve-kanana2-30b-backlog`` cell adds to the
benchmark: the family's operation and byte counts against hand counts,
the configuration file against the published widths, the new readers
off the chip and on a hand-made record, and the rehearsal cell walked
end to end and traced (in subprocesses, as test_benchmark_harness.py
does and for its reason; from the checkout: the cell's entries are in
the fragment ``rehearse/manifest.kanana2.json``, which run.py reads)."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.drivers import serve_family                  # noqa: E402
from benchmark.lib import flops_bytes_deepseek_v3 as fb     # noqa: E402
from grown_tree import grown_root, tree                     # noqa: E402,F401

CELL = "serve-kanana2-30b-backlog"
NEW_METRICS = ("moe.experts_touched_share", "moe.load_max_over_mean",
               "decode_step_roofline.moe_mla", "paged_mla_decode_roofline")


def load(*parts, root=ROOT):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


ARCH = load("benchmark", "configs", "kanana2-30b-a3b-serve.json")
SPAN_METRICS = ("sched.span_self_ms_per_step", "pager.span_ms_per_step",
                "step.dispatch_ms_per_step", "step.prefill_share",
                "step.readback_wait_share")


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
    # MLA: 2048*32*192 + 2048*576 + 512*32*256 + 4096*2048
    assert fb.attn_params(ARCH) == 12_582_912 + 1_179_648 + 4_194_304 \
        + 8_388_608 == 26_345_472
    assert fb.router_params(ARCH) == 2048 * 128
    assert fb.expert_params(ARCH) == 3 * 2048 * 768 == 4_718_592
    assert fb.shared_params(ARCH) == 3 * 2048 * 1536
    assert fb.dense_mlp_params(ARCH) == 3 * 2048 * 6144
    assert fb.head_params(ARCH) == 128256 * 2048
    per_moe = 26_345_472 + 262_144 + 9_437_184 + 128 * 4_718_592
    assert round(per_moe / 1e6, 1) == 640.0
    assert fb.total_params(ARCH) == 7 * per_moe + (
        26_345_472 + 37_748_736) + 2 * 262_668_288
    assert round(fb.total_params(ARCH) / 1e9, 2) == 5.07
    full = dict(ARCH, num_hidden_layers=48)
    assert round(fb.total_params(full) / 1e9, 1) == 30.7


def test_decode_step_counts_touched_experts_not_all():
    all_ = fb.decode_step_weight_params(ARCH, 128)
    some = fb.decode_step_weight_params(ARCH, 100)
    assert all_ - some == 7 * 28 * fb.expert_params(ARCH)
    # everything but the embedding when every expert is touched
    assert all_ == fb.total_params(ARCH) - fb.head_params(ARCH)
    # 64 slots, 57,600 live positions, bf16: latents are 8 x 576 x 2 B
    b = fb.decode_step_bytes(ARCH, 64, 57_600, 128, 2, 2)
    assert b == 2 * all_ + (57_600 + 64) * 8 * 576 * 2
    assert 11e-3 < b / 819e9 < 12.5e-3         # the issue's 11-12 ms floor
    f = fb.decode_step_flops(ARCH, 64, 57_600)
    assert f / 197e12 < b / 819e9              # memory binds


def test_kernel_counts_one_layer():
    assert fb.mla_decode_flops(ARCH, 1000) == 2 * 32 * 1000 * (576 + 512)
    assert fb.mla_decode_bytes(ARCH, 64, 1000, 2) == 2 * (
        1000 * 576 + 64 * 32 * (576 + 512))


# --------------------------------------------------------------------------
# the configuration file and the manifest's new entries
# --------------------------------------------------------------------------

def test_config_holds_every_published_width():
    published = {
        "hidden_size": 2048, "num_attention_heads": 32,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "qk_head_dim": 192,
        "v_head_dim": 128, "kv_lora_rank": 512, "q_lora_rank": None,
        "intermediate_size": 6144, "n_routed_experts": 128,
        "moe_intermediate_size": 768, "num_experts_per_tok": 6,
        "n_shared_experts": 2, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
        "norm_topk_prob": True, "routed_scaling_factor": 2.448,
        "rms_norm_eps": 1e-6, "rope_theta": 1000000,
        "rope_interleave": True, "rope_scaling": None,
        "vocab_size": 128256, "tie_word_embeddings": False,
        "first_k_dense_replace": 1, "moe_layer_freq": 1,
        "max_position_embeddings": 32768, "model_type": "deepseek_v3"}
    for key, value in published.items():
        assert ARCH[key] == value, key
    assert ARCH["reduced"] == ["num_hidden_layers"]
    assert ARCH["num_hidden_layers"] == 8
    assert ARCH["published_num_hidden_layers"] == 48
    for block in ("source", "assumed", "deployment", "sizing", "engine"):
        assert ARCH[block], block
    e = ARCH["engine"]
    assert (e["slots"], e["max_len"]) == (64, 2048)
    assert e["max_len"] % e["page_size"] == 0
    assert ARCH["sizing"]["chosen"]["num_pages"] == e["num_pages"]


def test_program_config_is_built_from_the_file_alone():
    model, reference, config_cls = serve_family.family_modules(
        ARCH["model_type"])
    cfg = serve_family.build_config(config_cls, ARCH)
    assert (cfg.num_hidden_layers, cfg.kv_lora_rank,
            cfg.n_routed_experts) == (8, 512, 128)
    assert model.kv_bytes_per_position(cfg, 2) == 8 * 576 * 2
    assert reference.__name__.endswith("reference_deepseek_v3")


def test_cell_traffic_is_the_issues_letter_for_letter(tree):
    mix = load("benchmark", "traffic", "backlog-256out.json", root=tree)
    assert mix["prompt_len"] == {"law": "lognormal", "median": 512,
                                 "sigma": 0.6, "min": 64, "max": 1024}
    assert mix["output_len"] == {"law": "lognormal", "median": 256,
                                 "sigma": 0.7, "min": 32, "max": 1024}
    assert mix["token_ids"] == {"law": "uniform"}
    assert (mix["block"], mix["backlog_depth"], mix["ramp_s"]) == (32, 8, 15)
    cell = next(c for c in load("BENCHMARK.json", root=tree)["workloads"]
                if c["name"] == CELL)
    assert cell == dict(cell, config="kanana2-30b-a3b-serve",
                        traffic="backlog-256out", chips=1)


def test_cell_reports_the_accepted_serving_metrics_its_record_feeds(tree):
    """The cell is ON the list of every serving metric its record feeds
    (other cells may be too), off GPT's step roofline, which counts
    GPT's bytes, and the family's own four readers are listed for it."""
    manifest = load("BENCHMARK.json", root=tree)

    def cells(name):
        return next(m for m in manifest["per_layer"] + manifest["end_to_end"]
                    if m["name"] == name).get("workloads")
    for name in ("serve_tokens_per_s", "sched.slot_occupancy",
                 "sched.host_ms_per_step", "pager.pool_fill_peak",
                 "pager.preempted_share",
                 "step.decode_ms_p50") + SPAN_METRICS:
        assert CELL in cells(name), name
    assert CELL not in cells("decode_step_roofline")
    listed = {m["name"]: m for m in manifest["per_layer"]}
    for name, (unit, better, source, layer) in {
            "moe.experts_touched_share":
                ("%", "higher", "program_counter", "jitted steps"),
            "moe.load_max_over_mean":
                ("ratio", "lower", "program_counter", "jitted steps"),
            "decode_step_roofline.moe_mla":
                ("%", "higher", "program_span", "kernels"),
            "paged_mla_decode_roofline":
                ("%", "higher", "device_trace", "kernels")}.items():
        m = listed[name]
        assert CELL in m["workloads"] and m["moves"] == "serve_tokens_per_s"
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            unit, better, source, layer), name
        assert callable(reader(name))


# --------------------------------------------------------------------------
# the new readers
# --------------------------------------------------------------------------

def record(on_chip):
    """Two decode steps of 7 expert layers, hand-made."""
    return {
        "on_chip": on_chip, "device_kind": "TPU v5 lite", "arch": ARCH,
        "counters": {"decode_steps": 2},
        "moe": {"moe_assignments": 2 * 7 * 384,
                "moe_experts_touched": 2 * 7 * 96,
                "moe_max_expert_load": 2 * 7 * 9},
        "hist": {"decode": {"p50": 0.020}},
        "samples": [(64, 900, 57_600)] * 2,
        "tail_samples": [(64, 900, 57_600)] * 2,
        "weight_itemsize": 2, "kv_itemsize": 2.0,
        "kernel": {"calls": 16, "seconds": 16 * 200e-6},
    }


def test_counter_readers_on_a_hand_made_record():
    run = record(on_chip=False)
    assert reader("moe.experts_touched_share")(run) == 75.0
    assert reader("moe.load_max_over_mean")(run) == 3.0


def test_roofline_readers_on_a_hand_made_record():
    run = record(on_chip=True)
    step = reader("decode_step_roofline.moe_mla")(run)
    least = fb.decode_step_bytes(ARCH, 64, 57_600, 96, 2, 2) / 819e9
    assert step == pytest.approx(100 * least / 0.020)
    assert 0 < step < 100
    kern = reader("paged_mla_decode_roofline")(run)
    least = fb.mla_decode_bytes(ARCH, 64, 57_664, 2) / 819e9
    assert kern == pytest.approx(100 * least / 200e-6)


@pytest.mark.parametrize("name", ["decode_step_roofline.moe_mla",
                                  "paged_mla_decode_roofline"])
def test_chip_readers_return_none_off_the_chip(name):
    """A share of a chip's peak is never computed from a CPU run."""
    assert reader(name)(record(on_chip=False)) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_readers_return_none_where_the_program_counts_nothing(name):
    """The parent's record has no ``moe`` and no ``kernel``: the line
    leaves the metric out, and nothing raises."""
    run = record(on_chip=True)
    run.update(moe=None, kernel=None, samples=[], tail_samples=[])
    assert reader(name)(run) is None


def test_kernel_events_are_counted_by_name_on_device_planes_only():
    planes = [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["paged_mla_decode.3 bf16[64,32,512] custom-call", 0.0, 2e5],
            ["paged_mla_decode.4 bf16[64,32,512] custom-call", 3e5, 1e5],
            ["fusion.7 bf16[64,2048]", 5e5, 9e5]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["paged_mla_decode", 0.0, 7e5]]}]}]
    assert serve_family.kernel_events(planes, True) == {
        "calls": 2, "seconds": pytest.approx(3e-4)}
    assert serve_family.kernel_events(planes, False) is None
    assert serve_family.kernel_events(planes[1:], True) is None


def test_emitted_gaps_cover_every_generated_row_and_see_a_wrong_token():
    """The comparison that decides ``correct``, on the rehearsal's toy:
    tokens the reference itself would emit read 0 at every generated
    position; one swapped for the reference's WORST token reads far
    beyond the limit on the worst row, in that row alone."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    tiny = load("benchmark", "rehearse", "configs", "tiny-kanana2.json")
    model, reference, config_cls = serve_family.family_modules(
        tiny["model_type"])
    cfg = serve_family.build_config(config_cls, tiny)
    hp = dataclasses.asdict(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    samples = []
    for n_prompt, n_gen in ((9, 5), (20, 12)):
        history = rng.integers(0, cfg.vocab_size, n_prompt + n_gen).astype(
            np.int32)
        for pos in range(n_prompt, n_prompt + n_gen):   # greedy, by the ref
            logits = reference.logits(
                jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                       params), jnp.asarray(history), hp)
            history[pos] = int(np.argmax(np.asarray(logits[pos - 1])))
        samples.append((history, history[n_prompt:].copy()))
    gaps, took = serve_family.emitted_logit_gaps(reference, params, hp,
                                                 samples, width=64, most=16)
    assert len(gaps) == 5 + 12 and len(took) == 2
    assert max(gaps) < 1e-3
    history, emitted = samples[1]
    logits = np.asarray(reference.logits(
        jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params),
        jnp.asarray(history), hp))
    emitted[7] = int(np.argmin(logits[20 + 7 - 1]))
    gaps, _ = serve_family.emitted_logit_gaps(
        reference, params, hp, [(history, emitted)], width=64, most=16)
    assert gaps[7] > serve_family.GAP_MAX_TOL
    assert max(g for i, g in enumerate(gaps) if i != 7) < 1e-3


# --------------------------------------------------------------------------
# the rehearsal cell, end to end on the CPU
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def run_cell(tmp_path_factory):
    """The rehearsal from the checkout, as the other two run:
    ``JAX_PLATFORMS=cpu python3 benchmark/run.py --workload
    rehearse-kanana2-backlog``."""
    cache = tmp_path_factory.mktemp("jax_cache")

    def run(trace):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(cache),
                   PYTHONPATH=ROOT + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
             "--workload", "rehearse-kanana2-backlog", "--seed",
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
    # every generated position of every sampled request, two limits
    ref = notes["reference"]
    assert ref["requests_checked"] >= 4
    assert ref["rows_checked"] > 3 * ref["requests_checked"]
    assert ref["rows_off_share"] <= ref["off_share_tol"] == 0.22
    assert ref["emitted_logit_gap_max"] <= ref["gap_max_tol"] == 3.0
    assert all(v for k, v in notes["checks"].items()
               if k not in ("phase", "t"))


def test_rehearsal_traced_reports_the_counters_and_no_chip_share(run_cell):
    """Every per-layer metric BENCHMARK.json lists for the cell, but the
    shares of a chip's peak: those are never computed from a CPU run
    (their readers return None and the line leaves them out)."""
    result, notes = run_cell(1)
    assert result["correct"] is True
    manifest = load("BENCHMARK.json")
    listed = {m["name"] for m in manifest["per_layer"]
              if "workloads" not in m or CELL in m["workloads"]}
    off_chip = {"decode_step_roofline.moe_mla", "paged_mla_decode_roofline"}
    assert off_chip <= listed
    got = result["metrics"]
    assert set(got) <= listed and not set(got) & off_chip
    assert {"moe.experts_touched_share", "moe.load_max_over_mean",
            "sched.host_ms_per_step", "compile.setup_misses"} \
        | set(SPAN_METRICS) <= set(got)
    assert result["device"]["busy_s"] > 0
    assert 0 < got["moe.experts_touched_share"]["value"] <= 100
    assert got["moe.load_max_over_mean"]["value"] >= 1.0
    assert got["moe.load_max_over_mean"]["unit"] == "ratio"
    assert "family_layer_metrics" not in notes
    # how far the window is from wrapping the program's span ring
    closed = notes["window_closed"]
    assert 0 < closed["spans_in_window"] < closed["ring_spans"]
