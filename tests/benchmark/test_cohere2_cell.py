"""CPU tests of what the ``serve-command-a-plus-rag-backlog`` cell adds to
the benchmark: the family's operation and byte counts against hand
counts and ``init_params``' own leaves, the configuration
file against the published config, the three new readers on hand-made
records, the cell driver's sample and its check rows, the reference's planted
controls, and the rehearsal cell walked end to end, traced and not (in
subprocesses, as test_benchmark_harness.py does and for its reason; the
cell's entries are in the fragment ``rehearse/manifest.cohere2.json``).
Manifest tests ask properties only — a name is ON a list — and run on
the checkout and on the grown tree (fixture ``tree``)."""
import dataclasses
import importlib.util
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.drivers import serve_cohere2, serve_family  # noqa: E402
from benchmark.lib import flops_bytes_cohere2_moe as fb    # noqa: E402
from grown_tree import grown_root, tree                    # noqa: E402,F401

CELL = "serve-command-a-plus-rag-backlog"
REHEARSAL = "rehearse-cohere2-rag-backlog"
NEW_METRICS = {
    "paged_attn_decode_roofline.gqa": ("%", "higher", "device_trace",
                                       "kernels"),
    "decode_step_roofline.cohere2": ("%", "higher", "program_span",
                                     "kernels"),
    "attn.window_skipped_share": ("%", "higher", "program_counter",
                                  "jitted steps"),
    "moe_grouped_matmul_roofline.cohere2": ("%", "higher", "device_trace",
                                            "kernels"),
    "moe.experts_touched_share.cohere2": ("%", "higher", "program_counter",
                                          "jitted steps"),
    "moe.load_max_over_mean.cohere2": ("ratio", "lower", "program_counter",
                                       "jitted steps")}
FED_METRICS = (
    "serve_tokens_per_s", "sched.slot_occupancy", "sched.host_ms_per_step",
    "pager.pool_fill_peak", "pager.preempted_share", "step.decode_ms_p50",
    "sched.span_self_ms_per_step", "pager.span_ms_per_step",
    "step.dispatch_ms_per_step", "step.prefill_share",
    "step.readback_wait_share", "compile.setup_misses",
    "prefill.device_share", "prefill.padded_rows_share",
    "prefill.device_us_per_prompt_token")
# what reads keys this configuration does not have, or another family's
NOT_FED = ("decode_step_roofline", "moe.load_max_over_mean",
           "moe.experts_touched_share", "paged_attn_decode_roofline")
OFF_CHIP = {"paged_attn_decode_roofline.gqa", "decode_step_roofline.cohere2",
            "moe_grouped_matmul_roofline.cohere2"}
# the catalog's config of command-a-plus-05-2026 (source_url's
# config.json), its numbers and flags; layer_types and rope_parameters
# are compared whole below
PUBLISHED = {
    "attention_bias": False, "expert_selection_fn": "sigmoid",
    "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 4096, "layer_norm_eps": 1e-05,
    "layer_switch": 4, "logit_scale": 1, "max_position_embeddings": 200000,
    "norm_topk_prob": True, "num_attention_heads": 128, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 8, "num_shared_experts": 4,
    "order_of_interleaved_layers": "local_attn_first",
    "position_embedding_type": "rope_gptj",
    "prefix_dense_intermediate_size": 16384,
    "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
    "rope_theta": 50000, "rotary_pct": 1,
    "shared_expert_combination_strategy": "average", "sliding_window": 4096,
    "tf_legacy_loss": False, "tie_word_embeddings": True,
    "use_embedding_sharing": True, "use_gated_activation": True,
    "use_parallel_block": True, "use_parallel_embedding": False,
    "use_qk_norm": False, "vocab_size": 262144}
REDUCED = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 32768}


def load(*parts, root=ROOT):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


ARCH = load("benchmark", "configs", "command-a-plus-serve.json")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"),
        os.path.join(ROOT, "benchmark", "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# --------------------------------------------------------------------------
# counts, against hand counts and the program's own leaves
# --------------------------------------------------------------------------

def test_parameter_counts_match_the_hand_counts():
    # Wq 67.11M, Wk 4.19M, Wv 4.19M, Wo 67.11M
    assert fb.attn_params(ARCH) == 4096 * (16384 + 2 * 1024) \
        + 16384 * 4096 == 142_606_336
    assert fb.expert_params(ARCH) == 3 * 4096 * 4096 == 50_331_648
    assert 4 * fb.expert_params(ARCH) == 201_326_592
    assert fb.router_params(ARCH) == 4096 * 128
    assert round(fb.outside_experts_params(ARCH) / 1e6, 2) == 344.46
    assert round(fb.total_params(ARCH) / 1e9, 3) == 4.733
    whole = dict(ARCH, num_hidden_layers=32, num_experts=128,
                 vocab_size=262144)
    assert round(fb.total_params(whole) / 1e9, 1) == 218.3


def test_counts_agree_with_the_programs_own_leaves():
    """The matmul leaves of ``init_params`` at the cell's size (shapes
    only) are the count's, plus nothing: the embedding is the head."""
    import jax
    model, _, config_cls = serve_family.family_modules(ARCH["model_type"])
    cfg = serve_family.build_config(config_cls, ARCH)
    shapes = jax.eval_shape(lambda k: model.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    # a layer's matrices are 3-D once stacked; its gains 2-D
    matmul = sum(x.size for path, x in
                 jax.tree_util.tree_leaves_with_path(shapes)
                 if x.ndim >= 2 + (jax.tree_util.keystr(path)
                                   .startswith(("['window", "['full"))))
    assert matmul == fb.total_params(ARCH)


def test_decode_step_and_kernel_counts():
    all_ = fb.decode_step_weight_params(ARCH, 16)
    some = fb.decode_step_weight_params(ARCH, 12)
    assert all_ - some == 4 * 4 * fb.expert_params(ARCH)
    assert all_ == fb.total_params(ARCH)
    # 64 slots at the traffic's mean final length, about half of it
    # read: 64 x 2,400 rows x 4 layers x 4,096 B
    rows = 64 * 2400 * 4
    b = fb.decode_step_bytes(ARCH, 64, rows, 15.5, 2, 2)
    assert b == 2 * fb.decode_step_weight_params(ARCH, 15.5) + (
        rows + 64 * 4) * 4096
    f = fb.decode_step_flops(ARCH, 64, rows, 64 * 8 / 8)
    assert f / 197e12 < b / 819e9              # memory binds
    assert fb.paged_attn_decode_bytes(ARCH, 4, 1000, 2) == (
        1000 * 4096 + 4 * 2 * 64 * 16384 * 2)
    assert fb.paged_attn_decode_flops(ARCH, 1000) == 4 * 16384 * 1000


# --------------------------------------------------------------------------
# the configuration file and the manifest's new entries
# --------------------------------------------------------------------------

def test_config_holds_every_published_number_but_the_reduced():
    for key, value in PUBLISHED.items():
        assert ARCH[key] == REDUCED.get(key, value), key
    assert sorted(ARCH["reduced"]) == sorted(REDUCED)
    assert ARCH["layer_types"] == (["sliding_attention"] * 3
                                   + ["full_attention"]) * 8
    assert ARCH["rope_parameters"] == {"rope_theta": 50000,
                                       "rope_type": "default"}
    assert (ARCH["published_num_hidden_layers"],
            ARCH["published_num_experts"],
            ARCH["published_vocab_size"]) == (32, 128, 262144)
    assert ARCH["num_experts_total"] == 128
    assert ARCH["model_type"] == "cohere2_moe"
    for block in ("source", "assumed", "deployment", "sizing", "engine"):
        assert ARCH[block], block
    assert "8 chips" in ARCH["deployment"]
    e = ARCH["engine"]
    assert e["max_len"] == 8192 and e["max_len"] % e["page_size"] == 0
    assert max(e["seq_buckets"]) == ARCH["sliding_window"]
    chosen = ARCH["sizing"]["chosen"]
    assert (chosen["slots"], chosen["num_pages"]) == (e["slots"],
                                                      e["num_pages"])
    assert e["slots"] % 8 == 0


def test_program_config_is_built_from_the_file_alone():
    model, reference, config_cls = serve_family.family_modules(
        ARCH["model_type"])
    cfg = serve_family.build_config(config_cls, ARCH)
    share = cfg.expert_share
    assert (share.first, share.held, share.routed, share.zero,
            share.width) == (0, 16, 128, 0, 128)
    assert (cfg.num_layers, cfg.periods, cfg.q_group) == (4, 1, 16)
    assert model.shared_down_scale(cfg) == 0.25
    assert model.kv_bytes_per_position(cfg, 2) == 4096
    assert reference.__name__.endswith("reference_cohere2_moe")


def test_cell_traffic_is_the_rag_backlog_letter_for_letter(tree):
    mix = load("benchmark", "traffic", "backlog-rag-4k-in.json", root=tree)
    assert mix["prompt_len"] == {"law": "lognormal", "median": 3072,
                                 "sigma": 0.5, "min": 512, "max": 4096}
    assert mix["output_len"] == {"law": "lognormal", "median": 1536,
                                 "sigma": 0.6, "min": 256, "max": 4096}
    assert mix["token_ids"] == {"law": "uniform"}
    assert (mix["block"], mix["backlog_depth"], mix["ramp_s"],
            mix["max_requests_per_s"]) == (32, 8, 30, 6)
    assert mix["driver"] == "serve_cohere2"
    cell = next(c for c in load("BENCHMARK.json", root=tree)["workloads"]
                if c["name"] == CELL)
    assert cell == dict(cell, config="command-a-plus-serve",
                        traffic="backlog-rag-4k-in", chips=1)
    assert "deployment share" in cell["why"] and "1/8" in cell["why"]


def test_cell_is_on_every_list_its_record_feeds(tree):
    manifest = load("BENCHMARK.json", root=tree)
    metrics = {m["name"]: m
               for m in manifest["per_layer"] + manifest["end_to_end"]}
    for name in FED_METRICS:
        assert CELL in metrics[name]["workloads"], name
    for name in NOT_FED:
        assert CELL not in metrics[name]["workloads"], name
    for name, (unit, better, source, layer) in NEW_METRICS.items():
        m = metrics[name]
        assert CELL in m["workloads"], name
        assert m["moves"] == "serve_tokens_per_s"
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            unit, better, source, layer), name
        assert callable(reader(name))


# --------------------------------------------------------------------------
# the readers
# --------------------------------------------------------------------------

def record(on_chip):
    """Two decode steps of 4 layers at 64 slots, hand-made."""
    return {
        "on_chip": on_chip, "device_kind": "TPU v5 lite", "arch": ARCH,
        "counters": {"decode_steps": 2},
        "moe": {"moe_assignments": 2 * 4 * 64, "moe_experts_touched":
                2 * 4 * 15, "moe_max_expert_load": 2 * 4 * 9,
                "moe_remote_assignments": 2 * 4 * 448},
        "attn": {"attn_window_rows": 2 * 3 * 64 * 3500,
                 "attn_full_rows": 2 * 64 * 4700},
        "tail_attn": {"attn_window_rows": 2 * 3 * 64 * 3500,
                      "attn_full_rows": 2 * 64 * 4700, "decode_steps": 2},
        "hist": {"decode": {"p50": 0.024}},
        "samples": [(64, 5000, 300_000)] * 2,
        "weight_itemsize": 2, "kv_itemsize": 2.0,
        "kernel": {"calls": 8, "seconds": 8 * 1.5e-3},
        "grouped_matmul": {"calls": 16, "seconds": 16 * 1.2e-3,
                           "rows": 512},
    }


def test_readers_on_a_hand_made_record():
    run = record(on_chip=True)
    assert reader("attn.window_skipped_share")(run) == pytest.approx(
        100 * (1 - 3500 / 4700))
    rows = 64 * (3 * 3500 + 4700)
    step = reader("decode_step_roofline.cohere2")(run)
    least = fb.decode_step_bytes(ARCH, 64, rows, 15, 2, 2) / 819e9
    assert step == pytest.approx(100 * least / 0.024)
    assert 0 < step < 100
    kern = reader("paged_attn_decode_roofline.gqa")(run)
    least = fb.paged_attn_decode_bytes(ARCH, 8, 2 * rows, 2) / 819e9
    assert kern == pytest.approx(100 * least / (8 * 1.5e-3))
    assert 0 < kern < 100
    # 64 assignments a layer a step to 15 of the 16 held experts, the
    # fullest taking 9
    assert reader("moe.experts_touched_share.cohere2")(run) == \
        pytest.approx(100 * 15 / 16)
    assert reader("moe.load_max_over_mean.cohere2")(run) == \
        pytest.approx(9 / (64 / 16))
    gmm = reader("moe_grouped_matmul_roofline.cohere2")(run)
    least = fb.grouped_matmul_bytes(ARCH, 15, 64, 2) / 819e9
    assert gmm == pytest.approx(100 * least * 8 / (16 * 1.2e-3))
    assert 0 < gmm < 100


def test_a_window_that_read_the_whole_history_reads_zero():
    run = record(on_chip=False)
    run["attn"] = {"attn_window_rows": 3 * 1000, "attn_full_rows": 1000}
    assert reader("attn.window_skipped_share")(run) == 0.0


@pytest.mark.parametrize("name", sorted(OFF_CHIP))
def test_chip_readers_return_none_off_the_chip(name):
    assert reader(name)(record(on_chip=False)) is None


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_readers_return_none_where_the_program_counts_nothing(name):
    """A record without the counters or events (what a program that
    lacks them gives): the line leaves the metric out, nothing raises."""
    run = record(on_chip=True)
    run.update(moe=None, attn=None, tail_attn=None, kernel=None,
               grouped_matmul=None, samples=[])
    assert reader(name)(run) is None


# --------------------------------------------------------------------------
# serve_cohere2's sample, its check rows and the reference's controls
# --------------------------------------------------------------------------

def test_the_sample_takes_requests_past_the_window_first():
    def item(prompt, emitted):
        req = types.SimpleNamespace(tokens=[0] * emitted)
        return types.SimpleNamespace(prompt=np.zeros(prompt), req=req)
    finished = [item(100, 50) for _ in range(20)] + [
        item(3000, 2000) for _ in range(4)]
    picked = serve_cohere2.checked_sample(finished, 4096, 2**31 + 1, 6, 3)
    assert len(picked) == 6
    assert sum(len(it.prompt) + len(it.req.tokens) > 4096
               for it in picked) == 3
    few = serve_cohere2.checked_sample(finished[:20] + finished[-1:], 4096,
                                       7, 6, 3)
    assert sum(len(it.prompt) > 1000 for it in few) == 1


def test_the_check_rows_lie_past_the_window_and_every_control_moves():
    """``reference_readings`` at the toy's size: the attention check's
    rows are decode rows past the window, the expert check's any decode
    rows, a row-shaped call each sample; the proper reference matches
    the program, each control moves the attention check, and the int8
    control the expert check (float32 toy: the controls' rounding is
    all the difference)."""
    import jax
    from paddle_tpu.models import cohere2_moe as cm
    from paddle_tpu.testing import reference_cohere2_moe as ref
    cfg = cm.cohere2_moe_tiny()
    hp = dataclasses.asdict(cfg)
    params = cm.init_params(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(0)
    samples = []
    for n_prompt, n in ((6, 20), (4, 3), (10, 30)):
        history = rng.integers(0, 512, n_prompt + n).astype(np.int32)
        samples.append((history, history[n_prompt:], n_prompt))
    seen, seen_h = [], []

    def check(rows, h, a):
        seen.append(np.asarray(rows))
        return 0.0

    def experts(h, fn):
        seen_h.append(h.shape)
        return 0, h.shape[1], 0.0, 0.0

    gaps, _, errs, exp = serve_cohere2.reference_readings(
        ref, params, hp, samples, 64, 32, 5, 2**31 + 7, check, experts)
    assert len(gaps) == 20 + 3 + 30
    # the second sample ends inside the window: no attention rows
    assert len(errs) == 2 and [len(r) for r in seen] == [5, 5]
    assert all(r.min() >= max(cfg.sliding_window, p) for r, p in
               zip(seen, (6, 10)))
    assert seen[0].max() <= 6 + 20 - 2
    assert len(exp) == 3 and seen_h == [(4, 5, cfg.hidden_size)] * 3
    check = serve_cohere2.attention_check(cm, cfg, params, 16)
    experts = serve_cohere2.expert_check(cm, ref, cfg, params)
    with jax.default_matmul_precision("highest"):
        proper = serve_cohere2.reference_readings(
            ref, params, hp, samples, 64, 32, 5, 3, check, experts)
    assert max(proper[2]) < 1e-5
    assert sum(e[0] for e in proper[3]) == 0
    assert max(e[3] for e in proper[3]) < 1e-5
    for control in ref.CONTROLS:
        with jax.default_matmul_precision("highest"):
            got = serve_cohere2.reference_readings(
                ref, params, hp, samples, 64, 32, 5, 3, check, experts,
                control)
        assert max(got[2]) > 100 * max(proper[2]), control
        if control == "int8":
            assert max(e[2] for e in got[3]) > 100 * max(
                e[3] for e in proper[3])


def test_the_stored_kv_check_reads_the_engines_rings_and_pool():
    """``live_kv`` reads live slots' rings and pool rows out of the
    engine between two steps, past the window (the rings wrapped), and
    ``kv_readings`` finds them at the reference's K/V (float32 toy),
    where a store rounded to int8 and a ring row written one row on read
    far off."""
    import jax
    from paddle_tpu.inference.serving import PagedServingEngine, Request
    from paddle_tpu.models import cohere2_moe as cm
    from paddle_tpu.testing import reference_cohere2_moe as ref
    cfg = cm.cohere2_moe_tiny()
    params = cm.init_params(cfg, jax.random.PRNGKey(4))
    rng = np.random.default_rng(1)
    items = []
    with jax.default_matmul_precision("highest"):
        eng = PagedServingEngine(
            (params, cfg), slots=3, max_len=64, page_size=4, num_pages=40,
            seq_buckets=(8,), batch_buckets=(1,))
        for i, n in enumerate((5, 8, 3)):
            prompt = rng.integers(0, 512, n).astype(np.int32)
            items.append(types.SimpleNamespace(
                idx=i, prompt=prompt, req=Request(prompt, 40)))
            eng.submit(items[-1].req)
        for _ in range(20):
            eng.step()
        held = serve_cohere2.live_kv(eng, items, cfg.sliding_window, 2)
    assert len(held) == 2
    W, C = cfg.sliding_window, cfg.kv_width
    for history, ring_k, ring_v, pool_k, pool_v in held:
        assert len(history) > 2 * W
        assert ring_k.shape == ring_v.shape == (3, W, C)
        assert pool_k.shape == pool_v.shape == (1, len(history), C)
    assert list(serve_cohere2.ring_positions(11, 8)) == [
        8, 9, 10, 3, 4, 5, 6, 7]
    hp = dataclasses.asdict(cfg)
    proper = serve_cohere2.kv_readings(ref, params, hp, held, 64)
    assert proper["kv_rows_checked"] == sum(
        3 * W + len(h[0]) for h in held)
    assert proper["kv_err_first"] < 1e-5 and proper["kv_err_mean"] < 1e-5
    assert proper["kv_err_first"] == proper["kv_err_by_layer"][0]
    for store in serve_cohere2.STORE_CONTROLS:
        got = serve_cohere2.kv_readings(ref, params, hp, held, 64,
                                        store=store)
        for key in ("kv_err_first", "kv_err_mean"):
            assert got[key] > 100 * proper[key], (store, key)
    assert serve_cohere2.kv_readings(ref, params, hp, [], 64) is None


# --------------------------------------------------------------------------
# the rehearsal cell, end to end on the CPU
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def run_cell(tmp_path_factory):
    """The rehearsal from the checkout: ``JAX_PLATFORMS=cpu python3
    benchmark/run.py --workload rehearse-cohere2-rag-backlog``."""
    cache = tmp_path_factory.mktemp("jax_cache")
    done = {}

    def run(trace):
        if trace not in done:
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       JAX_COMPILATION_CACHE_DIR=str(cache),
                       PYTHONPATH=ROOT + os.pathsep
                       + os.environ.get("PYTHONPATH", ""))
            proc = subprocess.run(
                [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
                 "--workload", REHEARSAL, "--seed", str(2**31 + 42),
                 "--seconds", "1.5", "--trace", str(trace)],
                cwd=ROOT, env=env, capture_output=True, text=True,
                timeout=600)
            assert proc.returncode == 0, proc.stderr[-2000:]
            lines = proc.stdout.strip().splitlines()
            notes = [json.loads(n) for n in lines[:-1]]
            done[trace] = (json.loads(lines[-1]), notes)
        return done[trace]
    return run


def test_rehearsal_end_to_end(run_cell):
    result, notes = run_cell(0)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    ref = next(n for n in notes if n["phase"] == "reference")
    assert ref["requests_checked"] == 6
    assert sum(n > 16 for n in ref["final_lengths"]) >= 3
    assert ref["rows_checked"] > 3 * ref["requests_checked"]
    assert ref["attn_rows_checked"] >= 4
    assert ref["rows_off_share"] <= ref["off_share_tol"]
    assert ref["attn_err_max"] <= ref["attn_tol"]
    assert ref["router_off_share"] <= ref["router_tol"]
    assert ref["expert_err_max"] <= ref["expert_tol"]
    assert ref["expert_rows_checked"] == 6 * 4 * 4
    assert ref["kv_err_first"] <= ref["kv_first_tol"]
    assert ref["kv_err_mean"] <= ref["kv_mean_tol"]
    assert len(ref["kv_lengths"]) == 2 and min(ref["kv_lengths"]) > 16
    assert not [n for n in notes if n["phase"] == "control"]


def test_rehearsal_traced_reports_the_counters_and_no_chip_share(run_cell):
    result, notes = run_cell(1)
    assert result["correct"] is True
    manifest = load("BENCHMARK.json")
    listed = {m["name"] for m in manifest["per_layer"]
              if "workloads" not in m or CELL in m["workloads"]}
    assert OFF_CHIP <= listed
    got = result["metrics"]
    assert set(got) <= listed and not set(got) & OFF_CHIP
    assert {"attn.window_skipped_share", "prefill.device_share",
            "sched.host_ms_per_step", "moe.experts_touched_share.cohere2",
            "moe.load_max_over_mean.cohere2"} <= set(got)
    # a window of 16 against histories up to 112 positions
    assert 0 < got["attn.window_skipped_share"]["value"] < 100
    assert result["device"]["busy_s"] > 0
