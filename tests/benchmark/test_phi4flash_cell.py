"""CPU tests of what the ``serve-phi4flash-reason-backlog`` cell adds to
the benchmark: the hybrid family's operation and byte counts against
hand counts and against ``init_params``' own leaves, the configuration
file against the published config, the three new readers on hand-made
records (and on another family's), the sampling rule of the reference
check, and the rehearsal cell walked end to end and traced (in
subprocesses, as test_benchmark_harness.py does and for its reason)."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.drivers import serve_family, serve_hybrid    # noqa: E402
from benchmark.lib import flops_bytes_phi4flash as fb       # noqa: E402
from grown_tree import grown_root, tree                     # noqa: E402,F401

CELL = "serve-phi4flash-reason-backlog"
NEW_METRICS = {
    "decode_step_roofline.hybrid": ("%", "higher", "program_span",
                                    "kernels"),
    "paged_diff_attn_decode_roofline": ("%", "higher", "device_trace",
                                        "kernels"),
    "prefill.cross_rows_share": ("%", "lower", "program_counter",
                                 "jitted steps")}
FED_METRICS = (
    "serve_tokens_per_s", "sched.slot_occupancy", "sched.host_ms_per_step",
    "pager.pool_fill_peak", "pager.preempted_share", "step.decode_ms_p50",
    "sched.span_self_ms_per_step", "pager.span_ms_per_step",
    "step.dispatch_ms_per_step", "step.prefill_share",
    "step.readback_wait_share", "compile.setup_misses")
ACCEPTED_BEFORE = ("train-1.3b-pretrain-2k", "serve-1.3b-backlog",
                   "serve-kanana2-30b-backlog",
                   "serve-ouro-2.6b-reason-backlog")


def load(*parts, root=ROOT):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


ARCH = load("benchmark", "configs", "phi4-mini-flash-serve.json")
TINY = load("benchmark", "rehearse", "configs", "tiny-phi4flash.json")
OURO = load("benchmark", "configs", "ouro-2.6b-serve.json")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"),
        os.path.join(ROOT, "benchmark", "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# --------------------------------------------------------------------------
# counts, against the issue's hand counts and the program's own leaves
# --------------------------------------------------------------------------

def test_parameter_counts_match_the_hand_counts():
    assert fb.layer_kinds(ARCH) == (["ssm", "window"] * 8 + ["ssm", "full"]
                                    + ["gmu", "cross"] * 7)
    assert fb.mlp_params(ARCH) == 3 * 2560 * 10240 + 2 * 2560
    # in 26.21M, out 13.11M, x and dt projections 1.81M, the rest 0.11M
    assert fb.ssm_params(ARCH) == (
        2560 * 10240 + 5120 * 2560 + 5120 * 192 + 160 * 5120 + 5120
        + 4 * 5120 + 5120 + 5120 * 16 + 5120 + 2 * 2560) == 41_246_720
    assert fb.attn_params(ARCH) == (2560 * 5120 + 5120 + 2560 * 2560 + 2560
                                    + 4 * 64 + 128 + 2 * 2560) == 19_673_984
    assert fb.gmu_params(ARCH) == 2 * 2560 * 5120 + 2 * 2560
    assert fb.cross_params(ARCH) == 2 * (2560 * 2560 + 2560) + 384 + 5120
    assert fb.embed_params(ARCH) == 200064 * 2560
    assert fb.total_params(ARCH) == 3_852_562_944
    assert round(fb.total_params(ARCH) / 1e6) == 3853     # the issue's 3,852M
    assert round(2 * fb.total_params(ARCH) / 1e9, 2) == 7.71      # bf16 GB


def test_parameter_count_is_init_params_leaves_by_shapes_alone():
    import jax
    import numpy as np
    model, _, config_cls = serve_family.family_modules("phi4flash")
    for arch in (ARCH, TINY):
        cfg = serve_family.build_config(config_cls, arch)
        shapes = jax.eval_shape(lambda k: model.init_params(cfg, k),
                                jax.random.PRNGKey(0))
        n = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(shapes))
        assert n == fb.total_params(arch)
        assert fb.layer_kinds(arch) == model.layer_kinds(cfg)
        assert fb.slot_state_bytes(arch, 2) == sum(
            int(np.prod(s)) * np.dtype(d).itemsize
            for s, d in model.slot_state_shapes(cfg, 1, 64))


def test_a_sequences_state_is_the_issues_table():
    assert 2 * fb.kv_values_per_position(ARCH) == 5120    # bytes, bf16
    assert fb.slot_state_bytes(ARCH, 2) == (
        8 * 512 * 5120 + 9 * (327_680 + 30_720)) == 24_197_120
    assert fb.pool_reads_a_step(ARCH) == 8      # layer 17 and 7 cross
    assert fb.kernel_calls_a_step(ARCH) == 16


def test_decode_step_bytes_against_a_hand_count():
    """176 slots over 193,600 live positions (1,100 each), every ring
    full: weights once, the pool's rows 8 times and the new row once,
    8 rings, 9 states read and written, the logits."""
    b = fb.decode_step_bytes(ARCH, 176, 193_600, 176 * 512, 2, 2)
    weights = 2 * 3_852_562_944
    pool = (8 * (193_600 + 176) + 176) * 5120
    rings = 8 * (176 * 512 + 176) * 5120
    states = 9 * 176 * 2 * (327_680 + 30_720)
    logits = 176 * 200064 * 4
    assert b == weights + pool + rings + states + logits
    assert round(b / 1e9, 1) == 20.6
    # more than half of a step's bytes are state and cache, not weights
    assert weights < b / 2
    f = fb.decode_step_flops(ARCH, 176, 193_600, 176 * 512)
    assert f == 2 * 176 * (fb.layers_params(ARCH) + 200064 * 2560) \
        + (2 * 40 * 64 + 2 * 40 * 128) * (8 * 193_776 + 8 * 176 * 512)
    assert f / 197e12 < b / 819e9              # memory binds


def test_kernel_counts_one_call():
    assert fb.paged_diff_attn_decode_flops(ARCH, 1000) == 15_360 * 1000
    assert fb.paged_diff_attn_decode_bytes(ARCH, 176, 1000, 2) == (
        1000 * 5120 + 176 * 2560 * (2 + 4))


# --------------------------------------------------------------------------
# the configuration file and the manifest's new entries
# --------------------------------------------------------------------------

def test_config_holds_every_published_key_unchanged():
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064}
    for key, value in published.items():
        assert ARCH[key] == value, key
    assert ARCH["reduced"] == []
    assert ARCH["source"] == ("https://huggingface.co/microsoft/"
                              "Phi-4-mini-flash-reasoning/blob/main/"
                              "config.json")
    for block in ("assumed", "deployment", "sizing", "engine"):
        assert ARCH[block], block
    for key in ("state_space_sizes", "norms", "attention_bias", "positions",
                "differential_attention", "layer_kinds", "mlp_gate",
                "memory", "window", "state_dtype", "weights", "dtype"):
        assert ARCH["assumed"][key], key


def test_engine_block_is_what_the_sizing_rule_gave():
    e, sizing = ARCH["engine"], ARCH["sizing"]
    assert (e["page_size"], e["max_len"], e["prefix_cache"]) == (
        64, 4096, True)
    assert (e["seq_buckets"], e["batch_buckets"]) == (
        [128, 256, 512, 1024], [1, 4])
    assert sizing["chosen"] == {"num_pages": e["num_pages"],
                                "slots": e["slots"]}
    assert "hybrid_pool.py" in sizing["script"]
    programs = sizing["programs_gib"]
    budget = 16_909_336_064 * 0.9 / 2 ** 30
    fullest = max(programs["decode"]["total"],
                  programs["prefill_4x1024"]["total"])
    # the MOST slots in steps of 8: this many fit, 8 more would not
    assert fullest <= budget < programs["one_step_more"]["fullest"]
    assert programs["one_step_more"]["slots"] == e["slots"] + 8
    assert e["slots"] % 8 == 0
    house = sizing["house_monte_carlo"][f"slots_{e['slots']}"]
    assert house["overflow_share"] < 0.01
    assert house["pool_positions"] == (e["num_pages"] - 1) * e["page_size"]
    assert sizing["kv_bytes_per_position"] == 5120
    assert sizing["slot_state_bytes"] == fb.slot_state_bytes(ARCH, 2)
    assert e["max_len"] >= 1024 + 3072
    # what the slots and the pool hold is what the file says they hold
    gib = 2 ** 30
    assert programs["slot_state"] == pytest.approx(
        e["slots"] * 24_197_120 / gib, abs=1e-3)
    assert programs["pool"] == pytest.approx(
        e["num_pages"] * 64 * 5120 / gib, abs=1e-3)
    # bf16, but the 9 state-space layers' A_log, which is float32
    assert programs["weights"] == pytest.approx(
        (2 * fb.total_params(ARCH) + 2 * 9 * 5120 * 16) / gib, abs=1e-3)


def test_program_config_is_built_from_the_file_alone():
    model, reference, config_cls = serve_family.family_modules(
        ARCH["model_type"])
    cfg = serve_family.build_config(config_cls, ARCH)
    assert config_cls.__name__ == "Phi4FlashConfig"
    assert (cfg.num_hidden_layers, cfg.sliding_window, cfg.head_dim,
            cfg.d_inner, cfg.mamba_dt_rank) == (32, 512, 64, 5120, 160)
    shape, dtype = model.slot_state_shapes(cfg, 8, 64)[2]
    # S: float32 by constant
    assert (shape, dtype.__name__) == ((9, 8, 5120, 16), "float32")
    assert model.kv_bytes_per_position(cfg, 2) == 5120
    assert model.paged_pool_shapes(cfg, 10, 64)[0] == (1, 10, 64, 1280)
    assert model.slot_state_arrays(cfg) == 4
    assert reference.__name__.endswith("reference_phi4flash")
    with open(reference.__file__) as f, open(os.path.join(
            ROOT, "paddle_tpu", "testing", "reference_phi4flash.py")) as g:
        assert f.read() == g.read()


def test_cell_traffic_is_the_issues_letter_for_letter(tree):
    mix = load("benchmark", "traffic", "backlog-reason-1k.json", root=tree)
    rate = mix.pop("max_requests_per_s")
    assert mix == {
        "driver": "serve_hybrid",
        "prompt_len": {"law": "lognormal", "median": 256, "sigma": 0.6,
                       "min": 64, "max": 1024},
        "output_len": {"law": "lognormal", "median": 1024, "sigma": 0.6,
                       "min": 128, "max": 3072},
        "token_ids": {"law": "uniform"}, "block": 32, "backlog_depth": 8,
        "ramp_s": 20}
    # at least three times the 3.8 requests/s the cell finishes (PERF.md)
    assert rate >= 12
    cell = next(c for c in load("BENCHMARK.json", root=tree)["workloads"]
                if c["name"] == CELL)
    assert cell == dict(cell, config="phi4-mini-flash-serve",
                        traffic="backlog-reason-1k", chips=1)
    entry = next(c for c in load("BENCHMARK.json", root=tree)["configs"]
                 if c["name"] == "phi4-mini-flash-serve")
    assert entry["reduced"] == [] and entry["source"] == ARCH["source"]


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
    # the other families' step and kernel shares count their own bytes
    for name in ("decode_step_roofline", "decode_step_roofline.moe_mla",
                 "decode_step_roofline.looped", "paged_attn_decode_roofline",
                 "paged_mla_decode_roofline", "loop.passes_per_token"):
        assert CELL not in metrics[name]["workloads"], name
    # appended together and in this order, behind every entry that was there
    names = [m["name"] for m in manifest["per_layer"]]
    first = names.index("decode_step_roofline.hybrid")
    assert names[first:first + 3] == list(NEW_METRICS)
    assert first > names.index("loop.passes_per_token")
    cells = [c["name"] for c in manifest["workloads"]]
    assert cells.index(CELL) > max(cells.index(c) for c in ACCEPTED_BEFORE)


# --------------------------------------------------------------------------
# the three new readers
# --------------------------------------------------------------------------

def record(on_chip, arch=ARCH, slots=176, live=193_600, ring=512):
    """Ten decode steps of ``slots`` slots over ``live`` positions, every
    ring holding ``ring`` rows, and five waves of 4 x 512: hand-made."""
    calls = 10 * fb.kernel_calls_a_step(arch)
    hybrid = {"state_steps": 10 * slots, "window_rows_read": 10 * slots * ring,
              "prefill_rows": 5 * 4 * 512, "prefill_cross_rows": 5 * 4}
    return {
        "on_chip": on_chip, "device_kind": "TPU v5 lite", "arch": arch,
        "counters": {"decode_steps": 10}, "hybrid": hybrid,
        "tail_hybrid": dict(hybrid, decode_steps=10),
        "hist": {"decode": {"p50": 0.033}},
        "samples": [(slots, 3000, live)] * 10,
        "tail_samples": [(slots, 3000, live)] * 10,
        "weight_itemsize": 2, "kv_itemsize": 2.0,
        "kernel": {"calls": calls, "seconds": 10 * 0.018},
    }


def test_cross_rows_share_reads_the_counters():
    assert reader("prefill.cross_rows_share")(record(False)) == \
        pytest.approx(100 / 512)
    run = record(True)
    run["hybrid"]["prefill_cross_rows"] = run["hybrid"]["prefill_rows"]
    assert reader("prefill.cross_rows_share")(run) == 100.0   # saving lost


def test_roofline_readers_on_a_hand_made_record():
    run = record(on_chip=True)
    step = reader("decode_step_roofline.hybrid")(run)
    least = fb.decode_step_bytes(ARCH, 176, 193_600, 176 * 512, 2, 2) / 819e9
    assert step == pytest.approx(100 * least / 0.033)
    assert 70 < step < 80                       # 20.6 GB in 33 ms
    kern = reader("paged_diff_attn_decode_roofline")(run)
    pool = fb.paged_diff_attn_decode_bytes(ARCH, 176, 193_776, 2)
    ring = fb.paged_diff_attn_decode_bytes(ARCH, 176, 176 * 512, 2)
    assert kern == pytest.approx(
        100 * (8 * pool + 8 * ring) / 819e9 / 0.018)
    assert 0 < kern < 100


def test_step_roofline_against_a_hand_count_at_the_rehearsals_size():
    """``tiny-phi4flash.json``: hidden 128, 8 x 16 query heads on 4
    key/value heads, MLP 192, inner 256, state 4, dt_rank 8, window 8,
    vocabulary 512; 4 slots over 100 live positions, every ring full,
    bf16.  A page row is 64 values: K and V 256 B a position."""
    run = record(on_chip=True, arch=TINY, slots=4, live=100, ring=8)
    mlp = 3 * 128 * 192 + 256
    ssm = (128 * 512 + 256 * 128 + 256 * 16 + 8 * 256 + 256 + 4 * 256 + 256
           + 256 * 4 + 256 + 256)
    attn = 128 * 256 + 256 + 128 * 128 + 128 + 4 * 16 + 32 + 256
    gmu = 2 * 128 * 256 + 256
    cross = 2 * (128 * 128 + 128) + 96 + 256
    total = 3 * ssm + 3 * attn + gmu + cross + 8 * mlp + 512 * 128 + 256
    assert fb.total_params(TINY) == total
    nbytes = (2 * total + (2 * (100 + 4) + 4) * 256 + 2 * (32 + 4) * 256
              + 3 * 4 * 2 * (256 * 4 * 4 + 3 * 256 * 2) + 4 * 512 * 4)
    assert fb.decode_step_bytes(TINY, 4, 100, 32, 2, 2) == nbytes
    assert reader("decode_step_roofline.hybrid")(run) == pytest.approx(
        100 * nbytes / 819e9 / 0.033)


@pytest.mark.parametrize("name", ["decode_step_roofline.hybrid",
                                  "paged_diff_attn_decode_roofline"])
def test_chip_readers_return_none_off_the_chip(name):
    """A share of a chip's peak is never computed from a CPU run."""
    assert reader(name)(record(on_chip=False)) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_readers_return_none_on_another_familys_record(name):
    """The looped family's record (``loop``, no ``hybrid``; a program
    that lacks this family): the line leaves the metric out, and nothing
    raises."""
    run = dict(record(on_chip=True), arch=OURO)
    del run["hybrid"], run["tail_hybrid"]
    run["loop"] = {"loop_tokens": 70, "loop_passes": 280}
    assert reader(name)(run) is None
    run.update(hybrid=None, tail_hybrid=None, kernel=None, samples=[],
               tail_samples=[])
    assert reader(name)(run) is None


def test_the_kernels_events_are_found_by_the_name_the_kernel_gives():
    with open(os.path.join(ROOT, "paddle_tpu", "ops", "pallas",
                           "paged_diff_attn.py")) as f:
        assert f'name="{serve_hybrid.KERNEL}"' in f.read()
    planes = [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
               "events": [["paged_diff_attn_decode.15 f32[176,2,1280]", 0.0,
                           2e6],
                          ["paged_attn_decode.7 bf16[7,1,2048]", 3e6, 6e4],
                          ["fusion.7 bf16[176,2560]", 5e6, 9e5]]}]}]
    assert serve_family.kernel_events(
        planes, True, prefix=serve_hybrid.KERNEL) == {
            "calls": 1, "seconds": pytest.approx(2e-3)}


def test_the_checked_requests_end_past_the_window_and_one_reused_a_slot():
    class It:
        def __init__(self, idx, n_prompt, n_out):
            self.idx, self.prompt = idx, [0] * n_prompt
            self.req = type("R", (), {"tokens": [0] * n_out})()

    # twelve early long requests, short ones, one late long one
    finished = ([It(i, 300, 400) for i in range(12)]
                + [It(20 + i, 100, 200) for i in range(6)]
                + [It(180, 300, 900)])
    for seed in range(8):
        picked = serve_hybrid.checked_sample(finished, 176, 512, seed, 4)
        assert len(picked) == 4 == len({it.idx for it in picked})
        assert all(len(it.prompt) + len(it.req.tokens) > 512
                   for it in picked)
        assert any(it.idx >= 176 for it in picked)
    # nothing late: the early ones alone
    picked = serve_hybrid.checked_sample(finished[:-1], 176, 512, 0, 4)
    assert len(picked) == 4 and all(it.idx < 12 for it in picked)


# --------------------------------------------------------------------------
# the rehearsal cell, end to end on the CPU
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def run_cell(tmp_path_factory):
    """``JAX_PLATFORMS=cpu python3 benchmark/run.py --workload
    rehearse-phi4flash-reason-backlog`` from the checkout."""
    cache = tmp_path_factory.mktemp("jax_cache")

    def run(trace):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(cache),
                   PYTHONPATH=ROOT + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
             "--workload", "rehearse-phi4flash-reason-backlog", "--seed",
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
    # every generated row of every sampled request, two limits; the
    # sampled requests all wrapped the rehearsal's window of 8, and ran
    # on slots that earlier requests had left
    ref = notes["reference"]
    assert ref["requests_checked"] == serve_hybrid.CHECKED_REQUESTS
    assert ref["rows_checked"] > 3 * ref["requests_checked"]
    assert min(ref["final_lengths"]) > TINY["sliding_window"]
    assert ref["on_a_reused_slot"] >= 1
    assert ref["emitted_logit_gap_mean"] <= ref["gap_mean_tol"] \
        == serve_hybrid.EMITTED_GAP_MEAN_TOL
    assert ref["emitted_logit_gap_max"] <= ref["gap_max_tol"] \
        == serve_hybrid.EMITTED_GAP_MAX_TOL
    # the state-space state of requests still running at the end, past
    # the window, against the reference's after the same positions
    assert 1 <= len(ref["state_positions"]) <= serve_hybrid.CHECKED_STATES
    assert min(ref["state_positions"]) > TINY["sliding_window"]
    assert all(len(e) == 3 for e in ref["state_err_by_layer"])
    # the limit is the cell's; a toy's state of a thousand numbers after
    # a dozen positions reads 0.3-1.2%, where a state one position out of
    # step, or not reset, reads tens of percent
    assert ref["state_err_tol"] == serve_hybrid.STATE_ERR_TOL
    assert ref["state_err"] == pytest.approx(
        max(e[0] for e in ref["state_err_by_layer"]), abs=1e-6)
    assert 0 < ref["state_err"] < 0.03
    assert ref["state_positions"] == sorted(ref["state_positions"],
                                            reverse=True)
    assert all(v for k, v in notes["checks"].items()
               if k not in ("phase", "t"))


def test_rehearsal_traced_reports_the_counters_and_no_chip_share(run_cell):
    """Every per-layer metric BENCHMARK.json lists for the cell but the
    two shares of a chip's peak, which are never computed from a CPU
    run; a wave of the rehearsal's buckets (32 or 64 rows, 1 or 4
    prompts) puts one row in 32..64 through the stateless layers."""
    result, notes = run_cell(1)
    assert result["correct"] is True
    off_chip = {"decode_step_roofline.hybrid",
                "paged_diff_attn_decode_roofline"}
    got = result["metrics"]
    assert set(got) == (set(FED_METRICS) | set(NEW_METRICS)) - off_chip - {
        "serve_tokens_per_s"}
    assert 100 / 64 <= got["prefill.cross_rows_share"]["value"] <= 100 / 32
    assert result["device"]["busy_s"] > 0
    closed = notes["window_closed"]
    assert 0 < closed["spans_in_window"] < closed["ring_spans"]
