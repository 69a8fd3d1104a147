"""The per-layer metrics that read the program's own spans
(benchmark/lib/spans.py and its five readers): the reduction on
hand-made rings, the readers on a real tiny engine, and the runner's
``rehearse-backlog --trace 1`` line — on the CPU, so the values prove
arithmetic and control flow, never a speed."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as runner                    # noqa: E402
from benchmark.lib import spans                        # noqa: E402

NEW = {"sched.span_self_ms_per_step": "ms", "pager.span_ms_per_step": "ms",
       "step.dispatch_ms_per_step": "ms", "step.prefill_share": "%",
       "step.readback_wait_share": "%"}


def ring():
    """Two engine steps by hand, children before parents (close order):
    step 1 = [10, 11) with admit [10, 10.3) holding a wave [10.1, 10.3)
    whose readback is [10.15, 10.25); step 2 = [12, 12.5), bare."""
    return [
        (4, 3, "serving.prefill_wave.readback", 10.15, 10.25, None),
        (3, 2, "serving.prefill_wave", 10.1, 10.3, {"batch": 1}),
        (2, 1, "serving.admit", 10.0, 10.3, None),
        (5, 1, "serving.pager.ensure", 10.3, 10.4, None),
        (1, None, "serving.step", 10.0, 11.0, {"step": 1}),
        (6, None, "serving.step", 12.0, 12.5, {"step": 2}),
        (7, None, "optimizer_step", 12.6, 12.7, None),
    ]


def test_reduce_keeps_the_steps_that_start_in_the_window():
    out = spans.reduce(ring(), 0, 9.0, 13.0)
    assert out["steps"] == 2 and out["step_s"] == pytest.approx(1.5)
    assert out["total_s"] == pytest.approx({
        "serving.step": 1.5, "serving.admit": 0.3,
        "serving.prefill_wave": 0.2, "serving.pager.ensure": 0.1,
        "serving.prefill_wave.readback": 0.1})
    # self time: duration minus what the child spans cover
    assert out["self_s"] == pytest.approx({
        "serving.step": 1.5 - 0.3 - 0.1, "serving.admit": 0.1,
        "serving.prefill_wave": 0.1, "serving.pager.ensure": 0.1,
        "serving.prefill_wave.readback": 0.1})
    assert sum(out["self_s"].values()) == pytest.approx(out["step_s"])
    # a step belongs to the window that saw it START, whole
    first = spans.reduce(ring(), 0, 9.0, 12.0)
    assert first["steps"] == 1 and first["step_s"] == pytest.approx(1.0)
    late = spans.reduce(ring(), 0, 10.5, 13.0)
    assert late["steps"] == 1 and set(late["total_s"]) == {"serving.step"}
    assert spans.reduce(ring(), 0, 20.0, 30.0) is None
    assert spans.reduce([], 0, 0.0, 1.0) is None


def test_reduce_raises_on_a_wrapped_ring():
    # evictions, but the oldest survivor closed before the window
    # opened: everything evicted closed earlier still, the window is whole
    assert spans.reduce(ring(), 3, 11.5, 13.0)["steps"] == 1
    # the oldest survivor closed inside the window: a part of a counted
    # step may be among the evicted
    with pytest.raises(RuntimeError, match="wrapped inside the window"):
        spans.reduce(ring(), 3, 10.0, 13.0)
    assert spans.reduce(ring(), 0, 10.0, 13.0)["steps"] == 2


def test_a_program_without_the_ring_reads_as_nothing(monkeypatch):
    """The parent of the PR that added the ring: every reader returns
    None and raises nothing, so the line leaves the metric out."""
    from paddle_tpu.observability import timeline
    monkeypatch.delattr(timeline, "spans")
    for name in NEW:
        assert runner.load_reader(name).read({"t0": 0.0, "t1": 1.0}) is None


def test_name_sets_partition_the_engines_tree():
    sets = (spans.SCHEDULER, spans.PAGER, spans.DISPATCH, spans.READBACK)
    names = [n for s in sets for n in s]
    assert len(names) == len(set(names))
    assert spans.ROOT in spans.SCHEDULER


def test_readers_on_a_real_engine_and_the_additive_identity():
    import jax
    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.models import gpt
    from paddle_tpu.observability import timeline
    cfg = gpt.gpt_tiny()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    eng = PagedServingEngine((params, cfg), slots=4, page_size=8,
                             num_pages=64, max_len=64, seq_buckets=(16, 32),
                             batch_buckets=(1, 2))
    eng.warmup()
    rng = np.random.RandomState(3)
    timeline.reset_spans()
    eng.submit(rng.randint(0, cfg.vocab_size, (9,)), 3)
    eng.step()                                  # before the window: left out
    t0 = time.perf_counter()
    for n in (10, 12, 20, 11, 25, 9):
        eng.submit(rng.randint(0, cfg.vocab_size, (n,)), 6)
    steps = 0
    while eng._busy():
        eng.step()
        steps += 1
    run = {"t0": t0, "t1": time.perf_counter()}
    value = {name: runner.load_reader(name).read(run) for name in NEW}
    w = run["program_spans"]
    assert w["steps"] == steps
    # every span inside a step is in exactly one of the four sets ...
    known = set(spans.SCHEDULER + spans.PAGER + spans.DISPATCH
                + spans.READBACK)
    assert set(w["total_s"]) <= known
    assert set(spans.PAGER + spans.READBACK + spans.DISPATCH) \
        <= set(w["total_s"])
    # ... so the three per-step times, times steps, plus the readback
    # time, are the time in serving.step
    per_step = (value["sched.span_self_ms_per_step"]
                + value["pager.span_ms_per_step"]
                + value["step.dispatch_ms_per_step"])
    readback_s = value["step.readback_wait_share"] / 100 * w["step_s"]
    assert per_step * steps / 1e3 + readback_s == pytest.approx(
        w["step_s"], rel=1e-6)
    assert all(v > 0 for v in value.values())
    assert value["step.prefill_share"] < 100
    assert value["step.readback_wait_share"] < 100


@pytest.fixture(scope="module")
def traced_backlog(tmp_path_factory):
    """The last line of ``rehearse-backlog --trace 1``, in a subprocess:
    the runner turns on the persistent compile cache and a profiler."""
    cache = tmp_path_factory.mktemp("jax_cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "rehearse-backlog", "--seed", str(2**31 + 9),
         "--seconds", "1.5", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_rehearsal_backlog_reports_the_span_metrics(traced_backlog):
    got = traced_backlog["metrics"]
    assert traced_backlog["correct"] is True
    for name, unit in NEW.items():
        assert got[name]["unit"] == unit and got[name]["value"] > 0, name
    # beside the metrics the cell already had, none displaced
    assert {"sched.slot_occupancy", "sched.host_ms_per_step",
            "pager.pool_fill_peak", "pager.preempted_share",
            "step.decode_ms_p50", "compile.setup_misses"} <= set(got)
    assert got["step.prefill_share"]["value"] \
        + got["step.readback_wait_share"]["value"] < 200
    # the host time outside the jitted calls, told two ways: wall minus
    # two histograms, and the spans that are not a readback.  The
    # histograms also hold the enqueue and the commit, so the spans read
    # higher by those, never lower by more than rounding
    by_span = sum(got[n]["value"] for n in (
        "sched.span_self_ms_per_step", "pager.span_ms_per_step",
        "step.dispatch_ms_per_step"))
    assert by_span > 0.5 * got["sched.host_ms_per_step"]["value"]


def test_manifest_lists_the_five_for_the_backlog_cell_only():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    added = manifest["per_layer"][-5:]
    assert [m["name"] for m in added] == list(NEW)
    for m in added:
        assert m["source"] == "program_span"
        assert m["moves"] == "serve_tokens_per_s"
        assert m["workloads"] == ["serve-1.3b-backlog"]
        assert m["unit"] == NEW[m["name"]]
