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
from grown_tree import grown_root, tree                # noqa: E402,F401

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


def tiny_engine(family):
    """A tiny paged engine of the family and its vocabulary."""
    import jax
    from paddle_tpu.inference.serving import PagedServingEngine
    if family == "gpt":
        from paddle_tpu.models import gpt
        cfg = gpt.gpt_tiny()
        params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    else:       # the rehearsal's toy of the family, as its driver builds it
        from benchmark.drivers import serve_family
        with open(os.path.join(ROOT, "benchmark", "rehearse", "configs",
                               "tiny-kanana2.json")) as f:
            arch = json.load(f)
        assert arch["model_type"] == family
        model, _, config_cls = serve_family.family_modules(family)
        cfg = serve_family.build_config(config_cls, arch)
        params = model.init_params(cfg, jax.random.PRNGKey(0))
    return PagedServingEngine((params, cfg), slots=4, page_size=8,
                              num_pages=64, max_len=64, seq_buckets=(16, 32),
                              batch_buckets=(1, 2)), cfg.vocab_size


@pytest.mark.parametrize("family", ["gpt", "deepseek_v3"])
def test_readers_on_a_real_engine_and_the_additive_identity(family):
    """Both families behind the one engine make the same tree of spans:
    the four name sets partition ``serving.step`` for each."""
    from paddle_tpu.observability import timeline
    eng, vocab_size = tiny_engine(family)
    eng.warmup()
    rng = np.random.RandomState(3)
    timeline.reset_spans()
    eng.submit(rng.randint(0, vocab_size, (9,)), 3)
    eng.step()                                  # before the window: left out
    t0 = time.perf_counter()
    for n in (10, 12, 20, 11, 25, 9):
        eng.submit(rng.randint(0, vocab_size, (n,)), 6)
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


SLEEP_S = 0.005


def exposed_ms_per_step(eng, vocab_size, before_step):
    """``sched.host_ms_per_step`` over a window of a tiny engine, the
    record made as the serving drivers make it, with ``before_step(eng)``
    called ahead of every ``eng.step()``."""
    from benchmark.drivers.serve_engine import hist_summary
    from paddle_tpu.observability import metrics
    rng = np.random.RandomState(3)
    for n in (10, 12, 20, 11):
        eng.submit(rng.randint(0, vocab_size, (n,)), 12)
    for _ in range(3):                          # the ramp: slots filled
        eng.step()
    for name in ("serving.decode_step_s", "serving.prefill_s"):
        metrics.histogram(name).reset()
    step_s = []
    t0 = time.perf_counter()
    for _ in range(6):      # every request still has tokens to make
        before_step(eng)
        t = time.perf_counter()
        eng.step()
        step_s.append(time.perf_counter() - t)
    t1 = time.perf_counter()
    run = {"window_s": t1 - t0, "step_s": step_s,
           "hist": {"decode": hist_summary("serving.decode_step_s"),
                    "prefill": hist_summary("serving.prefill_s")}}
    while eng._busy():
        eng.step()
    return runner.load_reader("sched.host_ms_per_step").read(run)


def test_host_ms_per_step_rises_by_the_time_the_queue_stood_empty():
    """What the reader sees and what it does not.  While the host leads
    (step n+1 dispatched before step n is read back) it reads about 0.
    A client that makes the host's view whole before every step (here a
    cancel of an unknown id: the engine commits what is in flight first)
    and then works for 5 ms leaves the device's queue empty for that
    long: the reading rises by it.  The same 5 ms spent while a program
    is in flight are NOT seen, though the device may have finished it
    long before: a program's arrival is stamped when the host reads it,
    so its interval swallows the wait.  That case is the traced idle
    share's to show."""
    eng, vocab_size = tiny_engine("gpt")
    eng.warmup()

    def emptied_then_slow(eng):
        assert eng.cancel(10 ** 9) is None
        time.sleep(SLEEP_S)

    leads = exposed_ms_per_step(eng, vocab_size, lambda eng: None)
    empty = exposed_ms_per_step(eng, vocab_size, emptied_then_slow)
    hidden = exposed_ms_per_step(eng, vocab_size,
                                 lambda eng: time.sleep(SLEEP_S))
    sleep_ms = 1e3 * SLEEP_S
    assert -0.5 < leads < 0.5 * sleep_ms
    assert empty - leads >= 0.9 * sleep_ms
    assert -0.5 < hidden < 0.5 * sleep_ms


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
    # the host's time in a step, told two ways.  The spans that are not
    # a readback are ALL of the host's own work, hidden under the
    # device's step or not; ``sched.host_ms_per_step`` is the part of the
    # window the device's queue stood empty for, which the host's work
    # caused: the spans read at least that, to rounding (the window also
    # holds the client's own time between steps, which no span covers)
    by_span = sum(got[n]["value"] for n in (
        "sched.span_self_ms_per_step", "pager.span_ms_per_step",
        "step.dispatch_ms_per_step"))
    exposed = got["sched.host_ms_per_step"]["value"]
    # a hair under 0 at worst: one program across the window's edge
    assert -0.5 < exposed <= by_span


def test_ring_use_counts_the_spans_that_start_in_the_window():
    from paddle_tpu.observability import timeline
    timeline.reset_spans()
    t0 = time.perf_counter()
    for _ in range(3):
        with timeline.span("serving.step"):
            pass
    t1 = time.perf_counter()
    with timeline.span("serving.step"):
        pass
    assert spans.ring_use(t0, t1) == {"spans_in_window": 3,
                                      "ring_spans": timeline.RING_SPANS}


def test_manifest_lists_the_five_span_metrics_for_the_backlog_cell(tree):
    """Present, each a ``program_span`` that moves ``serve_tokens_per_s``
    in its unit, with ``serve-1.3b-backlog`` ON its list: not last, not
    that cell's alone — entries are appended behind them and other cells
    join their lists."""
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    listed = {m["name"]: m for m in manifest["per_layer"]}
    for name, unit in NEW.items():
        m = listed[name]
        assert m["source"] == "program_span"
        assert m["moves"] == "serve_tokens_per_s"
        assert "serve-1.3b-backlog" in m["workloads"]
        assert m["unit"] == unit
