"""Unified telemetry layer (ISSUE 4): metrics registry concurrency and
exports, StepTimer span nesting + chrome-trace boundaries, the XLA
compile hook, cross-rank aggregation (fake KV store, straggler
thresholds, 2-process e2e) and the registry-view contract behind
``profiler.fast_path_summary()``."""
import json
import os
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.observability import (StepTimer, aggregate, metrics,
                                      timeline)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _telemetry_off():
    """Each test starts with the event log unconfigured; configure(tmp)
    inside a test is undone here."""
    timeline.configure(None)
    yield
    timeline.configure(None)


# ------------------------------------------------------------ registry ----

class TestRegistry:
    def test_threaded_counter_increments_lose_nothing(self):
        reg = metrics.MetricsRegistry()
        c = reg.counter("t.count")
        fam = reg.stats_family("t", {"hits": 0})

        def work():
            for _ in range(2000):
                c.inc()
                fam.inc("hits")

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 16000
        assert fam["hits"] == 16000

    def test_labels_key_distinct_series(self):
        reg = metrics.MetricsRegistry()
        reg.counter("req", op="a").inc(2)
        reg.counter("req", op="b").inc(5)
        snap = reg.snapshot()
        assert snap['req{op="a"}'] == 2
        assert snap['req{op="b"}'] == 5

    def test_type_collision_raises(self):
        reg = metrics.MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_histogram_percentile_math(self):
        reg = metrics.MetricsRegistry()
        h = reg.histogram("lat")
        for v in range(1, 101):
            h.observe(float(v))
        # nearest-rank percentiles over the raw observations
        assert h.percentile(50) == 50.0
        assert h.percentile(95) == 95.0
        assert h.percentile(100) == 100.0
        assert h.count == 100 and h.min == 1.0 and h.max == 100.0
        assert h.mean == pytest.approx(50.5)
        s = h.summary()
        assert s["p50"] == 50.0 and s["p95"] == 95.0

    def test_histogram_reservoir_is_bounded(self):
        reg = metrics.MetricsRegistry()
        h = reg.histogram("lat")
        for v in range(10000):
            h.observe(float(v))
        assert h.count == 10000
        assert len(h._reservoir) <= 4096
        # rolling window: old observations age out, recent ones dominate
        assert h.percentile(50) > 4000

    def test_stats_family_is_a_registry_view(self):
        reg = metrics.MetricsRegistry()
        fam = reg.stats_family("redu", {"launched": 0})
        fam["launched"] += 3
        assert reg.counter("redu.launched").value == 3
        reg.counter("redu.launched").inc(2)
        assert dict(fam) == {"launched": 5}
        reg.reset("redu")
        assert fam["launched"] == 0

    def test_prometheus_export_golden(self):
        reg = metrics.MetricsRegistry()
        h = reg.histogram("latency.s", buckets=(0.5, 2.0))
        for v in (0.5, 1.0, 4.0):
            h.observe(v)
        reg.gauge("queue.depth").set(2.5)
        reg.counter("requests.total", handler="train").inc(3)
        assert reg.to_prometheus() == (
            "# TYPE latency_s histogram\n"
            'latency_s_bucket{le="0.5"} 1\n'
            'latency_s_bucket{le="2.0"} 2\n'
            'latency_s_bucket{le="+Inf"} 3\n'
            "latency_s_sum 5.5\n"
            "latency_s_count 3\n"
            "# TYPE queue_depth gauge\n"
            "queue_depth 2.5\n"
            "# TYPE requests_total counter\n"
            'requests_total{handler="train"} 3\n')

    def test_jsonl_export_golden_format(self):
        reg = metrics.MetricsRegistry()
        reg.counter("a.count").inc(4)
        reg.histogram("a.lat").observe(0.25)
        lines = reg.export_jsonl()
        assert len(lines) == 2
        recs = [json.loads(line) for line in lines]
        for rec in recs:
            assert rec["event"] == "metric"
            assert {"name", "type", "labels", "time"} <= set(rec)
        assert recs[0] == {**recs[0], "name": "a.count",
                           "type": "counter", "value": 4}
        assert recs[1]["type"] == "histogram"
        assert recs[1]["summary"]["count"] == 1

    def test_global_reset_zeroes_everything(self):
        reg = metrics.MetricsRegistry()
        reg.counter("c").inc(7)
        reg.gauge("g").set(3)
        reg.histogram("h").observe(1.0)
        reg.reset()
        assert reg.counter("c").value == 0
        assert reg.gauge("g").value == 0.0
        assert reg.histogram("h").count == 0


# ----------------------------------------------------- registry views ----

class TestLegacyViewsServedFromRegistry:
    """The old stat dicts are VIEWS, not copies: mutating either side is
    visible on the other, and fast_path_summary() serves registry
    cells."""

    def test_reducer_view(self):
        from paddle_tpu.distributed import reducer as reducer_mod
        metrics.reset("reducer")
        reducer_mod._reducer_stats["collectives_launched"] += 2
        assert metrics.families()["reducer"]["collectives_launched"] == 2
        assert profiler.reducer_stats()["collectives_launched"] == 2
        metrics.REGISTRY.counter("reducer.collectives_launched").inc()
        assert reducer_mod.reducer_stats()["collectives_launched"] == 3
        metrics.reset("reducer")

    def test_fast_path_summary_equals_registry(self):
        net = paddle.nn.Linear(4, 2)
        opt = paddle.optimizer.Momentum(0.1, parameters=net.parameters())
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        loss = (net(x) ** 2).mean()
        loss.backward()
        opt.step()
        s = profiler.fast_path_summary()
        fams = metrics.families()
        for k, v in fams["fused_step"].items():
            assert s["fused_step"][k] == v
        for k, v in fams["dispatch_cache"].items():
            assert s["dispatch_cache"][k] == v
        # the composite faults family spans five registry families
        for fam in ("watchdog", "launch", "checkpoint", "bootstrap",
                    "faults"):
            for k, v in fams[fam].items():
                assert s["faults"][k] == v, (fam, k)

    def test_reset_helpers_deprecated_but_working(self):
        profiler._deprecated_reset_warned.discard("reset_reducer_stats")
        metrics.REGISTRY.counter("reducer.collectives_launched").inc()
        with pytest.warns(DeprecationWarning, match="metrics.reset"):
            profiler.reset_reducer_stats()
        assert profiler.reducer_stats()["collectives_launched"] == 0
        # warn-once: the second call stays silent
        import warnings as _w
        with _w.catch_warnings():
            _w.simplefilter("error")
            profiler.reset_reducer_stats()


# ------------------------------------------------------------ timeline ----

class TestStepTimerAndSpans:
    def test_step_records_and_span_nesting(self, tmp_path):
        timeline.configure(str(tmp_path))
        with StepTimer(name="t", tokens_per_step=64,
                       publish_interval=0) as timer:
            for _ in range(3):
                with timer.step():
                    with timer.span("forward"):
                        with timer.span("matmul"):
                            pass
                    with timer.span("backward"):
                        pass
        assert timer.steps == 3
        pct = timer.percentiles()
        assert pct["p50"] is not None and pct["p95"] >= pct["p50"]
        events = [json.loads(line) for line in
                  open(tmp_path / "events_rank0.jsonl")]
        steps = [e for e in events if e["event"] == "step"]
        spans = [e for e in events if e["event"] == "span"]
        assert len(steps) == 3
        assert steps[0]["step"] == 1 and steps[-1]["step"] == 3
        assert steps[0]["tokens_per_s"] > 0
        assert {"wall_s", "compiles", "compile_s", "collective_wait_s",
                "phases"} <= set(steps[0])
        # phase attribution: span durations land in the step record
        assert {"forward", "backward", "matmul"} \
            <= set(steps[0]["phases"])
        # nesting depth recorded: matmul sat inside forward
        inner = [s for s in spans if s["name"] == "matmul"]
        outer = [s for s in spans if s["name"] == "forward"]
        assert inner and outer
        assert inner[0]["depth"] == outer[0]["depth"] + 1

    def test_span_is_noop_when_inactive(self, tmp_path, monkeypatch):
        """With no sink a span writes no file and still lands in the
        ring."""
        monkeypatch.chdir(tmp_path)
        timeline.reset_spans()
        with timeline.span("anything", k=1) as sp:
            pass
        (rec,) = timeline.spans()
        assert rec == (sp.id, None, "anything", sp.t0, sp.t1, {"k": 1})
        assert sp.t1 >= sp.t0 and sp.dur == sp.t1 - sp.t0
        assert list(tmp_path.iterdir()) == []
        assert timeline.telemetry_dir() is None

    def test_event_log_rotates_at_cap(self, tmp_path, monkeypatch):
        timeline.configure(str(tmp_path))
        monkeypatch.setenv("PADDLE_TELEMETRY_MAX_MB", "0.0005")  # ~500B
        for i in range(40):
            timeline.emit({"event": "scalar", "name": "x", "value": i})
        assert (tmp_path / "events_rank0.jsonl.1").exists()
        # both generations parse
        for name in ("events_rank0.jsonl", "events_rank0.jsonl.1"):
            for line in open(tmp_path / name):
                json.loads(line)

    def test_compile_hook_fires_exactly_once_per_retrace(self):
        import jax
        import jax.numpy as jnp
        timeline.install_compile_hook()
        x3 = jnp.ones((3,))
        x5 = jnp.ones((5,))           # inputs built BEFORE counting
        f = jax.jit(lambda x: x * 3 + 1)
        c = metrics.counter("compile.count")
        f(x3).block_until_ready()     # warm: compiles f (maybe consts)
        n0 = c.value
        f(x3).block_until_ready()     # cache hit: no event
        assert c.value == n0
        f(x5).block_until_ready()     # retrace: exactly one event
        assert c.value == n0 + 1
        assert metrics.counter("compile.seconds").value > 0

    def test_profiler_step_spans_have_real_duration(self, tmp_path):
        p = profiler.Profiler()
        with p:
            x = paddle.to_tensor(np.ones((4, 4), np.float32))
            for _ in range(3):
                x = x * 2.0
                p.step()
        path = str(tmp_path / "trace.json")
        p.export_chrome_tracing(path)
        evs = json.load(open(path))["traceEvents"]
        marks = [e for e in evs if e["name"] == "profiler_step"]
        assert len(marks) == 3
        assert all(e["dur"] > 0 for e in marks)
        # consecutive step spans tile the timeline (close/open, no gaps
        # beyond float rounding)
        marks.sort(key=lambda e: e["ts"])
        for a, b in zip(marks, marks[1:]):
            assert b["ts"] == pytest.approx(a["ts"] + a["dur"], abs=50.0)


def test_chrome_trace_nested_training_spans(tmp_path):
    """Acceptance: a 3-step DP run's exported chrome trace contains
    nested forward/backward/allreduce/optimizer spans inside real step
    spans, plus at least one xla_compile event with nonzero duration."""
    import jax
    from jax.sharding import Mesh
    import paddle_tpu.distributed as dist

    paddle.seed(3)
    net = paddle.nn.Sequential(paddle.nn.Linear(8, 8), paddle.nn.Tanh(),
                               paddle.nn.Linear(8, 4))
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    dp = dist.DataParallel(net, mesh=mesh, bucket_size_mb=1e9)
    opt = paddle.optimizer.Momentum(0.05, parameters=net.parameters())
    x = paddle.to_tensor(np.random.RandomState(0)
                         .randn(8, 8).astype(np.float32))

    p = profiler.Profiler()
    with p, StepTimer(name="trace", publish_interval=0) as timer:
        for _ in range(3):
            with timer.step():
                loss = (dp(x) ** 2).mean()
                loss.backward()
                opt.step()
                opt.clear_grad()

    path = str(tmp_path / "trace.json")
    p.export_chrome_tracing(path)
    evs = json.load(open(path))["traceEvents"]
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)

    steps = by_name["step"]
    assert len(steps) == 3

    def contained(inner, outers):
        return any(o["ts"] - 1 <= inner["ts"]
                   and inner["ts"] + inner["dur"] <= o["ts"] + o["dur"] + 1
                   for o in outers)

    for name in ("forward", "backward", "allreduce", "optimizer_step"):
        assert name in by_name, sorted(by_name)
        assert all(contained(e, steps) for e in by_name[name]), name
    # one collective per step, launched from the grad-ready hook while
    # backward still runs -> the allreduce span nests inside backward
    assert any(contained(e, by_name["backward"])
               for e in by_name["allreduce"])
    compiles = by_name.get("xla_compile", [])
    assert compiles and any(e["dur"] > 0 for e in compiles)


# ----------------------------------------------------------- aggregate ----

class FakeKV:
    """Dict-backed stand-in for the jax coordination-service client."""

    def __init__(self):
        self.store = {}

    def key_value_set(self, key, value):
        if key in self.store:
            raise RuntimeError(f"ALREADY_EXISTS: {key}")
        self.store[key] = value

    def key_value_dir_get(self, prefix):
        return [(k, v) for k, v in sorted(self.store.items())
                if k.startswith(prefix)]

    def key_value_delete(self, key):
        self.store.pop(key, None)


def _snap(rank, steps, mean, wait, last_step=None, faults=None):
    return {
        "rank": rank, "time": 1000.0 + rank, "step": last_step or steps,
        "steps": steps,
        "step_wall": {"count": steps, "sum": mean * steps, "min": mean,
                      "max": mean, "mean": mean, "p50": mean,
                      "p95": mean},
        "compiles": 2, "compile_s": 0.5,
        "collective_wait_s": wait,
        "families": {"faults": faults or {}},
    }


class TestCrossRankAggregation:
    def test_publish_gather_roundtrip_on_fake_kv(self):
        kv = FakeKV()
        aggregate.publish(step=5, client=kv, rank=0)
        aggregate.publish(step=6, client=kv, rank=1)
        aggregate.publish(step=7, client=kv, rank=1)   # newer seq wins
        snaps = aggregate.gather(client=kv)
        assert [s["rank"] for s in snaps] == [0, 1]
        assert snaps[1]["step"] == 7
        assert "families" in snaps[0] and "step_wall" in snaps[0]
        # stale sequence keys were reclaimed (bounded store)
        rank1_keys = [k for k in kv.store if "/r1/" in k]
        assert len(rank1_keys) == 1

    def test_merge_names_per_rank_step_times_and_skew(self):
        report = aggregate.merge([_snap(0, 10, 0.1, 0.0),
                                  _snap(1, 8, 0.3, 0.0)])
        assert report["nranks_seen"] == 2
        assert report["step_skew"] == 2
        assert report["ranks"][0]["step_wall_mean_s"] == 0.1
        assert report["ranks"][1]["step_wall_p95_s"] == 0.3
        assert report["ranks"][1]["faults"] == {}

    def test_straggler_flagged_below_wait_threshold(self):
        # rank 1 waits ~0 while rank 0 waits 0.5s/step: rank 1 is the
        # straggler everyone stalls on
        report = aggregate.merge(
            [_snap(0, 10, 0.6, 5.0), _snap(1, 10, 0.6, 0.1)],
            straggler_gap_s=0.2)
        assert [s["rank"] for s in report["stragglers"]] == [1]
        assert report["stragglers"][0]["reason"] \
            == "collective_wait_asymmetry"
        # under the threshold: no flag
        report = aggregate.merge(
            [_snap(0, 10, 0.6, 5.0), _snap(1, 10, 0.6, 0.1)],
            straggler_gap_s=1.0)
        assert report["stragglers"] == []

    def test_straggler_warns_when_asked(self):
        with pytest.warns(RuntimeWarning, match="straggler"):
            aggregate.merge(
                [_snap(0, 10, 0.6, 5.0), _snap(1, 10, 0.6, 0.1)],
                straggler_gap_s=0.2, warn=True)

    def test_step_lag_straggler(self):
        report = aggregate.merge(
            [_snap(0, 20, 0.1, 0.0), _snap(1, 10, 0.1, 0.0)],
            step_lag=2)
        assert [s["rank"] for s in report["stragglers"]] == [1]
        assert report["stragglers"][0]["reason"] == "step_lag"

    def test_merge_from_dir_reads_fault_counters(self, tmp_path):
        snap = _snap(1, 4, 0.2, 0.0,
                     faults={"faults_fired": 3, "faults_installed": 3})
        (tmp_path / "snapshot_rank1.json").write_text(json.dumps(snap))
        report = aggregate.merge_from_dir(str(tmp_path))
        assert report["ranks"][1]["faults"]["faults.faults_fired"] == 3

    def test_spawn_two_process_aggregation_e2e(self, tmp_path):
        """2 spawned workers train under StepTimers writing into a
        shared telemetry dir; the merged report names both ranks' step
        counts and times."""
        import spawn_helper
        tdir = str(tmp_path / "telemetry")
        paddle.distributed.spawn(spawn_helper.telemetry_train,
                                 args=(tdir, 4), nprocs=2)
        report = aggregate.merge_from_dir(tdir)
        assert report["nranks_seen"] == 2
        for r in (0, 1):
            assert report["ranks"][r]["steps"] == 4
            assert report["ranks"][r]["step_wall_p50_s"] > 0
        # the report tool renders the same dir and exits 0
        import subprocess
        import sys
        env = dict(os.environ, PYTHONPATH=REPO)
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "telemetry_report.py"),
             tdir, "--json"], capture_output=True, env=env, timeout=120)
        assert out.returncode == 0, out.stderr.decode()
        rendered = json.loads(out.stdout.decode())
        assert rendered["nranks_seen"] == 2


def test_injected_straggler_flagged_in_merged_report(tmp_path):
    """Acceptance: a supervised 2-process CPU run with an injected
    collective_delay (testing/faults.py) on rank 1 produces a merged
    cross-rank report naming per-rank step times and flagging rank 1 as
    the straggler (its rendezvous wait is the LOW one — rank 0 sat at
    the barrier waiting for it)."""
    from paddle_tpu.distributed.launch import supervise
    from paddle_tpu.testing.env import clean_cpu_env

    tdir = str(tmp_path / "telemetry")
    env = clean_cpu_env(REPO, device_count=1)
    env["PADDLE_COLLECTIVE_TIMEOUT"] = "30"
    # delay rank 1's contribution to EVERY bucket collective by 0.35s
    env["PADDLE_FAULTS"] = \
        "collective_delay:op=dp_bucket,seconds=0.35,rank=1,repeat=1"
    argv = ["-m", "paddle_tpu.testing.recovery_worker",
            "--ckpt", str(tmp_path / "ckpt"),
            "--out", str(tmp_path / "out"), "--steps", "4"]
    summary = supervise(argv, nprocs=2, env_base=env,
                        log_dir=str(tmp_path / "logs"),
                        telemetry_dir=tdir)
    assert summary["rc"] == 0, summary
    assert summary["telemetry_dir"] == os.path.abspath(tdir)

    report = aggregate.merge_from_dir(tdir, straggler_gap_s=0.2)
    assert report["nranks_seen"] == 2
    for r in (0, 1):
        assert report["ranks"][r]["steps"] == 4
        assert report["ranks"][r]["step_wall_mean_s"] > 0
    flagged = [s for s in report["stragglers"]
               if s["reason"] == "collective_wait_asymmetry"]
    assert [s["rank"] for s in flagged] == [1], report
    # rank 0 paid the wait; the text rendering names the straggler
    text = aggregate.format_report(report)
    assert "STRAGGLERS" in text and "rank 1" in text


# ----------------------------------------------------------- callbacks ----

class TestCallbacks:
    def test_telemetry_callback_keeps_tsv_and_fills_registry(
            self, tmp_path):
        from paddle_tpu.hapi.callbacks import VisualDL
        timeline.configure(str(tmp_path / "telemetry"))
        cb = VisualDL(str(tmp_path / "logs"))
        cb.on_begin("train")
        cb.on_train_batch_end(0, {"loss": 0.5, "acc": 0.25,
                                  "tag": "skipme"})
        cb.on_train_batch_end(1, {"loss": 0.25})
        cb.on_end("train")
        tsv = (tmp_path / "logs" / "scalars.tsv").read_text().splitlines()
        assert tsv == ["1\tloss\t0.5", "1\tacc\t0.25", "2\tloss\t0.25"]
        assert metrics.gauge("train.loss").value == 0.25
        events = [json.loads(line) for line in
                  open(tmp_path / "telemetry" / "events_rank0.jsonl")]
        scalars = [e for e in events if e["event"] == "scalar"]
        assert {(e["name"], e["value"]) for e in scalars} \
            == {("loss", 0.5), ("acc", 0.25), ("loss", 0.25)}

    def test_progress_bar_callback_reports_throughput(self, capsys):
        from paddle_tpu.hapi.callbacks import ProgressBarCallback
        cb = ProgressBarCallback(log_freq=2, tokens_per_batch=256)
        cb.on_train_begin()
        for step in range(4):
            cb.on_train_batch_begin(step)
            cb.on_train_batch_end(step)
        cb.on_train_end()
        out = capsys.readouterr().out
        assert out.count("steps/s") == 2          # every log_freq batches
        assert "tokens/s" in out
        assert timeline.current_timer() is None   # timer detached


# -------------------------------------------------------------- launch ----

def test_launch_telemetry_dir_reaches_workers_and_summary(tmp_path):
    from paddle_tpu.distributed.launch import supervise
    tdir = str(tmp_path / "telemetry")
    script = tmp_path / "w.py"
    script.write_text(
        "import json, os\n"
        "print(json.dumps({'dir': os.environ['PADDLE_TELEMETRY_DIR']}))\n")
    summary = supervise([str(script)], nprocs=1, telemetry_dir=tdir)
    assert summary["rc"] == 0
    assert summary["telemetry_dir"] == os.path.abspath(tdir)
    assert os.path.isdir(tdir)
