"""The three per-layer metrics of prefill (``prefill.device_share``,
``prefill.padded_rows_share``, ``prefill.device_us_per_prompt_token``)
and what they read through: ``benchmark/lib/programs.py``, the
readback-side records of the programs a window holds.  The reduction on
hand-made rings against hand-computed numbers, the readers on a program
without the attributes (the parent: None, nothing raised) and on a ring
that wrapped (raised), the manifest's entries on the checkout and on the
grown tree, and a traced rehearsal of each of the four cells that stand
for a backlog cell — on the CPU, so the values prove arithmetic and
control flow, never a speed."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as runner                    # noqa: E402
from benchmark.lib import programs                     # noqa: E402
from grown_tree import grown_root, tree                # noqa: E402,F401

NEW = {"prefill.device_share": ("%", "program_span", "jitted steps"),
       "prefill.padded_rows_share": ("%", "program_counter", "scheduler"),
       "prefill.device_us_per_prompt_token": ("us", "program_span",
                                              "jitted steps")}
# the cells whose own test files leave room for a metric more (the other
# two backlog cells' files hold their lists to equality: PERF.md, open
# questions)
LISTED = ("serve-1.3b-backlog", "serve-kanana2-30b-backlog")
REHEARSALS = {"rehearse-backlog": True, "rehearse-kanana2-backlog": True,
              "rehearse-ouro-reason-backlog": False,
              "rehearse-phi4flash-reason-backlog": False}


def wave(sid, t0, t1, batch, seq, requests, tokens, device_s=None,
         cause=None):
    attrs = dict(batch=batch, seq=seq, requests=requests, tokens=tokens,
                 rows=batch * seq, hit_tokens=0,
                 request_ids=list(range(requests)))
    if device_s is not None:
        attrs = dict(attrs, dispatch_span=cause, device_s=device_s)
    return (sid, None, "serving.prefill_wave", t0, t1, attrs)


def decode(sid, t0, t1, device_s=None, cause=None):
    attrs = {"active": 3}
    if device_s is not None:
        attrs = dict(attrs, dispatch_span=cause, device_s=device_s)
    return (sid, None, "serving.decode", t0, t1, attrs)


def ring():
    """By hand, in closing order.  Before the window: a wave read back
    at 9.5.  In it: a 4 x 128 wave of 2 prompts (170 tokens, 30 ms), a
    1 x 512 wave of 300 tokens (20 ms), decode steps of 10, 10 and 30 ms;
    their dispatch-side spans carry no ``device_s``.  A decode read back
    at 20.5 is past the window's end; a span of another name is no
    program."""
    return [
        wave(2, 9.4, 9.5, 1, 128, 1, 100, device_s=0.5, cause=1),
        wave(3, 10.0, 10.1, 4, 128, 2, 170),
        decode(4, 10.1, 10.2),
        wave(5, 10.3, 10.4, 4, 128, 2, 170, device_s=0.030, cause=3),
        decode(6, 10.4, 10.5, device_s=0.010, cause=4),
        wave(7, 11.0, 11.1, 1, 512, 1, 300),
        wave(8, 11.2, 11.3, 1, 512, 1, 300, device_s=0.020, cause=7),
        decode(9, 11.3, 11.4, device_s=0.010, cause=6),
        decode(10, 11.5, 11.6, device_s=0.030, cause=9),
        (11, None, "serving.step", 10.0, 12.0, {"step": 1}),
        decode(12, 20.4, 20.5, device_s=9.0, cause=10),
    ]


def record(spans=None, dropped=0, t0=10.0, t1=20.0):
    return {"t0": t0, "t1": t1,
            "programs": programs.records(ring() if spans is None else spans,
                                         dropped, t0, t1)}


def read(name, run):
    return runner.load_reader(name).read(run)


# ------------------------------------------------------------ the reduction

def test_records_are_the_readbacks_that_closed_in_the_window():
    found = programs.records(ring(), 0, 10.0, 20.0)
    assert [(n, a["device_s"]) for n, a in found] == [
        ("serving.prefill_wave", 0.030), ("serving.decode", 0.010),
        ("serving.prefill_wave", 0.020), ("serving.decode", 0.010),
        ("serving.decode", 0.030)]
    # a record belongs to the window that saw its readback END
    assert [a["device_s"] for _, a in
            programs.records(ring(), 0, 9.0, 10.35)] == [0.5]
    assert len(programs.records(ring(), 0, 10.4, 20.5)) == 5
    assert programs.records(ring(), 0, 30.0, 40.0) is None
    assert programs.records([], 0, 0.0, 1.0) is None


def test_the_three_readers_on_a_hand_made_ring():
    run = record()
    # 50 ms of waves in 100 ms of programs
    assert read("prefill.device_share", run) == pytest.approx(50.0)
    # 470 prompt tokens in 512 + 512 rows
    assert read("prefill.padded_rows_share", run) == pytest.approx(
        100 * (1 - 470 / 1024))
    assert read("prefill.device_us_per_prompt_token", run) == pytest.approx(
        1e6 * 0.050 / 470)
    assert programs.total(run, "device_s") == pytest.approx(0.100)
    assert programs.total(run, "tokens", programs.WAVE) == 470


def test_by_bucket_is_the_engines_table_over_the_window():
    table = programs.by_bucket(record())
    assert table == {
        "4x128": {"waves": 1, "requests": 2, "tokens": 170, "rows": 512,
                  "device_s": 0.030},
        "1x512": {"waves": 1, "requests": 1, "tokens": 300, "rows": 512,
                  "device_s": 0.020}}
    assert programs.by_bucket({"programs": None}) == {}


def test_a_window_of_decode_steps_alone_reads_no_prefill():
    """No wave was read back in it: the share of the device's time is 0,
    and the two ratios over the waves' tokens are not reported."""
    run = record(t0=11.35, t1=20.0)
    assert read("prefill.device_share", run) == 0.0
    assert read("prefill.padded_rows_share", run) is None
    assert read("prefill.device_us_per_prompt_token", run) is None


def test_a_program_without_the_attributes_reads_as_nothing(monkeypatch):
    """The parent of the PR that added them: its spans have the names
    and ``batch`` / ``seq`` but no ``device_s``, ``tokens`` or ``rows``.
    Every reader returns None and raises nothing; so does a program
    without the ring."""
    bare = [(s[0], s[1], s[2], s[3], s[4],
             {k: v for k, v in s[5].items()
              if k in ("batch", "seq", "active", "request_ids", "step")})
            for s in ring()]
    run = record(bare)
    assert run["programs"] is None
    for name in NEW:
        assert read(name, run) is None
    assert programs.by_bucket(run) == {}
    from paddle_tpu.observability import timeline
    monkeypatch.delattr(timeline, "spans")
    for name in NEW:
        assert read(name, {"t0": 0.0, "t1": 1.0}) is None


def test_records_raise_on_a_wrapped_ring():
    # evictions, but the oldest survivor closed before the window opened
    assert len(programs.records(ring(), 3, 10.0, 20.0)) == 5
    # the oldest survivor closed inside the window: a readback the
    # window counts may be among the evicted
    with pytest.raises(RuntimeError, match="wrapped inside the window"):
        programs.records(ring(), 3, 9.0, 20.0)
    assert len(programs.records(ring(), 0, 9.0, 20.0)) == 6


def test_window_reads_the_programs_own_ring_once():
    """Through ``timeline.span`` itself, as the engine sets them: entry
    attributes at entry, ``device_s`` once the tokens have arrived."""
    import time
    from paddle_tpu.observability import timeline
    timeline.reset_spans()
    t0 = time.perf_counter()
    with timeline.span("serving.prefill_wave", batch=1, seq=64, requests=1,
                       tokens=40, rows=64, hit_tokens=0) as sent:
        pass
    with timeline.span("serving.prefill_wave", dispatch_span=sent.id,
                       batch=1, seq=64, requests=1, tokens=40, rows=64,
                       hit_tokens=0) as sp:
        sp.attrs["device_s"] = 0.004
    with timeline.span("serving.decode", dispatch_span=0, active=1) as sp:
        sp.attrs["device_s"] = 0.012
    run = {"t0": t0, "t1": time.perf_counter()}
    assert read("prefill.device_share", run) == pytest.approx(25.0)
    assert read("prefill.padded_rows_share", run) == pytest.approx(37.5)
    assert read("prefill.device_us_per_prompt_token", run) == pytest.approx(
        100.0)
    timeline.reset_spans()              # kept on the record: read once
    assert read("prefill.device_share", run) == pytest.approx(25.0)


# ------------------------------------------------------------- the manifest

def test_manifest_lists_the_three_behind_everything_that_was_there(tree):
    """Present, in this order, behind every entry the accepted benchmark
    had; each moves ``serve_tokens_per_s`` and lists the two backlog cells
    whose own test files allow a metric more; a reader file each."""
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [m["name"] for m in manifest["per_layer"]]
    first = names.index("prefill.device_share")
    assert names[first:first + 3] == list(NEW)
    assert first > names.index("prefill.cross_rows_share")
    listed = {m["name"]: m for m in manifest["per_layer"]}
    for name, (unit, source, layer) in NEW.items():
        m = listed[name]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            unit, "lower", source, layer), name
        assert m["moves"] == "serve_tokens_per_s"
        assert set(LISTED) <= set(m["workloads"])
        assert os.path.exists(os.path.join(
            tree, "benchmark", "metrics", name + ".py"))
    # the one they supersede stays: a PR may only add
    assert "step.prefill_share" in names


# ------------------------------------------------------------ the rehearsals

@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """``tools/prefill_table.py --workload <rehearsal> --trace 1`` in a
    subprocess: ``benchmark/run.py``'s own run and result line, and one
    note line more with the window's table and the three readings."""
    cache = tmp_path_factory.mktemp("jax_cache")
    done = {}

    def run(cell):
        if cell not in done:
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       JAX_COMPILATION_CACHE_DIR=str(cache),
                       PYTHONPATH=ROOT + os.pathsep
                       + os.environ.get("PYTHONPATH", ""))
            proc = subprocess.run(
                [sys.executable, os.path.join(ROOT, "tools",
                                              "prefill_table.py"),
                 "--workload", cell, "--seed", str(2**31 + 37),
                 "--seconds", "1.5", "--trace", "1"],
                cwd=ROOT, env=env, capture_output=True, text=True,
                timeout=600)
            assert proc.returncode == 0, proc.stderr[-2000:]
            lines = proc.stdout.strip().splitlines()
            done[cell] = (json.loads(lines[-1]),
                          {n["phase"]: n for n in map(json.loads,
                                                      lines[:-1])})
        return done[cell]
    return run


@pytest.mark.parametrize("cell", sorted(REHEARSALS))
def test_a_traced_rehearsal_reports_all_three(traced, cell):
    """Each of the four rehearsal cells that stand for a backlog cell
    gives the three readings as numbers; where ``BENCHMARK.json`` lists
    them for the cell they are on the result line, the same numbers."""
    result, notes = traced(cell)
    assert result["correct"] is True
    note = notes["prefill_programs"]
    got = note["readings"]
    assert set(got) == set(NEW)
    assert 0 < got["prefill.device_share"] < 100
    assert 0 < got["prefill.padded_rows_share"] < 100
    assert got["prefill.device_us_per_prompt_token"] > 0
    # the share is the histograms' own, program by program
    assert got["prefill.device_share"] == pytest.approx(
        note["hist_prefill_share"], abs=1e-6)
    table = note["by_bucket"]
    assert sum(r["waves"] for r in table.values()) \
        == note["hist"]["prefill"]["count"] > 0
    tokens = sum(r["tokens"] for r in table.values())
    rows = sum(r["rows"] for r in table.values())
    assert got["prefill.padded_rows_share"] == pytest.approx(
        100 * (1 - tokens / rows))
    assert got["prefill.device_us_per_prompt_token"] == pytest.approx(
        1e6 * note["hist"]["prefill"]["sum"] / tokens, rel=1e-6)
    for key, row in table.items():
        batch, seq = map(int, key.split("x"))
        assert row["rows"] == row["waves"] * batch * seq
    on_the_line = {n: result["metrics"][n]["value"] for n in NEW
                   if n in result["metrics"]}
    if REHEARSALS[cell]:
        assert on_the_line == pytest.approx(got)
        assert {n: result["metrics"][n]["unit"] for n in NEW} == {
            n: unit for n, (unit, _, _) in NEW.items()}
    else:
        assert on_the_line == {}
    # no new span: the ring is as far from wrapping as it was
    closed = notes["window_closed"]
    assert 0 < closed["spans_in_window"] < closed["ring_spans"]
