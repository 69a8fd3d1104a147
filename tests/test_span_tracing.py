"""The one span primitive (observability/timeline.py::span) and what the
serving engine records with it: ids and parents, the bounded ring, the
mirror into ``jax.profiler.TraceAnnotation``, the span tree of one
``PagedServingEngine.step()``, the histograms fed by span durations,
``Request.token_t``, and the named scopes of the jitted steps.

All on the CPU at ``gpt_tiny`` size; nothing here is a time worth
reporting."""
import contextlib
import re
import threading
import time

import numpy as np
import pytest

from paddle_tpu.observability import metrics, timeline


@pytest.fixture(autouse=True)
def _fresh_ring():
    timeline.configure(None)
    timeline.reset_spans()
    yield
    timeline.reset_spans()


def _self_times(spans):
    covered = {}
    for sid, parent, name, t0, t1, _ in spans:
        if parent is not None:
            covered[parent] = covered.get(parent, 0.0) + (t1 - t0)
    return {sid: (t1 - t0) - covered.get(sid, 0.0)
            for sid, parent, name, t0, t1, _ in spans}


# ---------------------------------------------------------- the primitive

def test_ids_parents_and_self_time_across_two_threads():
    def work(tag):
        with timeline.span(f"{tag}.outer"):
            time.sleep(0.002)
            with timeline.span(f"{tag}.inner", k=tag):
                time.sleep(0.002)
            with timeline.span(f"{tag}.inner"):
                pass

    threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    spans = timeline.spans()
    assert len(spans) == 6 and len({s[0] for s in spans}) == 6
    by_name = {}
    for s in spans:
        by_name.setdefault(s[2], []).append(s)
    own = _self_times(spans)
    for tag in "ab":
        (outer,) = by_name[f"{tag}.outer"]
        inner = by_name[f"{tag}.inner"]
        assert outer[1] is None
        # a parent is the span open on the SAME thread, never the other's
        assert [s[1] for s in inner] == [outer[0]] * 2
        assert inner[0][5] == {"k": tag} and inner[1][5] is None
        # a child closes, and lands in the ring, before its parent
        assert spans.index(inner[0]) < spans.index(outer)
        assert outer[3] <= inner[0][3] <= inner[0][4] <= outer[4]
        dur = outer[4] - outer[3]
        assert own[outer[0]] == pytest.approx(
            dur - sum(s[4] - s[3] for s in inner))
        assert 0.002 <= own[outer[0]] < dur


def test_ring_is_bounded_and_counts_what_it_drops(monkeypatch):
    import collections
    monkeypatch.setattr(timeline, "_ring", collections.deque(maxlen=8))
    for i in range(11):
        with timeline.span("s", i=i):
            pass
    spans = timeline.spans()
    assert len(spans) == 8 and timeline.spans_dropped() == 3
    assert [s[5]["i"] for s in spans] == list(range(3, 11))   # oldest first
    spans.clear()                                   # a copy, not the ring
    assert len(timeline.spans()) == 8
    timeline.reset_spans()
    assert timeline.spans() == [] and timeline.spans_dropped() == 0


def test_real_ring_holds_eight_backlog_windows():
    # ~100 spans a second over a 51 s window with ramp and traced tail
    assert timeline._ring.maxlen == timeline.RING_SPANS >= 8 * 7000


def test_span_enters_a_trace_annotation_of_its_name(monkeypatch):
    import jax
    seen = []

    class Annotation:
        def __init__(self, name, **kw):
            self.name, self.kw = name, kw

        def __enter__(self):
            seen.append(("enter", self.name, self.kw))

        def __exit__(self, *a):
            seen.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    with timeline.span("serving.x", batch=2):
        with timeline.span("serving.y"):
            pass
    assert seen == [("enter", "serving.x", {"batch": 2}),
                    ("enter", "serving.y", {}),
                    ("exit", "serving.y"), ("exit", "serving.x")]
    assert [s[2] for s in timeline.spans()] == ["serving.y", "serving.x"]


def test_real_trace_annotation_takes_the_engines_attributes():
    # ids of any hashable kind, lists of them: what the engine passes
    with timeline.span("serving.prefill_wave", batch=1, seq=16, paged=True,
                       request_ids=[0, "a", ("t", 1)]):
        pass
    assert len(timeline.spans()) == 1


def test_compile_inside_a_span_names_that_span_as_parent():
    import jax
    import jax.numpy as jnp
    timeline.install_compile_hook()
    x = jnp.ones((7,))
    timeline.reset_spans()
    with timeline.span("serving.step", step=1) as step:
        jax.jit(lambda v: v * 5 - 2)(x).block_until_ready()
    compiles = [s for s in timeline.spans() if s[2] == "xla_compile"]
    assert compiles, timeline.spans()
    for sid, parent, name, t0, t1, attrs in compiles:
        assert parent == step.id and t1 > t0 and attrs["kind"]
        assert t1 <= step.t1
    jax.jit(lambda v: v * 7 - 2)(x).block_until_ready()      # no span open
    assert [s[1] for s in timeline.spans()
            if s[2] == "xla_compile"][-1] is None


def test_sinks_get_the_same_record(tmp_path):
    import json
    timeline.configure(str(tmp_path))
    try:
        with timeline.span("outer") as outer:
            with timeline.span("inner", k=3) as inner:
                pass
    finally:
        timeline.configure(None)
    lines = [json.loads(ln) for ln in open(tmp_path / "events_rank0.jsonl")]
    rec = {ln["name"]: ln for ln in lines if ln["event"] == "span"}
    assert rec["inner"]["id"] == inner.id
    assert rec["inner"]["parent"] == outer.id == rec["outer"]["id"]
    assert rec["outer"]["parent"] is None and rec["inner"]["k"] == 3
    assert rec["inner"]["t0"] == pytest.approx(inner.t0, abs=1e-5)
    assert rec["inner"]["dur_s"] == pytest.approx(inner.dur, abs=1e-5)
    assert rec["inner"]["depth"] == rec["outer"]["depth"] + 1


# ------------------------------------------------------ the serving engine

@pytest.fixture(scope="module")
def tiny_model():
    import jax
    from paddle_tpu.models import gpt
    cfg = gpt.gpt_tiny()
    return gpt.init_params(cfg, jax.random.PRNGKey(0)), cfg


def _engine(tiny_model, **kw):
    from paddle_tpu.inference.serving import PagedServingEngine
    args = dict(slots=4, page_size=8, num_pages=64, max_len=64,
                seq_buckets=(16, 32), batch_buckets=(1, 2))
    args.update(kw)
    eng = PagedServingEngine(tiny_model, **args)
    eng.warmup()
    return eng


def _prompt(n, seed, vocab):
    return np.random.RandomState(seed).randint(0, vocab, (n,)).astype(
        np.int32)


def _tree(spans):
    """``(name, [children...])`` of every top-level span, by start."""
    kids = {}
    for s in sorted(spans, key=lambda s: s[3]):
        kids.setdefault(s[1], []).append(s)

    def node(s):
        return (s[2], [node(c) for c in kids.get(s[0], [])])
    return [node(s) for s in kids.get(None, [])]


def test_one_step_yields_the_span_tree_and_self_times_sum(tiny_model):
    """The loop runs a step ahead of its readbacks, so a program has two
    spans of its name: one where it is dispatched, and one — a ``step()``
    later in steady state — where its tokens are read back and
    committed.  Every name is one of the four sets that partition
    ``serving.step``."""
    from paddle_tpu.inference.serving import Request
    eng = _engine(tiny_model)
    vocab = tiny_model[1].vocab_size
    # two prompts of one bucket (one wave of two), a third of another
    for i, n in enumerate((10, 12, 20)):
        eng.submit(Request(_prompt(n, i, vocab), 5, request_id=i))
    h_decode = metrics.histogram("serving.decode_step_s")
    h_prefill = metrics.histogram("serving.prefill_s")
    d0, p0 = h_decode.count, h_prefill.count
    d_sum, p_sum = h_decode.sum, h_prefill.sum
    timeline.reset_spans()
    t_first = time.perf_counter()
    eng.step()
    eng.step()
    spans = timeline.spans()

    pager_admit = ("serving.pager.admit", [])
    operands = ("serving.prefill_operands", [])
    dispatched = ("serving.prefill_wave",
                  [("serving.prefill_wave.dispatch", [])])
    read_back = ("serving.prefill_wave",
                 [("serving.prefill_wave.readback", [])])
    decode_out = [("serving.pager.ensure", []),
                  ("serving.decode_operands", []),
                  ("serving.decode", [("serving.decode.dispatch", [])])]
    assert _tree(spans) == [
        # step 1 dispatches two waves and a decode step, reads nothing
        ("serving.step", [
            ("serving.admit", [pager_admit, pager_admit, operands,
                               dispatched, pager_admit, operands,
                               dispatched]),
            *decode_out]),
        # step 2 dispatches its decode step, THEN reads step 1 back:
        # the waves first, as the device ran them
        ("serving.step", [
            ("serving.admit", []), *decode_out, read_back, read_back,
            ("serving.decode", [("serving.decode.readback", []),
                                ("serving.decode.commit", [])])]),
    ]
    by_name = {}
    for s in spans:
        by_name.setdefault(s[2], []).append(s)
    first, second = sorted(by_name["serving.step"], key=lambda s: s[3])
    assert (first[5]["step"], second[5]["step"]) == (eng._step_idx - 1,
                                                     eng._step_idx)
    assert [s[5]["request_id"] for s in by_name["serving.pager.admit"]] \
        == [0, 1, 2]
    assert all(s[5]["hits"] == 0 for s in by_name["serving.pager.admit"])
    # a wave's two spans carry the same attributes
    waves = sorted(by_name["serving.prefill_wave"], key=lambda s: s[3])
    assert [w[5]["request_ids"] for w in waves] == [[0, 1], [2]] * 2
    assert [(w[5]["batch"], w[5]["seq"]) for w in waves] \
        == [(2, 16), (1, 32)] * 2
    assert [d[5]["active"] for d in by_name["serving.decode"]] == [3, 3, 3]
    assert eng.stats()["steps_overlapped"] == 2

    # the four name sets of benchmark/lib/spans.py partition both steps
    from benchmark.lib import spans as reducer
    known = (reducer.SCHEDULER + reducer.PAGER + reducer.DISPATCH
             + reducer.READBACK)
    assert set(by_name) <= set(known)
    own = _self_times(spans)
    assert all(v >= 0 for v in own.values())
    assert sum(own.values()) == pytest.approx(
        sum(s[4] - s[3] for s in (first, second)), rel=1e-9)

    # the histograms: one observation for each program READ BACK (step
    # 2's decode is still in flight), each the time from the later of
    # (its enqueue returned, the program before it arrived) to its own
    # arrival — so they follow one another and fit in the wall time
    assert h_decode.count - d0 == 1 and h_prefill.count - p0 == 2
    assert len(eng._inflight) == 1 and not eng._inflight[0].wave
    observed = (h_decode.sum - d_sum) + (h_prefill.sum - p_sum)
    assert 0 < observed <= eng._t_arrived - t_first


def test_decode_histogram_counts_the_decode_spans_over_a_run(tiny_model):
    """Over a run that ends idle every decode program is dispatched once
    and read back once — a ``serving.decode`` span each, the histogram's
    observation with the second — and every wave likewise."""
    eng = _engine(tiny_model)
    vocab = tiny_model[1].vocab_size
    h = metrics.histogram("serving.decode_step_s")
    hp = metrics.histogram("serving.prefill_s")
    c0, p0 = h.count, hp.count
    timeline.reset_spans()
    for i in range(5):
        eng.submit(_prompt(9 + i, 40 + i, vocab), 4 + i)
    eng.run(max_steps=200)
    names = [s[2] for s in timeline.spans()]
    steps = eng.stats()["decode_steps"]
    assert h.count - c0 == steps > 0
    assert names.count("serving.decode.dispatch") == steps
    assert names.count("serving.decode.readback") == steps
    assert names.count("serving.decode.commit") == steps
    assert names.count("serving.decode") == 2 * steps
    assert hp.count - p0 == names.count("serving.prefill_wave.readback") \
        == names.count("serving.prefill_wave.dispatch") > 0
    assert names.count("serving.step") >= steps
    assert "serving.prefill" not in names and \
        "serving.decode_step" not in names          # replaced, not doubled


def test_token_stamps_match_a_stamping_list_through_a_preemption(
        tiny_model):
    """``Request.token_t`` against the harness's ``Stamped`` list (the
    rule it implements: only a NEW position is news), with the pool
    forced to preempt."""
    from benchmark.drivers.serve_engine import Stamped
    from paddle_tpu.inference.serving import Request
    eng = _engine(tiny_model, slots=2, page_size=4, num_pages=9,
                  seq_buckets=(16,), batch_buckets=(1,), prefix_cache=False)
    h_ttft = metrics.histogram("serving.ttft_s")
    h_gap = metrics.histogram("serving.token_gap_s")
    n_ttft, n_gap = h_ttft.count, h_gap.count
    reqs, stamps = [], []
    for i, lo in enumerate((1, 3)):
        req = Request(np.arange(lo, lo + 12, dtype=np.int32), 16,
                      request_id=f"r{i}")
        stamps.append([])
        req.tokens = Stamped(stamps[-1])
        reqs.append(eng.submit(req))
    for _ in range(400):
        if all(r.done for r in reqs):
            break
        eng.step()
        for r, st in zip(reqs, stamps):
            if type(r.tokens) is list:      # scrubbed by the preemption
                fresh = Stamped(st)
                fresh.extend(r.tokens)
                r.tokens = fresh
    assert all(r.done for r in reqs)
    assert sum(r.preemptions for r in reqs) >= 1
    for r, st in zip(reqs, stamps):
        assert len(r.token_t) == len(st) == len(r.tokens) == 16
        assert np.abs(np.asarray(r.token_t) - np.asarray(st)).max() < 50e-6
        assert r.first_token_t == r.token_t[0] > r.submit_t
        assert r.token_t == sorted(r.token_t)
    assert h_ttft.count - n_ttft == 2
    assert h_gap.count - n_gap == 2 * 15


def test_no_stamp_and_no_histogram_while_warming(tiny_model):
    h_ttft = metrics.histogram("serving.ttft_s")
    n = h_ttft.count
    _engine(tiny_model)                       # warm-up runs real requests
    assert h_ttft.count == n


def test_sliding_token_window_is_gone(tiny_model):
    eng = _engine(tiny_model)
    assert "tokens_per_s" not in eng.stats()
    assert not hasattr(eng, "_tok_window")


# ------------------------------------------------------- names on the device

def _lowered_decode_and_prefill(eng):
    jnp = eng._jnp
    slots = eng.slots
    zeros = jnp.zeros((slots,), jnp.int32)
    decode = eng._build_decode().lower(
        eng.params, *eng._cache_operands(), jnp.asarray(eng._tables_np),
        zeros, zeros, jnp.asarray(eng._lens), jnp.asarray(eng._last_tok))
    prefill = eng._build_prefill(2, 16).lower(
        eng.params, *eng._cache_operands(), jnp.zeros((2, 16), jnp.int32),
        jnp.ones((2,), jnp.int32),
        jnp.zeros((2, 16 // eng._page_size), jnp.int32))
    return decode, prefill


def _op_count(lowered):
    text = lowered.as_text()
    return (len(re.findall(r"\bstablehlo\.[a-z_]+", text)),
            text.count("tpu_custom_call"))


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_named_scopes_are_in_the_lowered_steps_and_add_no_op(
        tiny_model, monkeypatch, kv_dtype):
    """Both pools run ONE body of each program (models/gpt.py), so the
    int8 programs carry the same names."""
    import jax
    eng = _engine(tiny_model, kv_dtype=kv_dtype)
    decode, prefill = _lowered_decode_and_prefill(eng)
    text = decode.as_text(debug_info=True)
    for scope in ("embed/", "layer/ln_qkv/", "layer/kv_write/",
                  "layer/paged_attn/", "layer/attn_out/", "layer/ffn/",
                  "head_sample/"):
        assert f'"{scope}' in text or f"/{scope}" in text, scope
    text = prefill.as_text(debug_info=True)
    for scope in ("embed/", "layer/ln_qkv/", "layer/kv_write/",
                  "layer/attn/", "layer/attn_out/", "layer/ffn/",
                  "head_sample/", "kv_scatter/"):
        assert f'"{scope}' in text or f"/{scope}" in text, scope
    named = _op_count(decode), _op_count(prefill)
    # the same two programs traced with every scope a no-op: metadata
    # only means not one operation more, fewer, or of another kind
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare_decode, bare_prefill = _lowered_decode_and_prefill(eng)
    assert "layer/ln_qkv" not in bare_decode.as_text(debug_info=True)
    assert named == (_op_count(bare_decode), _op_count(bare_prefill))
    assert named[0][0] > 100


def test_train_step_scopes_add_no_op(monkeypatch):
    import dataclasses
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import gpt, gpt_hybrid
    from paddle_tpu.parallel.mesh import create_mesh
    cfg = dataclasses.replace(gpt.gpt_tiny(), remat=True)
    mesh = create_mesh(dp=1, tp=1, pp=1, sp=1, devices=jax.devices()[:1])
    params, m, v = gpt_hybrid.init_sharded(cfg, mesh, jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 16), jnp.int32)

    def lowered():
        return gpt_hybrid.make_train_step(cfg, mesh).lower(
            params, m, v, jnp.int32(1), toks, toks, jnp.float32(1e-4))

    named = lowered()
    text = named.as_text(debug_info=True)
    for scope in ("jvp(forward)", "transpose(jvp(forward))",
                  "grad_sync_clip", "adamw"):
        assert scope in text, scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert _op_count(named) == _op_count(lowered())
