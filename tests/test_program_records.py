"""One record a program: what ``PagedServingEngine`` writes on the spans
it already makes so that a program's enqueue, its device interval and its
commit can be put on one line, and the operator's table built from the
same numbers (``stats()["prefill_by_bucket"]``).

The readback-side span of every program names the span it was dispatched
under (``dispatch_span``) and carries ``device_s``, the interval the
``serving.prefill_s`` / ``serving.decode_step_s`` histograms observe;
both spans of a prefill wave carry what it was given (``requests``,
``tokens``, ``hit_tokens``) beside what it paid for (``rows`` = batch x
seq).  All on the CPU at a tiny size: counts and identities, never a
time worth reporting."""
import dataclasses

import numpy as np
import pytest

from paddle_tpu.observability import metrics, timeline

WAVE, DECODE = "serving.prefill_wave", "serving.decode"
WAVE_KEYS = ("batch", "seq", "requests", "tokens", "rows", "hit_tokens",
             "request_ids")


@pytest.fixture(autouse=True)
def _fresh_ring():
    timeline.configure(None)
    timeline.reset_spans()
    yield
    timeline.reset_spans()


@pytest.fixture(scope="module")
def gpt_model():
    import jax
    from paddle_tpu.models import gpt
    cfg = dataclasses.replace(gpt.gpt_tiny(), max_seq_len=256)
    return gpt.init_params(cfg, jax.random.PRNGKey(0)), cfg


@pytest.fixture(scope="module")
def hybrid_model():
    import jax
    from paddle_tpu.models import phi4flash
    cfg = phi4flash.phi4flash_tiny()
    return phi4flash.init_params(cfg, jax.random.PRNGKey(0)), cfg


def _engine(model, **kw):
    from paddle_tpu.inference.serving import PagedServingEngine
    args = dict(slots=4, max_len=64, page_size=8, num_pages=64,
                seq_buckets=(16, 32), batch_buckets=(1, 2))
    args.update(kw)
    return PagedServingEngine(model, **args)


def _prompt(n, seed, vocab=256):
    return np.random.RandomState(seed).randint(1, vocab, n).astype(np.int32)


def _programs(spans):
    """(dispatch-side, readback-side) spans of the two program names."""
    both = [s for s in spans if s[2] in (WAVE, DECODE)]
    read = [s for s in both if "device_s" in s[5]]
    return [s for s in both if "device_s" not in s[5]], read


def _hist_sums():
    return (metrics.histogram("serving.prefill_s").sum,
            metrics.histogram("serving.decode_step_s").sum)


# ------------------------------------------------- a program is one record

def test_every_readback_names_its_dispatch_and_carries_the_interval(
        gpt_model):
    """Over a run that ends idle every program has two spans of its
    name.  The readback's ``dispatch_span`` is the id of a span of the
    SAME name that closed before the readback opened, no two readbacks
    name the same one, and the ``device_s`` of the wave spans sum to what
    ``serving.prefill_s`` observed over the same steps, those of the
    decode spans to ``serving.decode_step_s``'s."""
    eng = _engine(gpt_model)
    eng.warmup()
    timeline.reset_spans()
    p0, d0 = _hist_sums()
    for i, n in enumerate((10, 12, 20, 11, 25, 9, 30)):
        eng.submit(_prompt(n, i), 4 + i)
    eng.run(max_steps=200)
    p1, d1 = _hist_sums()
    spans = timeline.spans()
    sent, read = _programs(spans)
    st = eng.stats()
    assert len(read) == len(sent) == st["decode_steps"] + st["prefill_calls"]
    by_id = {s[0]: s for s in spans}
    assert len({r[5]["dispatch_span"] for r in read}) == len(read)
    for r in read:
        cause = by_id[r[5]["dispatch_span"]]
        assert cause[2] == r[2] and cause in sent
        assert cause[4] <= r[3]             # it closed before this opened
        assert 0 < r[5]["device_s"]
        # everything the dispatch carried, the readback carries too
        assert {k: v for k, v in r[5].items()
                if k not in ("dispatch_span", "device_s")} == cause[5]
    waves = sum(r[5]["device_s"] for r in read if r[2] == WAVE)
    decodes = sum(r[5]["device_s"] for r in read if r[2] == DECODE)
    assert waves == pytest.approx(p1 - p0, rel=1e-9)
    assert decodes == pytest.approx(d1 - d0, rel=1e-9)
    # the old constant is gone from every span, not moved
    assert not any("paged" in (s[5] or {}) for s in spans)


def test_entry_attributes_reach_the_profiler_and_device_s_does_not(
        gpt_model, monkeypatch):
    """The profiler's annotation is built when a span opens: what the
    readback span is given at entry (``dispatch_span`` and the wave's
    counts) is on the trace's host line, ``device_s`` — known only once
    the tokens have arrived — is the ring's alone."""
    import jax
    seen = []

    class Annotation:
        def __init__(self, name, **attrs):
            seen.append((name, attrs))

        def __enter__(self):
            return self

        def __exit__(self, *a):
            pass

    eng = _engine(gpt_model)
    eng.warmup()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    eng.submit(_prompt(10, 0), 3)
    eng.run(max_steps=50)
    waves = [a for n, a in seen if n == WAVE]
    assert len(waves) == 2
    assert set(waves[0]) == set(WAVE_KEYS)
    assert set(waves[1]) == set(WAVE_KEYS) | {"dispatch_span"}
    decodes = [a for n, a in seen if n == DECODE]
    assert {frozenset(a) for a in decodes} == {
        frozenset({"active"}), frozenset({"active", "dispatch_span"})}


# ---------------------------------- what a wave was given and what it paid

def test_two_prompts_in_a_bucket_of_four_by_128(gpt_model):
    """Prompts of 70 and 100 tokens, admitted together, run as 4 x 128
    rows: both spans of the wave and the operator's table say so."""
    eng = _engine(gpt_model, max_len=256, num_pages=128,
                  seq_buckets=(128,), batch_buckets=(1, 4))
    eng.warmup()
    timeline.reset_spans()
    assert eng.stats()["prefill_by_bucket"] == {}
    eng.submit(_prompt(70, 1), 3)
    eng.submit(_prompt(100, 2), 3)
    eng.run(max_steps=50)
    waves = [s[5] for s in timeline.spans() if s[2] == WAVE]
    assert len(waves) == 2
    for attrs in waves:
        assert (attrs["batch"], attrs["seq"]) == (4, 128)
        assert (attrs["requests"], attrs["tokens"], attrs["rows"],
                attrs["hit_tokens"]) == (2, 170, 512, 0)
    st = eng.stats()
    row = st["prefill_by_bucket"]
    assert list(row) == ["4x128"]
    assert {k: row["4x128"][k] for k in ("waves", "requests", "tokens",
                                         "rows")} == {
        "waves": 1, "requests": 2, "tokens": 170, "rows": 512}
    assert row["4x128"]["device_s"] == waves[1]["device_s"] > 0
    assert (st["prefill_tokens"], st["prefill_padded_rows"]) == (170, 512)


def test_hit_tokens_are_the_positions_the_prefix_cache_supplied(gpt_model):
    """The second request repeats the first's prompt of 20: its two
    whole pages of 8 and the part of a third come from the prefix cache,
    3 page hits for 20 positions (not 24), and the wave says so; a third
    shares two pages of a longer prompt."""
    eng = _engine(gpt_model)
    eng.warmup()
    prompt = _prompt(20, 5)
    eng.submit(prompt.copy(), 2)
    eng.run(max_steps=50)
    timeline.reset_spans()
    eng.submit(prompt.copy(), 2)
    eng.run(max_steps=50)
    waves = [s[5] for s in timeline.spans() if s[2] == WAVE]
    assert [w["hit_tokens"] for w in waves] == [20, 20]
    assert [w["tokens"] for w in waves] == [20, 20]
    timeline.reset_spans()
    eng.submit(np.concatenate([prompt[:16], _prompt(9, 6)]), 2)
    eng.run(max_steps=50)
    waves = [s[5] for s in timeline.spans() if s[2] == WAVE]
    assert [(w["hit_tokens"], w["tokens"]) for w in waves] == [(16, 25)] * 2


def test_table_and_totals_over_mixed_buckets(gpt_model):
    """The table's ``tokens`` and ``rows``, summed, are the two counters;
    its ``waves`` are ``prefill_calls`` and its ``requests`` the
    admissions; its ``device_s`` what ``serving.prefill_s`` observed."""
    eng = _engine(gpt_model)
    eng.warmup()
    p0, _ = _hist_sums()
    lens = (10, 12, 20, 11, 25, 9, 30, 16, 17)
    for i, n in enumerate(lens):
        eng.submit(_prompt(n, i), 3 + i % 3)
    eng.run(max_steps=200)
    st = eng.stats()
    table = st["prefill_by_bucket"]
    assert set(table) <= {"1x16", "2x16", "1x32", "2x32"}
    for key, row in table.items():
        b, s = map(int, key.split("x"))
        assert row["rows"] == row["waves"] * b * s
        assert row["waves"] <= row["requests"] <= row["waves"] * b
        assert row["tokens"] <= row["rows"]
    total = {k: sum(r[k] for r in table.values())
             for k in ("waves", "requests", "tokens", "rows", "device_s")}
    assert total["tokens"] == st["prefill_tokens"] == sum(lens)
    assert total["rows"] == st["prefill_padded_rows"]
    assert total["waves"] == st["prefill_calls"]
    assert total["requests"] == st["requests_admitted"] == len(lens)
    assert total["device_s"] == pytest.approx(_hist_sums()[0] - p0,
                                              rel=1e-9)
    # a copy: the caller cannot reach the engine's own sums
    key = next(iter(table))
    table[key]["waves"] = -1
    assert eng.stats()["prefill_by_bucket"][key]["waves"] >= 1


def test_nothing_is_counted_while_warming(gpt_model, hybrid_model):
    """``warmup()`` runs a wave of every bucket: the spans are in the
    ring (a warm-up is traced like anything else), the table, the two
    totals and the family's own per-wave counts stay empty."""
    for model in (gpt_model, hybrid_model):
        eng = _engine(model)
        timeline.reset_spans()
        eng.warmup()
        assert any(s[2] == WAVE and "device_s" in s[5]
                   for s in timeline.spans())
        st = eng.stats()
        assert st["prefill_by_bucket"] == {}
        assert st.get("prefill_tokens", 0) == 0
        assert st.get("prefill_padded_rows", 0) == 0
        assert st.get("prefill_rows", 0) == 0


def test_a_preempted_requests_second_prefill_counts_again(gpt_model):
    """A pool too small for both answers: the newer request is preempted
    and prefilled again from its prompt.  That wave is paid again, so it
    is counted again — the table holds more tokens than the prompts."""
    eng = _engine(gpt_model, slots=2, max_len=32, page_size=4, num_pages=9,
                  seq_buckets=(16,), batch_buckets=(1,), prefix_cache=False)
    eng.warmup()
    reqs = [eng.submit(_prompt(12, 7), 14), eng.submit(_prompt(11, 8), 14)]
    eng.run(max_steps=400)
    st = eng.stats()
    assert all(r.done and not r.failed for r in reqs)
    again = sum(len(r.prompt) * r.preemptions for r in reqs)
    assert st["preemptions"] >= 1 and again > 0
    assert st["prefill_tokens"] == sum(len(r.prompt) for r in reqs) + again
    row = st["prefill_by_bucket"]["1x16"]
    assert row["waves"] == row["requests"] == 2 + st["preemptions"]
    assert row["rows"] == st["prefill_padded_rows"] == 16 * row["waves"]


def test_the_hybrid_familys_host_rows_are_its_devices_rows(hybrid_model):
    """Two counts of one thing, from both sides: the rows the host says
    its waves paid for (batch x seq, from the buckets it chose) and the
    rows the wave's own program says its stateful layers were traced
    over (``prefill_rows``, returned behind the first tokens)."""
    eng = _engine(hybrid_model, slots=3, page_size=4, num_pages=40,
                  seq_buckets=(8, 16, 32))
    eng.warmup()
    for i, n in enumerate((3, 9, 16, 21, 6, 30, 8)):
        eng.submit(_prompt(n, 20 + i, 512), 5)
    eng.run(max_steps=300)
    st = eng.stats()
    assert st["prefill_calls"] >= 3
    assert st["prefill_padded_rows"] == st["prefill_rows"] > 0
    assert sum(r["rows"] for r in st["prefill_by_bucket"].values()) \
        == st["prefill_rows"]
    # one row a prompt goes through the stateless layers
    assert st["prefill_cross_rows"] == sum(
        r["waves"] * int(k.split("x")[0])
        for k, r in st["prefill_by_bucket"].items())


# ------------------------------------------------------- the chunked path

def test_a_chunk_carries_its_tokens_and_rows_and_a_histogram_of_its_own(
        gpt_model):
    """A prompt of 21 in chunks of 8 (8 + 8 + 5): each chunk's span says
    what it was given and what its program ran, and the admission's
    HOST wall time goes to ``serving.prefill_chunked_s`` —
    ``serving.prefill_s`` keeps one kind of interval, the device's for a
    wave, so a chunked prompt leaves it alone."""
    eng = _engine(gpt_model, prefill_chunk=8, seq_buckets=(8, 16))
    eng.warmup()
    h_wave = metrics.histogram("serving.prefill_s")
    h_chunked = metrics.histogram("serving.prefill_chunked_s")
    w0, c0, s0 = h_wave.count, h_chunked.count, h_chunked.sum
    timeline.reset_spans()
    eng.submit(_prompt(21, 3), 4)
    eng.run(max_steps=100)
    chunks = [s for s in timeline.spans() if s[2] == "serving.prefill_chunk"]
    assert [(c[5]["pos"], c[5]["tokens"], c[5]["rows"]) for c in chunks] \
        == [(0, 8, 8), (8, 8, 8), (16, 5, 8)]
    assert eng.stats()["prefill_chunks"] == 3
    assert h_wave.count == w0
    assert h_chunked.count == c0 + 1
    assert h_chunked.sum - s0 >= sum(c[4] - c[3] for c in chunks)
    # a chunk is not a wave: the table and its totals are the waves'
    assert eng.stats()["prefill_by_bucket"] == {}
    assert eng.stats().get("prefill_tokens", 0) == 0
