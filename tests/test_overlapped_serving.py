"""The paged engine's loop runs one step ahead of its readbacks: the
sampled tokens stay on the device between programs, ``step()`` dispatches
step n+1 before it reads step n back, and whatever needs the host's view
whole first drains what is in flight (``stats()["drains"]`` names who
asked).  Everything here is CPU, tiny and token-exact: the overlapped
loop, the drained loop (``capture_logits=True``) and ``gpt.generate``
give the same tokens, whatever made the loop drain on the way.
"""
import time

import numpy as np
import pytest

from paddle_tpu.observability import metrics


@pytest.fixture(scope="module")
def gpt_model():
    import jax
    from paddle_tpu.models import gpt as G
    cfg = G.GPTConfig(vocab_size=256, hidden_size=32, num_layers=2,
                      num_heads=2, max_seq_len=64, dtype="float32",
                      use_flash=False, remat=False)
    return G.init_params(cfg, jax.random.PRNGKey(0)), cfg


@pytest.fixture(scope="module")
def latent_model():
    import jax
    from paddle_tpu.models import deepseek_v3 as ds
    cfg = ds.deepseek_v3_tiny()
    return ds.init_params(cfg, jax.random.PRNGKey(0)), cfg


def _engine(model, **kw):
    from paddle_tpu.inference.serving import PagedServingEngine
    args = dict(slots=3, max_len=32, page_size=8, seq_buckets=(8, 16),
                batch_buckets=(1, 2))
    args.update(kw)
    eng = PagedServingEngine(model, **args)
    eng.warmup()
    return eng


def _want(model, prompt, n):
    import jax.numpy as jnp
    from paddle_tpu.models import gpt as G
    params, cfg = model
    out = G.generate(params, cfg, jnp.asarray(prompt)[None], n)
    return [int(t) for t in np.asarray(out)[0, len(prompt):]]


def _churn(seed, vocab, n=10):
    rng = np.random.RandomState(seed)
    return [(rng.randint(1, vocab, rng.randint(3, 15)).astype(np.int32),
             int(rng.randint(3, 8))) for _ in range(n)]


def _assert_exact(model, reqs):
    for r in reqs:
        assert r.done and not r.failed, r.id
        assert list(r.tokens) == _want(model, r.prompt, r.max_new_tokens), \
            r.id


# ------------------------------------------------- the two loops, one answer

@pytest.mark.parametrize("family", ["gpt", "deepseek_v3"])
def test_overlapped_and_drained_loops_emit_the_same_tokens(
        family, gpt_model, latent_model):
    """Ten requests churn through three slots.  The loop as served runs
    ahead in every decode step and drains only when it has nothing left
    to dispatch; ``capture_logits=True`` is the synchronous loop.  Same
    tokens from both (GPT: ``gpt.generate``'s), one decode executable
    each, and the latent family's expert counts still ride the readback
    — one count a decoded row, none for a row that was dropped."""
    model = gpt_model if family == "gpt" else latent_model
    kw = {} if family == "gpt" else dict(max_len=64, seq_buckets=(16, 32))
    traffic = _churn(3, model[1].vocab_size)
    out = {}
    for loop, cap in (("overlapped", False), ("drained", True)):
        eng = _engine(model, capture_logits=cap, **kw)
        reqs = [eng.submit(p, m) for p, m in traffic]
        done = eng.run(max_steps=400)
        st = eng.stats()
        assert len(done) == len(reqs) and all(r.done for r in reqs)
        assert st["decode_compiles"] == 1
        assert eng._decode_jit._cache_size() == 1    # no silent retrace
        assert st["pages_in_use"] == 0 and st["kv_tokens_held"] == 0
        assert st["tokens_generated"] == sum(len(r.tokens) for r in reqs)
        out[loop] = ([list(r.tokens) for r in reqs], st)
    (toks, st), (toks_d, st_d) = out["overlapped"], out["drained"]
    assert toks == toks_d
    if family == "gpt":
        assert toks == [_want(model, p, m) for p, m in traffic]
    else:
        cfg = model[1]
        decoded = sum(len(t) - 1 for t in toks)
        for s in (st, st_d):
            assert s["moe_assignments"] == (
                decoded * cfg.num_experts_per_tok
                * (cfg.num_hidden_layers - 1))
    # every decode dispatch found the wave or the step before it unread
    assert st["steps_overlapped"] == st["decode_steps"] > 0
    assert set(st["drains"]) <= {"idle"}
    assert st_d["steps_overlapped"] == 0
    assert st_d["drains"] == {
        "capture_logits": st_d["prefill_calls"] + st_d["decode_steps"]}
    # a slot is handed on when its last token is COMMITTED, a call after
    # the one that dispatched it: the same tokens in no fewer steps
    assert st["decode_steps"] >= st_d["decode_steps"]


def test_warmup_builds_the_same_programs_and_traffic_builds_none(gpt_model):
    """The token vector is an operand of the programs there were: the
    (batch, seq) ladder and ONE decode executable, whether the vector
    comes from the host (first dispatch, first after a drain) or from
    the program before.  No scatter, slice or copy of its own."""
    from paddle_tpu.inference.serving import PagedServingEngine
    eng = PagedServingEngine(gpt_model, slots=3, max_len=32, page_size=8,
                             seq_buckets=(8, 16), batch_buckets=(1, 2))
    c0 = metrics.counter("compile.count").value
    assert eng.warmup() == 4                    # 2 x 2 prefill rungs
    built = metrics.counter("compile.count").value - c0
    assert built == 4 + 1 + 1                   # + decode + the COW copy
    reqs = [eng.submit(p, m) for p, m in _churn(11, 256, n=6)]
    eng.step()
    eng.step()
    assert eng._inflight                        # chained on the device
    eng.cancel("nobody")                        # a drain: host vector next
    eng.run(max_steps=200)
    assert metrics.counter("compile.count").value - c0 == built
    _assert_exact(gpt_model, reqs)
    assert eng._decode_jit._cache_size() == 1
    assert all(fn._cache_size() == 1 for fn in eng._prefill.entries.values())


# ------------------------------------------------------ finishing with a lag

def test_eos_emits_nothing_after_it_and_its_extra_row_reaches_nobody(
        gpt_model):
    """A request that ends by ``eos_token`` is known at commit only: its
    slot has run one more position by then.  That token is dropped, the
    pages are released, and the request that takes the slot over reads
    none of it."""
    eng = _engine(gpt_model, slots=2)
    free0 = eng.stats()["pages_free"]
    prompt = np.arange(1, 7, dtype=np.int32)
    want = _want(gpt_model, prompt, 8)
    eos = want[2]
    first = want.index(eos)                          # eos may repeat
    a = eng.submit(prompt, 8, eos_token=eos)
    rest = [eng.submit(p, m) for p, m in _churn(5, 256, n=5)]
    eng.run(max_steps=400)
    assert a.done and a.finish_reason == "eos"
    assert list(a.tokens) == want[:first + 1]
    assert len(a.token_t) == len(a.tokens)
    _assert_exact(gpt_model, rest)
    assert any(r.slot == a.slot for r in rest)       # the slot was reused
    st = eng.stats()
    # the dropped position is neither a token nor a held page
    assert st["tokens_generated"] == sum(
        len(r.tokens) for r in [a] + rest)
    assert st["pages_in_use"] == 0 and st["pages_free"] == free0
    assert st["steps_overlapped"] == st["decode_steps"]


def test_a_request_finishing_in_admission_never_runs_a_decode_step(
        gpt_model):
    """``max_new_tokens=1`` ends by length with the wave's own token:
    known by count, so no decode step is dispatched for it, and the
    ``step()`` that commits the wave returns it."""
    eng = _engine(gpt_model)
    r = eng.submit(np.arange(1, 6, dtype=np.int32), 1)
    assert eng.step() == [r]
    assert r.done and r.finish_reason == "length"
    assert list(r.tokens) == _want(gpt_model, r.prompt, 1)
    st = eng.stats()
    assert st["decode_steps"] == 0 and st["drains"] == {"idle": 1}
    assert st["pages_in_use"] == 0 and not eng._busy()


def test_step_returns_a_request_with_the_call_that_commits_it(gpt_model):
    """One step of lag: the call that dispatches a request's last
    position does not return it, the next call does — and an engine
    whose last tokens are still in flight is busy, so ``run()`` and
    ``generate()`` end with every token delivered."""
    eng = _engine(gpt_model)
    r = eng.submit(np.arange(1, 6, dtype=np.int32), 3)
    assert eng.step() == []          # wave + decode 1 dispatched
    assert r.tokens == [] and eng._busy()
    assert eng.step() == []          # decode 2 dispatched, wave + 1 read
    assert len(r.tokens) == 2 and eng._busy()
    # nothing left to dispatch: the call drains, and returns the request
    assert eng.step() == [r] and not eng._busy()
    assert list(r.tokens) == _want(gpt_model, r.prompt, 3)
    prompts = [p for p, _ in _churn(7, 256, n=7)]
    assert eng.generate(prompts, max_new_tokens=5) == [
        _want(gpt_model, p, 5) for p in prompts]
    assert not eng._inflight and eng.stats()["pages_in_use"] == 0


# ------------------------------------------------------------- the one drain

def _preempted(model):
    eng = _engine(model, slots=2, page_size=4, num_pages=9,
                  seq_buckets=(16,), batch_buckets=(1,), prefix_cache=False)
    reqs = [eng.submit(np.arange(lo, lo + 12, dtype=np.int32), 16)
            for lo in (1, 3)]
    eng.run(max_steps=400)
    assert eng.stats()["preemptions"] >= 1
    return eng, reqs


def _cancelled(model):
    eng = _engine(model, slots=2)
    reqs = [eng.submit(p, m) for p, m in _churn(13, 256, n=5)]
    eng.step()
    eng.step()
    assert eng._inflight
    gone = eng.cancel(reqs[-1].id)               # still queued
    assert gone is reqs.pop() and not eng._inflight
    assert eng.cancel(reqs[0].id) is None        # running: not cancelled
    eng.run(max_steps=400)
    assert not gone.done and not gone.tokens
    return eng, reqs


def _chunked(model):
    eng = _engine(model, prefill_chunk=8, seq_buckets=(8, 16))
    short = eng.submit(np.arange(1, 6, dtype=np.int32), 12)
    eng.step()
    long = eng.submit(np.arange(2, 22, dtype=np.int32), 5)   # 3 chunks
    eng.run(max_steps=400)
    assert eng.stats()["prefill_chunks"] == 3
    return eng, [short, long]


def _ship(model):
    """The prefill side extracts a ``prefill_only`` request's pages when
    its first token is committed: in the call that dispatched its wave."""
    from paddle_tpu.inference.serving import Request
    eng = _engine(model, kv_handoff=True)
    other = eng.submit(np.arange(1, 6, dtype=np.int32), 6)
    eng.step()
    req = Request(np.arange(3, 16, dtype=np.int32), 8)
    req.prefill_only = True
    eng.submit(req)
    assert eng.step() == [req] and req.finish_reason == "prefill_done"
    assert req.kv_payload is not None
    eng.run(max_steps=100)
    return eng, other, req


def _handed_off(model):
    eng, other, _ = _ship(model)
    return eng, [other]


def _injected(model):
    """The decode side writes a slot from the host (pages, first token)."""
    from paddle_tpu.inference.serving import Request
    src = _ship(model)[2]
    eng = _engine(model, kv_handoff=True)
    other = eng.submit(np.arange(1, 6, dtype=np.int32), 9)
    eng.step()
    eng.step()
    req = Request(src.prompt, 8)
    eng.submit_prefilled(req, src.tokens[0], src.kv_payload)
    eng.run(max_steps=100)
    assert eng.stats()["kv_injects"] == 1
    return eng, [other, req]


def _faulted_back(model):
    """A repeat whose evicted prompt pages come back from the host tier
    while another request is in flight."""
    eng = _engine(model, page_size=4, num_pages=10, host_tier_mb=4)
    prompt = np.arange(1, 11, dtype=np.int32)
    first = eng.submit(prompt, 6)
    eng.run()
    rng = np.random.RandomState(7)
    for _ in range(4):          # unique chains push the pages off-device
        eng.submit(rng.randint(1, 256, 10).astype(np.int32), 4)
        eng.run()
    other = eng.submit(np.arange(40, 45, dtype=np.int32), 10)
    eng.step()
    eng.step()
    again = eng.submit(prompt, 6)
    eng.run(max_steps=100)
    assert eng.stats()["fault_backs"] == 1
    return eng, [first, other, again]


def _fault_injected(model):
    """An injected fault is aimed at a step by its number: with faults
    installed the loop commits what is in flight before every step."""
    from paddle_tpu.testing import faults
    faults.clear()
    faults.install("engine_error:step=2")
    try:
        eng = _engine(model, slots=2)
        reqs = [eng.submit(np.arange(lo, lo + 7, dtype=np.int32), 5)
                for lo in (1, 2)]
        with pytest.raises(faults.InjectedFault):
            eng.run()
        victims = eng.take_aborted()
        assert victims and {v.id for v in victims} <= {r.id for r in reqs}
        assert eng.stats()["pages_in_use"] == 0 and not eng._inflight
        for v in victims:
            eng.submit(v.reset_for_retry())
        eng.run()
    finally:
        faults.clear()
    return eng, reqs


def _aborted(model):
    """A dispatch that raises with a step in flight: the step is
    DISCARDED with the pool it wrote, its requests restart from their
    prompts, and nothing of it is committed afterwards."""
    eng = _engine(model, slots=2)
    reqs = [eng.submit(p, m) for p, m in _churn(17, 256, n=4)]
    eng.step()
    eng.step()
    real, calls = eng._decode_jit, []

    def broken(*args):
        if not calls:
            calls.append(1)
            raise RuntimeError("device fell over")
        return real(*args)

    eng._decode_jit = broken
    held = [len(r.tokens) for r in reqs]
    with pytest.raises(RuntimeError, match="fell over"):
        eng.step()
    assert not eng._inflight and not eng._active.any()
    victims = eng.take_aborted()
    assert len(victims) == 2
    assert [len(r.tokens) for r in reqs] == held     # nothing committed
    for v in victims:
        eng.submit(v.reset_for_retry())
    eng.run(max_steps=400)
    return eng, reqs


@pytest.mark.parametrize("scenario,reason", [
    (_preempted, "page_exhaustion"), (_cancelled, "cancel"),
    (_chunked, "chunk_done"), (_handed_off, "handoff"),
    (_injected, "inject"), (_faulted_back, "fault_back"),
    (_fault_injected, "fault_injection"), (_aborted, "abort")],
    ids=lambda x: x if isinstance(x, str) else "")
def test_what_needs_the_hosts_view_drains_the_step_in_flight(
        gpt_model, scenario, reason):
    """Each rare path finds a step in flight, commits it (an abort
    discards it) through the one drain, which counts it under the
    path's name — and every request still gets exactly its tokens."""
    eng, reqs = scenario(gpt_model)
    _assert_exact(gpt_model, reqs)
    st = eng.stats()
    assert st["drains"].get(reason, 0) >= 1, st["drains"]
    assert not eng._inflight and not eng._busy()
    assert st["pages_in_use"] == 0 and st["decode_compiles"] == 1


@pytest.mark.parametrize("seed,num_pages,chunk", [(2, 14, 16), (10, 20, None)])
def test_mixed_traffic_under_pool_pressure_matches_the_drained_loop(
        gpt_model, seed, num_pages, chunk):
    """Everything at once, arrivals between steps: shared prefixes (COW
    on the tail page), ``eos`` requests, a pool small enough to preempt,
    chunked prompts.  The loop that runs ahead and the drained loop give
    every request the same tokens and leave no page behind."""
    rng = np.random.RandomState(seed)
    base = [rng.randint(1, 256, 12).astype(np.int32) for _ in range(3)]
    traffic = []
    for _ in range(20):
        if rng.rand() < 0.5:
            p = np.concatenate([
                base[rng.randint(3)][:rng.choice([4, 8, 12])],
                rng.randint(1, 256, rng.randint(1, 6)).astype(np.int32)])
        else:
            p = rng.randint(1, 256, rng.randint(3, 30)).astype(np.int32)
        eos = int(rng.randint(1, 256)) if rng.rand() < 0.6 else None
        traffic.append((p, int(rng.randint(1, 14)), eos))
    out = {}
    for cap in (False, True):
        eng = _engine(gpt_model, slots=4, max_len=48, page_size=4,
                      seq_buckets=(8, 16, 32), batch_buckets=(1, 2, 4),
                      num_pages=num_pages, prefill_chunk=chunk,
                      capture_logits=cap)
        arrivals = np.random.RandomState(seed + 1000)
        reqs, pending, steps = [], list(traffic), 0
        while pending or eng._busy():
            for p, m, eos in pending[:arrivals.randint(0, 3)]:
                reqs.append(eng.submit(p, m, eos_token=eos))
                pending.pop(0)
            eng.step()
            steps += 1
            assert steps < 3000
        st = eng.stats()
        assert all(r.done and not r.failed for r in reqs)
        assert st["pages_in_use"] == 0 and st["decode_compiles"] == 1
        out[cap] = ([list(r.tokens) for r in reqs], st)
    assert out[False][0] == out[True][0]
    st = out[False][1]
    assert st["preemptions"] >= 1 and st["cow_copies"] >= 1
    assert st["steps_overlapped"] > st["decode_steps"] // 2
    assert st["drains"].get("page_exhaustion", 0) >= 1


# ------------------------------------------------------- the two histograms

def test_histogram_intervals_do_not_overlap_and_fit_in_the_wall(
        gpt_model, monkeypatch):
    """``serving.decode_step_s`` and ``serving.prefill_s`` observe, for
    each program, the time from the later of (its enqueue returned, the
    program before it arrived on the host) to its own arrival: the time
    the device had it at the head of its queue.  So one observation a
    program, no two intervals overlap, and their sum is no more than
    the wall time — whatever the host did meanwhile."""
    eng = _engine(gpt_model)
    seen = []
    for hist, kind in ((eng._h_decode, "decode"), (eng._h_prefill, "wave")):
        def observe(dt, kind=kind, real=hist.observe):
            seen.append((kind, eng._t_arrived - dt, eng._t_arrived))
            real(dt)
        monkeypatch.setattr(hist, "observe", observe)
    t0 = time.perf_counter()
    reqs = [eng.submit(p, m) for p, m in _churn(19, 256, n=8)]
    eng.run(max_steps=400)
    wall = time.perf_counter() - t0
    _assert_exact(gpt_model, reqs)
    st = eng.stats()
    assert [k for k, _, _ in seen].count("decode") == st["decode_steps"]
    assert [k for k, _, _ in seen].count("wave") == st["prefill_calls"]
    assert all(e >= s >= t0 for _, s, e in seen)
    assert all(nxt[1] >= prev[2] for prev, nxt in zip(seen, seen[1:]))
    assert sum(e - s for _, s, e in seen) <= wall
