"""Paged KV serving (ISSUE 8): the block-table pager, the paged engine's
token-exact parity through slot churn, shared-prefix reuse, chunked
prefill interleaving, page-table edge cases, and the page-exhaustion
preemption path.

Everything here runs on the lax gather fallback (tier-1, CPU); the
Pallas paged-attention kernel itself is validated in interpret mode in
the slow class at the bottom, alongside the other kernel suites.
"""
import os

import numpy as np
import pytest

from paddle_tpu.inference.kv_pager import KVPager, PagesExhausted


# --------------------------------------------------------------------------
# pager units (pure host bookkeeping, no jax)
# --------------------------------------------------------------------------

class TestKVPager:
    def test_alloc_release_roundtrip(self):
        pg = KVPager(9, 4, slots=2, prefix_cache=False)
        table, hits = pg.admit(0, np.arange(10))     # 3 pages
        assert len(table) == 3 and hits == 0
        assert pg.pages_in_use() == 3 and pg.pages_free() == 5
        assert 0 not in table                        # scratch reserved
        pg.release(0)
        assert pg.pages_in_use() == 0 and pg.pages_free() == 8

    def test_prefix_share_refcount(self):
        pg = KVPager(17, 4, slots=3)
        prompt = np.arange(1, 11)                    # 10 tokens, 3 pages
        t0, h0 = pg.admit(0, prompt)
        t1, h1 = pg.admit(1, prompt)
        assert h0 == 0 and h1 == 3
        assert t0 == t1                              # same physical pages
        assert pg.pages_in_use() == 3                # counted once
        pg.release(0)
        assert pg.pages_in_use() == 3                # slot 1 still holds
        pg.release(1)
        assert pg.pages_in_use() == 0
        # retained: a third admission still hits
        t2, h2 = pg.admit(2, prompt)
        assert h2 == 3 and t2 == t0

    def test_partial_prefix_differs(self):
        pg = KVPager(17, 4, slots=2)
        pg.admit(0, np.arange(1, 11))                # tail = tokens (9, 10)
        _, h = pg.admit(1, np.arange(1, 10))         # tail = (9,) — no hit
        assert h == 2                                # the two full pages

    def test_reclaim_lru_eviction(self):
        pg = KVPager(5, 4, slots=2)                  # 4 usable pages
        pg.admit(0, np.arange(8))                    # 2 pages
        pg.release(0)                                # retained
        assert pg.pages_free() == 4
        t, h = pg.admit(1, np.arange(100, 116))      # needs all 4 pages
        assert h == 0 and len(t) == 4
        assert pg.evictions == 2                     # retained pages evicted
        pg.release(1)
        # the evicted prefix no longer hits
        _, h2 = pg.admit(0, np.arange(8))
        assert h2 == 0

    def test_exhaustion_rolls_back(self):
        pg = KVPager(4, 4, slots=2, prefix_cache=False)   # 3 usable
        pg.admit(0, np.arange(8))                    # 2 pages
        with pytest.raises(PagesExhausted):
            pg.admit(1, np.arange(100, 110))         # needs 3
        assert pg.pages_free() == 1                  # rollback complete
        assert pg.tables[1] == []

    def test_ensure_append_tail_and_new_page(self):
        pg = KVPager(9, 4, slots=1, prefix_cache=False)
        pg.admit(0, np.arange(5))                    # 2 pages, tail has 1
        pid, off, cow = pg.ensure_append(0, 5)       # into the tail page
        assert pid == pg.tables[0][1] and off == 1 and cow is None
        # idempotent
        assert pg.ensure_append(0, 5) == (pid, off, None)
        pid2, off2, _ = pg.ensure_append(0, 8)       # page boundary
        assert off2 == 0 and pid2 == pg.tables[0][2]

    def test_cow_on_shared_tail(self):
        pg = KVPager(17, 4, slots=2)
        prompt = np.arange(1, 7)                     # 6 tokens: 1 full + tail
        pg.admit(0, prompt)
        pg.admit(1, prompt)                          # shares both pages
        old_tail = pg.tables[0][1]
        pid, off, cow = pg.ensure_append(0, 6)       # diverging write
        assert cow == old_tail and pid != old_tail and off == 2
        assert pg.tables[1][1] == old_tail           # peer untouched
        assert pg.cow_copies == 1
        # the registered tail stays FROZEN at prompt-only content: the
        # peer's first append COWs too, retiring the pristine page to
        # the reclaim list for future identical prompts
        pid1, _, cow1 = pg.ensure_append(1, 6)
        assert cow1 == old_tail and pid1 not in (old_tail, pid)
        assert pg.cow_copies == 2
        assert old_tail in pg._reclaim               # pristine, reusable
        pg.release(0)
        pg.release(1)
        _, hits = pg.admit(0, prompt)
        assert hits == 2                             # full page + pristine tail

    def test_frozen_tail_never_shares_live_decode_state(self):
        """Regression (review finding): request A decodes into its tail
        page, request B then admits the same prompt — B must NOT share
        the page A is writing generated K/V into."""
        pg = KVPager(17, 4, slots=2)
        prompt = np.arange(1, 7)                     # 1 full + 2-token tail
        pg.admit(0, prompt)
        a_tail, _, cow = pg.ensure_append(0, 6)      # A's first append
        assert cow is not None                       # moved off the frozen page
        t1, hits = pg.admit(1, prompt)
        assert hits == 2                             # full + pristine tail
        assert t1[1] != a_tail                       # never A's live page

    def test_deferred_registration(self):
        pg = KVPager(17, 4, slots=2)
        prompt = np.arange(1, 11)                    # 3 pages
        pg.admit(0, prompt, defer_register=True)
        # nothing registered yet: an identical admit allocates fresh
        _, h = pg.admit(1, prompt)
        assert h == 0
        pg.release(1)
        pg.register_prompt(0, 8)                     # two full pages in
        pg.register_prompt(0, 10)                    # tail in
        pg.release(0)
        _, h2 = pg.admit(1, prompt)
        assert h2 == 3


# --------------------------------------------------------------------------
# chain digests + pinned admission (ISSUE 17 pager half, no jax)
# --------------------------------------------------------------------------

class TestChainDigestsAndPinnedAdmit:
    def test_chain_keys_dtype_invariant(self):
        """The router hashes Python-int lists, the engine int32 arrays —
        both must land on the SAME chain digests."""
        from paddle_tpu.inference.kv_pager import prompt_chain_keys
        toks = [5, 9, 200, 3, 17, 44, 250, 1, 7, 12]
        a = prompt_chain_keys(toks, 4, "salt")
        b = prompt_chain_keys(np.asarray(toks, np.int32), 4, "salt")
        c = prompt_chain_keys(np.asarray(toks, np.int64), 4, "salt")
        assert a == b == c

    def test_chain_keys_structure_and_salt(self):
        from paddle_tpu.inference.kv_pager import (
            SHORT_DIGEST_LEN, prompt_chain_keys, short_digest)
        keys = prompt_chain_keys(np.arange(1, 11), 4, "s1")
        assert [k[0] for k in keys] == ["full", "full", "part"]
        assert keys[2][2] == (9, 10)                 # tail rides its tokens
        digs = [short_digest(k) for k in keys]
        assert digs[2] is None                       # part pages: no digest
        assert all(len(d) == SHORT_DIGEST_LEN for d in digs[:2])
        # the chain is position-dependent: same page tokens, different
        # predecessor -> different digest
        keys2 = prompt_chain_keys(np.r_[np.arange(5, 9), np.arange(5, 11)],
                                  4, "s1")
        assert short_digest(keys2[1]) != digs[1]
        # and salted: quant/kv-dtype splits the digest space
        assert [short_digest(k) for k in
                prompt_chain_keys(np.arange(1, 11), 4, "s2")][:2] != digs[:2]

    def test_head_digest_is_first_chain_digest(self):
        from paddle_tpu.inference.kv_pager import (
            prompt_chain_keys, prompt_head_digest, short_digest)
        prompt = np.arange(40, 54)
        head = prompt_head_digest(prompt, 4, "k")
        assert head == short_digest(prompt_chain_keys(prompt, 4, "k")[0])
        assert prompt_head_digest([1, 2, 3], 4, "k") is None

    def test_admit_pinned_flags_and_counters(self):
        pg = KVPager(17, 4, slots=2)
        prompt = np.arange(1, 11)                    # 2 full + tail
        pg.admit(0, prompt)
        pg.release(0)                                # retained in cache
        t, flags = pg.admit_pinned(1, prompt)
        assert flags == [True, True, True]           # exact repeat: the
        assert pg.prefix_hits == 3                   # tail key (tokens
        assert len(t) == 3                           # inline) hits too

    def test_admit_pinned_hits_survive_own_allocations(self):
        """Two-pass law: the second pass's fresh allocations must not
        reclaim the first pass's cache hits out from under the
        admission."""
        pg = KVPager(5, 4, slots=2)                  # 4 usable pages
        pg.admit(0, np.arange(1, 9))                 # 2 full pages
        pg.release(0)                                # both reclaimable
        # same 2-page prefix + 8 new tokens: 2 hits + 2 fresh = all 4
        t, flags = pg.admit_pinned(1, np.r_[np.arange(1, 9),
                                            np.arange(50, 58)])
        assert flags == [True, True, False, False]
        assert pg.evictions == 0                     # hits were pinned
        assert len(set(t)) == 4

    def test_admit_pinned_rolls_back_pins(self):
        pg = KVPager(4, 4, slots=2)                  # 3 usable
        pg.admit(0, np.arange(1, 9))                 # 2 pages
        pg.release(0)
        free0 = pg.pages_free()
        with pytest.raises(PagesExhausted):
            # 2 hits + needs 2 fresh, only 1 left
            pg.admit_pinned(1, np.arange(1, 17))
        assert pg.pages_free() == free0              # pins decref'd
        assert pg.tables[1] == []
        # the hit pages are reclaimable again, not leaked as pinned
        t, h = pg.admit(1, np.arange(1, 9))
        assert h == 2

    def test_evict_hook_fires_with_key_then_uncached(self):
        pg = KVPager(5, 4, slots=2)
        spilled = []
        pg.evict_hook = lambda pid, key: spilled.append((pid, key))
        pg.admit(0, np.arange(1, 9))
        keys = pg._prompt_keys(np.arange(1, 9))
        pg.release(0)
        pg.admit(1, np.arange(100, 116))             # needs all 4 pages
        assert [k for _, k in spilled] == keys[:2]   # LRU order, full keys
        for _, k in spilled:
            assert pg.cached_page(k) is None         # gone from the cache

    def test_reclaim_lru_respects_refcount_sharing(self):
        """A retained chain re-acquired by a live slot is pinned OUT of
        the reclaim LRU: eviction must take the oldest UNREFERENCED
        chain instead."""
        pg = KVPager(7, 4, slots=3)                  # 6 usable
        a = np.arange(1, 9)                          # 2 pages (oldest)
        b = np.arange(30, 38)                        # 2 pages
        pg.admit(0, a)
        pg.release(0)
        pg.admit(0, b)
        pg.release(0)
        t_a, h_a = pg.admit(1, a)                    # re-pin A (ref >= 1)
        assert h_a == 2
        pg.admit(2, np.arange(60, 70))               # 3 pages: must evict
        ka = pg._prompt_keys(a)
        kb = pg._prompt_keys(b)
        assert pg.cached_page(ka[0]) == t_a[0]       # A pinned, survives
        assert pg.cached_page(kb[0]) is None         # B (LRU) evicted
        assert pg.chain_digests() \
            and all(len(d) == 12 for d in pg.chain_digests())


# --------------------------------------------------------------------------
# paged engine (lax fallback, CPU)
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    import jax
    from paddle_tpu.models import gpt as G
    cfg = G.GPTConfig(vocab_size=256, hidden_size=32, num_layers=2,
                      num_heads=2, max_seq_len=64, dtype="float32",
                      use_flash=False, remat=False)
    params = G.init_params(cfg, jax.random.PRNGKey(0))
    return params, cfg


def _generate_ref(tiny_model, prompt, n):
    import jax.numpy as jnp
    from paddle_tpu.models import gpt as G
    params, cfg = tiny_model
    out = G.generate(params, cfg, jnp.asarray(prompt)[None], n)
    return np.asarray(out)[0, len(prompt):]


def _make_engine(tiny_model, **kw):
    from paddle_tpu.inference.serving import PagedServingEngine
    kw.setdefault("slots", 3)
    kw.setdefault("max_len", 32)
    kw.setdefault("page_size", 8)
    kw.setdefault("seq_buckets", (8, 16))
    kw.setdefault("batch_buckets", (1, 2))
    return PagedServingEngine(tiny_model, **kw)


@pytest.mark.parametrize("family", ["gpt", "deepseek_v3"])
def test_only_init_paged_pools_is_told_the_pool_format(family):
    """A family is TOLD which pool to make once; its programs read what
    they were handed off the pools themselves."""
    import importlib
    import inspect
    from paddle_tpu.inference import serving
    mod = importlib.import_module(f"paddle_tpu.models.{family}")
    told = [name for name in serving.FAMILY_INTERFACE if "kv_quant"
            in inspect.signature(getattr(mod, name)).parameters]
    assert told == ["init_paged_pools"]


@pytest.mark.parametrize("kv_dtype,dtypes", [
    (None, ["float32"] * 2), ("int8", ["int8", "float32"] * 2)])
def test_engine_holds_the_pool_as_the_family_returned_it(
        tiny_model, monkeypatch, kv_dtype, dtypes):
    from paddle_tpu.models import gpt as G
    made = []

    def spy(*args, real=G.init_paged_pools, **kw):
        made.append(real(*args, **kw))
        return made[-1]

    monkeypatch.setattr(G, "init_paged_pools", spy)
    eng = _make_engine(tiny_model, kv_dtype=kv_dtype)
    ops = eng._cache_operands()
    assert isinstance(ops, tuple) and eng._n_cache == len(dtypes)
    assert len(ops) == len(made[-1]) and all(
        a is b for a, b in zip(ops, made[-1]))
    # the family's order: K's arrays, then V's (pages, then their scales)
    assert [str(a.dtype) for a in ops] == dtypes
    eng.submit(np.arange(1, 12, dtype=np.int32), 3)
    eng.run()
    assert [(a.shape, a.dtype) for a in eng._cache_operands()] == [
        (a.shape, a.dtype) for a in made[-1]]


class TestPagedEngine:
    def test_parity_across_churned_slots(self, tiny_model):
        eng = _make_engine(tiny_model, capture_logits=True)
        assert eng.warmup() >= 1
        rng = np.random.RandomState(3)
        reqs = [eng.submit(
            rng.randint(1, 256, rng.randint(3, 15)).astype(np.int32),
            int(rng.randint(3, 8))) for _ in range(10)]
        done = eng.run()
        st = eng.stats()
        assert len(done) == 10
        assert st["decode_compiles"] == 1
        assert st["prefill_compiles"] <= 2 * 2     # the (batch, seq) ladder
        assert st["slot_occupancy_peak"] >= 2      # churn really batched
        for r in reqs:
            want = _generate_ref(tiny_model, r.prompt, r.max_new_tokens)
            assert (np.asarray(r.tokens) == want).all(), r.id
        # pool fully drained: nothing leaks
        assert st["pages_in_use"] == 0
        assert st["kv_tokens_held"] == 0

    def test_stats_report_the_decode_kernels_grid(self, tiny_model):
        """``paged_attn_group_pages`` is the rule's G for the engine's
        pool (float32, page 8, table width 4 -> 4 pages a step) and
        ``paged_attn_live_step_share`` the share of slots x width / G
        grid steps whose first row an ACTIVE slot's length reaches."""
        for ps, want in ((8, 4), (2, 1)):    # 2 rows: not a whole tile
            eng = _make_engine(tiny_model, page_size=ps)
            st = eng.stats()
            assert st["paged_attn_group_pages"] == want
            assert st["paged_attn_live_step_share"] == 0.0
        eng = _make_engine(tiny_model, page_size=2, slots=4)
        eng._active[:] = [True, True, False, True]
        eng._lens[:] = [0, 5, 31, 31]        # live steps 1, 3, (16), 16
        assert eng.stats()["paged_attn_live_step_share"] == round(
            (1 + 3 + 16) / (4 * 16), 4)

    def test_prefix_reuse_attestation(self, tiny_model):
        """The ISSUE's attestation: a second request with the same
        system prompt allocates ZERO new prefix pages."""
        eng = _make_engine(tiny_model, page_size=4)
        eng.warmup()
        sys_prompt = np.arange(1, 11, dtype=np.int32)   # 10 tokens, 3 pages
        r1 = eng.submit(sys_prompt, 4)
        eng.run()
        s1 = eng.stats()
        r2 = eng.submit(sys_prompt, 4)
        eng.run()
        s2 = eng.stats()
        assert s2["prefix_page_hits"] - s1["prefix_page_hits"] == 3
        assert s2["prefix_page_misses"] - s1["prefix_page_misses"] == 0
        assert r1.tokens == r2.tokens

    def test_concurrent_shared_prefix_cow(self, tiny_model):
        """Two in-flight requests on one physical prefix: the first
        diverging write triggers copy-on-write, and both stay
        token-exact with the reference."""
        eng = _make_engine(tiny_model, page_size=4)
        eng.warmup()
        prompt = np.arange(20, 30, dtype=np.int32)
        ra = eng.submit(prompt, 6)
        rb = eng.submit(prompt, 6)
        eng.run()
        st = eng.stats()
        assert st["cow_copies"] >= 1
        want = _generate_ref(tiny_model, prompt, 6)
        assert (np.asarray(ra.tokens) == want).all()
        assert (np.asarray(rb.tokens) == want).all()

    def test_chunked_prefill_interleaves_decode(self, tiny_model):
        """While a long prompt trickles in chunk by chunk, in-flight
        decodes must advance between every pair of chunks."""
        eng = _make_engine(tiny_model, prefill_chunk=8, capture_logits=True)
        eng.warmup()
        # occupy a slot with a decoding request first
        short = eng.submit(np.arange(1, 6, dtype=np.int32), 12)
        eng.step()
        long_prompt = np.arange(40, 62, dtype=np.int32)     # 22 tokens: 3 chunks
        long_req = eng.submit(long_prompt, 4)
        trace = []
        while not (short.done and long_req.done):
            eng.step()
            st = eng.stats()
            trace.append((st["prefill_chunks"], st["decode_steps"]))
        chunks = [c for c, _ in trace]
        assert max(chunks) == 3
        # between consecutive chunk advances the decode counter moved
        for (c0, d0), (c1, d1) in zip(trace, trace[1:]):
            if c1 > c0 and c0 > 0:
                assert d1 > d0, trace
        want = _generate_ref(tiny_model, long_prompt, 4)
        assert (np.asarray(long_req.tokens) == want).all()
        want_s = _generate_ref(tiny_model, short.prompt, 12)
        assert (np.asarray(short.tokens) == want_s).all()

    def test_one_token_tail_page(self, tiny_model):
        """A prompt of len ≡ 1 (mod page_size) pins a 1-token tail page;
        decode appends into it and parity holds."""
        eng = _make_engine(tiny_model, page_size=8)
        eng.warmup()
        prompt = np.arange(1, 10, dtype=np.int32)        # 9 = 8 + 1
        r = eng.submit(prompt, 5)
        eng.run()
        want = _generate_ref(tiny_model, prompt, 5)
        assert (np.asarray(r.tokens) == want).all()
        assert eng.stats()["pages_in_use"] == 0

    def test_eos_releases_pages(self, tiny_model):
        eng = _make_engine(tiny_model)
        eng.warmup()
        free0 = eng.stats()["pages_free"]
        want = _generate_ref(tiny_model, np.arange(1, 7), 8)
        eos = int(want[2])                               # stop at token 3
        r = eng.submit(np.arange(1, 7, dtype=np.int32), 8, eos_token=eos)
        eng.run()
        assert r.done and r.finish_reason == "eos"
        first = int(np.nonzero(want == eos)[0][0])       # eos may repeat
        assert len(r.tokens) == first + 1
        assert (np.asarray(r.tokens) == want[:first + 1]).all()
        st = eng.stats()
        assert st["pages_in_use"] == 0
        assert st["pages_free"] == free0                 # ref-counts clean

    def test_max_new_one_finishes_in_admission(self, tiny_model):
        eng = _make_engine(tiny_model)
        eng.warmup()
        r = eng.submit(np.arange(1, 6, dtype=np.int32), 1)
        eng.run()
        assert r.done and len(r.tokens) == 1
        assert (np.asarray(r.tokens)
                == _generate_ref(tiny_model, r.prompt, 1)).all()
        assert eng.stats()["pages_in_use"] == 0

    def test_warmup_covers_rungs_past_prefill_chunk(self, tiny_model):
        """Regression (review finding): a bucket rung larger than
        prefill_chunk is still reachable by SHORT prompts that bucket
        up into it — warmup must compile it via a chunk-capped prompt
        instead of diverting to the chunked path and leaving it cold."""
        from paddle_tpu.observability import metrics
        eng = _make_engine(tiny_model, seq_buckets=(8, 32),
                           prefill_chunk=16)
        eng.warmup()
        before = metrics.counter("compile.count").value
        # 12 tokens: > bucket 8, <= chunk 16 -> wave path, rung 32
        r = eng.submit(np.arange(1, 13, dtype=np.int32), 3)
        eng.run()
        assert r.done
        assert metrics.counter("compile.count").value == before, \
            "rung past prefill_chunk was cold after warmup"

    def test_oversize_request_named_rejection(self, tiny_model):
        eng = _make_engine(tiny_model, num_pages=4)      # 3 usable pages
        with pytest.raises(ValueError, match="KV pages"):
            eng.submit(np.arange(1, 16, dtype=np.int32), 16)


class TestPageExhaustion:
    def test_real_exhaustion_preempts_newest(self, tiny_model):
        """Pool exhaustion preempts the NEWEST request back to the
        queue: pages freed, request re-admitted later, both complete
        token-exact — no deadlock, failure named in the counters."""
        eng = _make_engine(tiny_model, slots=2, page_size=4,
                          num_pages=9,                   # 32 positions
                          seq_buckets=(16,), batch_buckets=(1,),
                          prefix_cache=False)
        eng.warmup()
        a = eng.submit(np.arange(1, 13, dtype=np.int32), 16)
        b = eng.submit(np.arange(3, 15, dtype=np.int32), 16)
        done = eng.run(max_steps=400)                    # bounded: no hang
        st = eng.stats()
        assert len(done) == 2 and a.done and b.done
        assert st["preemptions"] >= 1
        assert a.preemptions + b.preemptions >= 1        # named on the req
        for r in (a, b):
            want = _generate_ref(tiny_model, r.prompt, r.max_new_tokens)
            assert (np.asarray(r.tokens) == want).all(), r.id
        assert st["pages_in_use"] == 0

    def test_injected_page_exhaustion_fault(self, tiny_model):
        from paddle_tpu.testing import faults
        faults.clear()
        faults.install("page_exhaustion:step=2")
        try:
            eng = _make_engine(tiny_model, slots=2, seq_buckets=(16,))
            eng.warmup()
            c = eng.submit(np.arange(1, 6, dtype=np.int32), 6)
            d = eng.submit(np.arange(2, 7, dtype=np.int32), 6)
            done = eng.run(max_steps=200)
            st = eng.stats()
            assert st["preemptions"] == 1
            assert len(done) == 2 and c.done and d.done
            assert c.preemptions + d.preemptions == 1
            for r in (c, d):
                want = _generate_ref(tiny_model, r.prompt, r.max_new_tokens)
                assert (np.asarray(r.tokens) == want).all(), r.id
        finally:
            faults.clear()

    def test_engine_error_aborts_and_rebuilds_paged_pool(self, tiny_model):
        """The PR-6 slot-leak fix must hold on the paged path: a mid-step
        failure frees slots AND pages, victims are re-queueable, and the
        rebuilt pool serves the retries token-exact."""
        from paddle_tpu.testing import faults
        faults.clear()
        faults.install("engine_error:step=2")
        try:
            eng = _make_engine(tiny_model, slots=2)
            eng.warmup()
            a = eng.submit(np.arange(1, 8, dtype=np.int32), 5)
            b = eng.submit(np.arange(2, 9, dtype=np.int32), 5)
            with pytest.raises(faults.InjectedFault):
                eng.run()
            victims = eng.take_aborted()
            assert {v.id for v in victims} <= {a.id, b.id}
            assert victims
            st = eng.stats()
            assert st["pages_in_use"] == 0               # pager rebuilt
            assert st["slot_occupancy"] == 0
            for v in victims:
                eng.submit(v.reset_for_retry())
            eng.run()
            for r in (a, b):
                want = _generate_ref(tiny_model, r.prompt, r.max_new_tokens)
                assert (np.asarray(r.tokens) == want).all(), r.id
        finally:
            faults.clear()


# --------------------------------------------------------------------------
# router satellite: page-aware least-loaded capacity
# --------------------------------------------------------------------------

class TestFleetPageRouting:
    def _fleet_stub(self):
        from paddle_tpu.inference.fleet import ServingFleet
        fleet = ServingFleet.__new__(ServingFleet)
        fleet._slots = 4
        fleet.dispatch_queue_depth = 4
        return fleet

    class _R:
        def __init__(self, stats, inflight=0):
            self.last_stats = stats
            self.inflight = dict.fromkeys(range(inflight))

    def test_slot_fallback_for_non_paged(self):
        fleet = self._fleet_stub()
        r = self._R({"slots": 4}, inflight=2)
        assert fleet._capacity(r) == 6                   # 4 + 4 - 2

    def test_free_pages_cap_routing(self):
        """A replica whose slots look free but whose page pool is pinned
        (fragmented-but-counted-free slots) must NOT win routing."""
        fleet = self._fleet_stub()
        starved = self._R({"slots": 4, "pages_free": 3,
                           "pages_per_request_est": 3}, inflight=0)
        roomy = self._R({"slots": 4, "pages_free": 24,
                         "pages_per_request_est": 3}, inflight=0)
        assert fleet._capacity(starved) == 1             # 3 // 3
        assert fleet._capacity(roomy) == 8               # slot bound wins
        # admitted in-flight work already holds its pages (pages_free
        # excludes them) — only not-yet-admitted in-flight claims from
        # the free set
        admitted = self._R({"slots": 4, "pages_free": 9, "slot_occupancy": 2,
                            "pages_per_request_est": 3}, inflight=2)
        assert fleet._capacity(admitted) == 3            # min(6, 9//3 - 0)
        queued = self._R({"slots": 4, "pages_free": 9, "slot_occupancy": 0,
                          "pages_per_request_est": 3}, inflight=2)
        assert fleet._capacity(queued) == 1              # 9//3 - 2

    def test_zero_free_pages_zero_capacity(self):
        fleet = self._fleet_stub()
        r = self._R({"slots": 4, "pages_free": 0,
                     "pages_per_request_est": 2})
        assert fleet._capacity(r) == 0


# --------------------------------------------------------------------------
# host-RAM page tier: spill on evict, hash-verified fault-back (ISSUE 17)
# --------------------------------------------------------------------------

class TestHostTierSpillFaultBack:
    """Evicted device pages spill to the pinned-host LRU tier; an exact
    repeat routed back faults them in through the donated inject
    executable — token-exact, hash-verified, ZERO re-prefill."""

    @pytest.fixture(autouse=True, scope="class")
    def _aot_cache(self, tmp_path_factory):
        # repeat engine builds of the same config deserialize their
        # executables instead of re-compiling (~0s vs ~4s each)
        d = str(tmp_path_factory.mktemp("aot"))
        old = os.environ.get("PADDLE_AOT_CACHE_DIR")
        os.environ["PADDLE_AOT_CACHE_DIR"] = d
        yield
        if old is None:
            os.environ.pop("PADDLE_AOT_CACHE_DIR", None)
        else:
            os.environ["PADDLE_AOT_CACHE_DIR"] = old

    def _tier_engine(self, tiny_model, **kw):
        kw.setdefault("page_size", 4)
        kw.setdefault("num_pages", 10)               # 9 usable: tight
        kw.setdefault("max_len", 32)
        kw.setdefault("host_tier_mb", 4)
        return _make_engine(tiny_model, **kw)

    def _spill_then_repeat(self, tiny_model, **kw):
        eng = self._tier_engine(tiny_model, **kw)
        eng.warmup()
        prompt = np.arange(1, 11, dtype=np.int32)    # 3 pages
        r1 = eng.submit(prompt, 6)
        eng.run()
        # churn: unique chains force the retained pages off-device —
        # one at a time, so nothing preempts (a preempted request's
        # re-admission is itself a legitimate fault-back and would
        # blur the exact counts below)
        rng = np.random.RandomState(7)
        for _ in range(4):
            eng.submit(rng.randint(1, 256, 10).astype(np.int32), 4)
            eng.run()
        st0 = eng.stats()
        assert st0["pages_spilled"] >= 1
        assert st0["host_tier_entries"] >= 1
        r2 = eng.submit(prompt, 6)
        eng.run()
        st1 = eng.stats()
        return eng, r1, r2, st0, st1

    def test_fault_back_token_exact_no_prefill_fp32(self, tiny_model):
        eng, r1, r2, st0, st1 = self._spill_then_repeat(tiny_model)
        assert st1["fault_backs"] == 1
        assert st1["pages_faulted_back"] >= 1
        assert st1["fault_back_rejects"] == 0
        # THE attestation: the repeat never touched the prefill path
        assert st1["prefill_calls"] == st0["prefill_calls"]
        # <= 1: with warm AOT artifacts the decode step deserializes
        # instead of compiling at all
        assert st1["decode_compiles"] <= 1
        want = _generate_ref(tiny_model, r2.prompt, 6)
        assert (np.asarray(r2.tokens) == want).all()
        assert r1.tokens == r2.tokens

    def test_fault_back_token_exact_no_prefill_int8(self, tiny_model):
        """Same laws on the int8+scale pool: BOTH per-pool operands
        (codes and scales) round-trip the host tier byte-exactly."""
        eng, r1, r2, st0, st1 = self._spill_then_repeat(
            tiny_model, quant="int8", kv_dtype="int8")
        assert st1["fault_backs"] == 1
        assert st1["fault_back_rejects"] == 0
        assert st1["prefill_calls"] == st0["prefill_calls"]
        assert r1.tokens == r2.tokens                # bit-exact repeat

    def test_cow_on_faulted_back_page(self, tiny_model):
        """A faulted-back chain re-enters the prefix cache shared; a
        second live request on the same prompt must copy-on-write the
        tail, not scribble on the shared page."""
        eng = self._tier_engine(tiny_model, num_pages=12)
        eng.warmup()
        prompt = np.arange(20, 30, dtype=np.int32)
        eng.submit(prompt, 4)
        eng.run()
        rng = np.random.RandomState(11)
        for _ in range(4):
            eng.submit(rng.randint(1, 256, 10).astype(np.int32), 4)
        eng.run()
        assert eng.stats()["pages_spilled"] >= 1
        cow0 = eng.stats()["cow_copies"]
        ra = eng.submit(prompt, 6)                   # faults back
        rb = eng.submit(prompt, 6)                   # shares the chain
        eng.run()
        st = eng.stats()
        assert st["fault_backs"] >= 1
        assert st["cow_copies"] > cow0
        want = _generate_ref(tiny_model, prompt, 6)
        assert (np.asarray(ra.tokens) == want).all()
        assert (np.asarray(rb.tokens) == want).all()

    def test_host_tier_corrupt_rejected_never_served(self, tiny_model):
        """Injected bit-flip in a spilled entry: the content stamp must
        reject it (counted), the request re-prefills, and the answer
        stays token-exact — bad KV is never served."""
        from paddle_tpu.testing import faults
        faults.clear()
        faults.install("host_tier_corrupt:nth=1")
        try:
            eng, r1, r2, st0, st1 = self._spill_then_repeat(tiny_model)
            assert st1["fault_back_rejects"] >= 1
            assert st1["fault_backs"] == 0           # admission refused
            assert st1["prefill_calls"] > st0["prefill_calls"]
            want = _generate_ref(tiny_model, r2.prompt, 6)
            assert (np.asarray(r2.tokens) == want).all()
        finally:
            faults.clear()

    def test_spill_stall_does_not_block_decode(self, tiny_model):
        """A stalled host readback (injected sleep in the drain) may
        only delay the spill copy — the decode compute of the step that
        evicted must still advance its in-flight requests."""
        import time as _time

        from paddle_tpu.testing import faults
        eng = self._tier_engine(tiny_model, num_pages=10)
        eng.warmup()
        done_first = eng.submit(np.arange(1, 11, dtype=np.int32), 4)
        eng.run()                                    # chain retained
        bg = eng.submit(np.arange(100, 110, dtype=np.int32), 12)
        eng.step()                                   # bg decoding
        faults.clear()
        faults.install("spill_stall:nth=1,seconds=0.25")
        try:
            # this admission must evict the retained chain -> spill
            eng.submit(np.arange(200, 210, dtype=np.int32), 4)
            n0 = len(bg.tokens)
            t0 = _time.perf_counter()
            eng.step()
            dt = _time.perf_counter() - t0
            assert len(bg.tokens) > n0               # decode advanced
            assert dt >= 0.2                         # the stall really hit
            st = eng.stats()
            assert st["pages_spilled"] >= 1
            eng.run()
            want = _generate_ref(tiny_model, bg.prompt, 12)
            assert (np.asarray(bg.tokens) == want).all()
            assert done_first.done
        finally:
            faults.clear()


# --------------------------------------------------------------------------
# prefix-sticky routing laws (router side, FakeFleet — no processes)
# --------------------------------------------------------------------------

class TestPrefixStickyRouting:
    def _stub(self, migrate_hot_routes=3):
        import collections
        import threading

        from paddle_tpu.inference.fleet import ServingFleet, _stats_family
        fleet = ServingFleet.__new__(ServingFleet)
        fleet._slots = 4
        fleet.dispatch_queue_depth = 4
        fleet._lock = threading.RLock()
        fleet.prefix_sticky = True
        fleet._prefix_index = collections.OrderedDict()
        fleet._route_counts = collections.OrderedDict()
        fleet._stats = _stats_family()
        fleet._counts = {}
        fleet.migrate_enabled = True
        fleet.migrate_hot_routes = migrate_hot_routes
        fleet.migrate_window_s = 10.0
        fleet._replicas = []
        return fleet

    class _R:
        def __init__(self, rid, role="unified", state="healthy",
                     draining=False, stats=None, inflight=0):
            self.id = rid
            self.role = role
            self.state = state
            self.draining = draining
            self.last_stats = stats if stats is not None else {"slots": 4}
            self.inflight = dict.fromkeys(range(inflight))

    class _Req:
        def __init__(self, chain, phase=None):
            self.prefix_chain = tuple(chain)
            self.prefix_digest = chain[-1] if chain else None
            self.phase = phase
            self.migrate_from = None
            self.migrate_to = None
            self.kv_bytes = 0

    def test_deepest_digest_wins(self):
        """An exact repeat matches its deep digest's sole holder even
        when another replica owns the shared head page."""
        fleet = self._stub()
        r1, r2 = self._R(1), self._R(2)
        fleet._replicas = [r1, r2]
        fleet._prefix_index["head"] = 1
        fleet._prefix_index["deep"] = 2
        req = self._Req(("deep", "head"))             # deepest first
        assert fleet._sticky_defers_locked(req, r1, 0.0)   # held for r2
        assert not fleet._sticky_defers_locked(req, r2, 0.0)
        assert fleet._counts.get("prefix_routed") == 1
        # a fresh prompt sharing only the head page sticks to r1
        fresh = self._Req(("other", "head"))
        assert not fleet._sticky_defers_locked(fresh, r1, 0.0)
        assert fleet._counts.get("prefix_routed") == 2

    def test_unknown_chain_routes_least_loaded(self):
        fleet = self._stub()
        r1 = self._R(1)
        fleet._replicas = [r1]
        assert not fleet._sticky_defers_locked(
            self._Req(("nobody",)), r1, 0.0)
        assert not fleet._counts                      # no verdict counted

    def test_fallback_when_owner_unusable(self):
        """Dead, draining, cross-pool, or full owners never hold a
        request hostage: least-loaded wins, counted as a fallback."""
        fleet = self._stub()
        r1 = self._R(1)
        for owner in (self._R(2, state="dead"),
                      self._R(2, draining=True),
                      self._R(2, role="decode"),
                      self._R(2, stats={"slots": 4, "pages_free": 0,
                                        "pages_per_request_est": 2})):
            fleet._replicas = [r1, owner]
            fleet._prefix_index.clear()
            fleet._prefix_index["d"] = 2
            assert not fleet._sticky_defers_locked(
                self._Req(("d",)), r1, 0.0)
        assert fleet._counts["prefix_fallbacks"] == 4

    def test_first_writer_keeps_digest_while_healthy(self):
        fleet = self._stub()
        r1, r2 = self._R(1), self._R(2)
        fleet._replicas = [r1, r2]
        fleet._update_prefix_index(r1, {"chain_digests": ["d"]})
        fleet._update_prefix_index(r2, {"chain_digests": ["d"]})
        assert fleet._prefix_index["d"] == 1          # no flapping
        r1.state = "dead"
        fleet._update_prefix_index(r2, {"chain_digests": ["d"]})
        assert fleet._prefix_index["d"] == 2          # dead owner yields

    def test_prefix_index_bounded(self):
        fleet = self._stub()
        r1 = self._R(1)
        fleet._replicas = [r1]
        fleet._update_prefix_index(
            r1, {"chain_digests": [f"d{i}" for i in range(9000)]})
        assert len(fleet._prefix_index) == 8192
        assert "d0" not in fleet._prefix_index        # oldest evicted

    def test_hot_route_migration_triggers_and_repoints(self):
        """Past migrate_hot_routes sticky routes inside the window, the
        next dispatch becomes a migration: prefill pinned to the hot
        owner, decode pinned to the coldest replica, which now owns the
        digest."""
        fleet = self._stub(migrate_hot_routes=3)
        hot = self._R(1, inflight=3)
        cold = self._R(2)
        fleet._replicas = [hot, cold]
        fleet._prefix_index["d"] = 1
        reqs = [self._Req(("d",)) for _ in range(3)]
        for q in reqs:
            assert not fleet._sticky_defers_locked(q, hot, 1.0)
        assert reqs[0].migrate_to is None             # below threshold
        assert reqs[2].phase == "prefill"             # the hot one
        assert reqs[2].migrate_from == 1
        assert reqs[2].migrate_to == 2
        assert fleet._prefix_index["d"] == 2          # index repointed
        # the phased legs pin to their replicas
        assert fleet._phase_ok(reqs[2], hot)
        assert not fleet._phase_ok(reqs[2], cold)
        reqs[2].phase = "decode"
        assert fleet._phase_ok(reqs[2], cold)
        assert not fleet._phase_ok(reqs[2], hot)
        # a dead pin never strands the request
        cold.state = "dead"
        assert fleet._phase_ok(reqs[2], hot)

    def test_migration_needs_cold_capacity(self):
        fleet = self._stub(migrate_hot_routes=2)
        hot = self._R(1)
        full = self._R(2, stats={"slots": 4, "pages_free": 0,
                                 "pages_per_request_est": 2})
        fleet._replicas = [hot, full]
        fleet._prefix_index["d"] = 1
        reqs = [self._Req(("d",)) for _ in range(3)]
        for q in reqs:
            fleet._sticky_defers_locked(q, hot, 1.0)
        assert all(q.migrate_to is None for q in reqs)
        assert fleet._prefix_index["d"] == 1          # stays sticky


# --------------------------------------------------------------------------
# Pallas paged-attention kernel (interpret mode)
# --------------------------------------------------------------------------

def _kernel_case(rng, S, nh, hd, L, P, ps, maxP, dtype, quant=False,
                 lens=None):
    """Random pools as the engine stores them ([L, P, ps, nh * hd], and
    for int8 the [L, P, ps, nh] scale rows), queries, tables and lengths
    (``lens``, or random with one lane at length 0 and one ending
    mid-page)."""
    import jax.numpy as jnp
    C = nh * hd
    if quant:
        pools = [jnp.asarray(rng.randint(-127, 128, (L, P, ps, C))
                             .astype(np.int8)) for _ in range(2)]
        scales = [jnp.asarray((rng.rand(L, P, ps, nh).astype(np.float32)
                               + 0.05) / 64) for _ in range(2)]
    else:
        pools = [jnp.asarray(rng.randn(L, P, ps, C), dtype)
                 for _ in range(2)]
        scales = []
    q = jnp.asarray(rng.randn(S, 1, nh, hd), dtype)
    pt = jnp.asarray(rng.randint(0, P, (S, maxP)).astype(np.int32))
    if lens is None:
        lens = rng.randint(0, maxP * ps, (S,)).astype(np.int32)
        lens[0], lens[-1] = 0, maxP * ps - ps // 2 - 1
    return q, pools, scales, pt, jnp.asarray(lens, jnp.int32)


def _group_edges(nh, hd, ps, itemsize, groups):
    """(table width, lengths) that walk the grid of the kernel as the
    committed rule builds it: ``groups`` grid steps a slot (0: a width
    of 3, which no power of two divides — one page a step), and lanes
    at length 0, inside the first group, on a group's last row, on the
    next group's first row (the table's last row where there is one
    group) and in the last page."""
    from paddle_tpu.ops.pallas.paged_attn import group_pages
    G = group_pages(1 << 10, ps, nh * hd, itemsize, nh)
    maxP = groups * G if groups else 3
    G = group_pages(maxP, ps, nh * hd, itemsize, nh)
    assert maxP // G == (groups or 3)
    rows, view = G * ps, maxP * ps
    return maxP, [0, rows // 2 - 3, rows - 1, min(rows, view - 1),
                  view - ps // 2 - 1]


def _kernel_reference(q, pools, scales, pt, lens, layer):
    """The lax fallback on float32 copies of the same values: the
    kernel's contract is float32 products, softmax and sums over the
    stored (bf16 / int8 x scale) numbers."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attn
    f32 = jnp.float32
    k, v = (p[layer].astype(f32) for p in pools)
    if scales:
        return paged_attn._ref_paged_attention_quant(
            q.astype(f32), k, scales[0][layer], v, scales[1][layer],
            pt, lens)
    return paged_attn._ref_paged_attention(q.astype(f32), k, v, pt, lens)


def _kernel_run(q, pools, scales, pt, lens, layer, mesh=None):
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attn
    return paged_attn._over_heads(mesh, q, pools,
                                  [s[layer] for s in scales], pt, lens,
                                  jnp.int32(layer), interpret=True)


class TestPagedKernelOnTheStoredPool:
    """The kernel against the float32 fallback at the served head
    splits, reading layer 1 of a three-layer pool in place.

    Tolerance: q and the pool hold bf16 (or int8 x fp32 scale) values
    and both sides multiply and sum them in float32, so they differ by
    the order of the sums (1e-5) and by the kernel's ONE rounding, of
    its output to bf16: half an ulp, 2**-8 of the value.  A product
    rounded to bf16 on the way (a ``q . k`` of 64..128 terms at 2**-8
    each, through the softmax) would show as 1e-1."""

    # every pool at two groups a slot; the other table widths (3: no
    # power of two divides it, one page a step; one group; four) at one
    # bf16 and one int8 pool — interpret mode is slow at these widths
    @pytest.mark.parametrize("nh,hd,ps,quant,groups", [
        (nh, hd, ps, quant, 2)
        for nh, hd in ((32, 64), (16, 128)) for ps in (16, 32)
        for quant in (False, True)
    ] + [(nh, hd, ps, quant, groups)
         for nh, hd, ps, quant in ((32, 64, 16, False), (16, 128, 32, True))
         for groups in (0, 1, 4)])
    def test_matches_float32_reference(self, nh, hd, ps, quant, groups):
        import jax.numpy as jnp
        rng = np.random.RandomState(nh + ps + quant)
        maxP, lens = _group_edges(nh, hd, ps, 1 if quant else 2, groups)
        case = _kernel_case(rng, len(lens), nh, hd, 3, 7, ps, maxP,
                            jnp.bfloat16, quant, lens)
        ref = _kernel_reference(*case, layer=1)
        got = _kernel_run(*case, layer=1)
        assert got.dtype == jnp.bfloat16 and got.shape == ref.shape
        over = (jnp.abs(got.astype(jnp.float32) - ref)
                - (2.0 ** -8 * jnp.abs(ref) + 1e-5))
        assert float(over.max()) <= 0, float(over.max())

    @pytest.mark.parametrize("pool", ["bf16", "int8", "float32"])
    def test_dead_table_entries_are_never_read(self, pool):
        """Every table entry past a slot's last live page names a page
        of NaN (for int8, of NaN scales): the result is finite and is
        the reference's over a table whose dead entries name page 0."""
        import jax.numpy as jnp
        nh, hd, ps, P = 8, 64, 32 if pool == "int8" else 16, 6
        dtype = jnp.float32 if pool == "float32" else jnp.bfloat16
        maxP, lens = _group_edges(nh, hd, ps, {"bf16": 2, "int8": 1,
                                               "float32": 4}[pool], 2)
        rng = np.random.RandomState(11)
        q, pools, scales, pt, lens = _kernel_case(
            rng, len(lens), nh, hd, 2, P, ps, maxP, dtype, pool == "int8",
            lens)
        dead = np.arange(maxP)[None, :] > np.asarray(lens)[:, None] // ps
        clean = jnp.asarray(np.where(dead, 0, np.asarray(pt) % (P - 1)))
        poisoned = jnp.asarray(np.where(dead, P - 1, np.asarray(clean)))
        if scales:
            scales = [a.at[:, P - 1].set(jnp.nan) for a in scales]
        else:
            pools = [a.at[:, P - 1].set(jnp.nan) for a in pools]
        ref = _kernel_reference(q, pools, scales, clean, lens, layer=1)
        got = _kernel_run(q, pools, scales, poisoned, lens,
                          layer=1).astype(jnp.float32)
        assert bool(jnp.isfinite(got).all())
        tol = 1e-5 if pool == "float32" else 2.0 ** -8 * jnp.abs(ref) + 1e-5
        assert float((jnp.abs(got - ref) - tol).max()) <= 0

    @pytest.mark.parametrize("table_width,ps,width,itemsize,heads,want", [
        (128, 16, 2048, 2, 32, 16),    # the benchmark's cell: 256 rows
        (64, 32, 2048, 2, 32, 8),
        (64, 32, 2048, 1, 32, 8),      # the int8 engine
        (128, 16, 512, 2, 8, 16),      # one rank of tp=4
        (128, 16, 2048, 4, 32, 16),    # a float32 pool
        (3, 16, 2048, 2, 32, 1),       # no power of two divides 3
        (6, 16, 2048, 2, 32, 2),
        (128, 8, 2048, 2, 32, 1),      # 8 bf16 rows: half a packed tile
        (128, 16, 2048, 1, 32, 1),     # 16 int8 rows: half a packed tile
        (64, 64, 8192, 2, 32, 2),      # the VMEM bound binds, not rows
        (64, 64, 8192, 4, 32, 1),
    ])
    def test_group_rule(self, table_width, ps, width, itemsize, heads,
                        want):
        """Shapes in, G out: a power of two that divides the table's
        width, within the row target and the VMEM bound."""
        from paddle_tpu.ops.pallas import paged_attn
        G = paged_attn.group_pages(table_width, ps, width, itemsize, heads)
        assert G == want
        assert table_width % G == 0 and G & (G - 1) == 0
        assert G * ps <= max(ps, paged_attn.GROUP_ROWS)
        assert paged_attn._step_vmem_bytes(
            G, ps, width, itemsize, heads) <= paged_attn._MAX_STEP_VMEM_BYTES

    def test_two_tp_shards(self):
        """Under a 'tp' mesh each rank runs the kernel on its own
        contiguous half of the merged axis (its nh/2 heads) and of the
        scale rows; the halves reassemble the unsharded result."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.framework import jax_compat
        mesh = jax_compat.make_mesh(np.array(jax.devices()[:2]), ("tp",))
        rng = np.random.RandomState(5)
        for quant in (False, True):
            case = _kernel_case(rng, 2, 8, 64, 2, 5, 16, 2, jnp.bfloat16,
                                quant)
            whole = _kernel_run(*case, layer=1)
            split = _kernel_run(*case, layer=1, mesh=mesh)
            assert jnp.array_equal(whole, split)

    @pytest.mark.parametrize("S,nh,hd,P,ps,maxP", [
        (4, 4, 16, 12, 8, 4),
        (2, 2, 64, 6, 16, 2),
        (3, 4, 32, 16, 8, 6),
    ])
    def test_float32_pool(self, S, nh, hd, P, ps, maxP):
        """float32 pools (the CPU engines' dtype) take the HIGHEST
        matmul path: equal to the fallback to summation order."""
        import jax.numpy as jnp
        rng = np.random.RandomState(S + P)
        case = _kernel_case(rng, S, nh, hd, 2, P, ps, maxP, jnp.float32)
        ref = _kernel_reference(*case, layer=1)
        got = _kernel_run(*case, layer=1)
        assert float(jnp.abs(ref - got).max()) < 1e-5
