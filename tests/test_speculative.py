"""Speculative decoding (ISSUE 13): the n-gram matcher, the
longest-accepted-prefix commit math, draft/verify parity through churn
in both drafting modes (incl. kv_dtype="int8"), eos inside an accepted
window, the spec_reject all-reject page-byte regression, preemption
retry with speculation on, and the fleet spec-mode contract.

Everything runs on the lax paths (tier-1, CPU); the verify forward has
no Pallas kernel of its own — it deliberately reuses the decode's
reference math per lane so accepted positions are BITWISE what a
sequential decode writes.
"""
import numpy as np
import pytest

from paddle_tpu.inference.speculative import accept_commit, ngram_draft


# --------------------------------------------------------------------------
# n-gram / prompt-lookup matcher (pure host, no jax)
# --------------------------------------------------------------------------

class TestNgramDraft:
    def test_basic_continuation(self):
        h = [1, 2, 3, 9, 9, 1, 2, 3]
        assert list(ngram_draft(h, 2)) == [9, 9]

    def test_longest_ngram_preferred(self):
        # 2-gram (2, 3) matches at two places with different
        # continuations; the 3-gram (1, 2, 3) disambiguates
        h = [1, 2, 3, 7, 5, 2, 3, 8, 1, 2, 3]
        assert list(ngram_draft(h, 1, max_ngram=3)) == [7]
        # capped at 2-grams, the most RECENT (2, 3) wins
        assert list(ngram_draft(h, 1, max_ngram=2)) == [8]

    def test_continuation_padded_with_its_tail(self):
        h = [5, 6, 7, 5, 6]
        assert list(ngram_draft(h, 4)) == [7, 5, 6, 6]

    def test_no_match_falls_back_to_last_token(self):
        assert list(ngram_draft([1, 2, 3], 3, max_ngram=2)) == [3, 3, 3]

    def test_trailing_window_never_matches_itself(self):
        # the only occurrence of (1, 2) is the trailing one
        assert list(ngram_draft([9, 1, 2], 2)) == [2, 2]

    def test_single_token_history(self):
        assert list(ngram_draft([4], 2)) == [4, 4]

    def test_cycle_detection(self):
        h = [1, 2, 3, 4, 1, 2, 3, 4, 1, 2]
        assert list(ngram_draft(h, 4)) == [3, 4, 1, 2]


# --------------------------------------------------------------------------
# accept / commit math (traced function, tested via concrete arrays)
# --------------------------------------------------------------------------

class TestAcceptCommit:
    def _run(self, drafts, greedy, caps, eos=None, force=0):
        import jax.numpy as jnp
        S = len(greedy)
        eos_ids = np.full((S,), -1, np.int32) if eos is None \
            else np.asarray(eos, np.int32)
        out, n = accept_commit(jnp.asarray(drafts, jnp.int32),
                               jnp.asarray(greedy, jnp.int32),
                               jnp.asarray(caps, jnp.int32),
                               jnp.asarray(eos_ids),
                               jnp.int32(force))
        return np.asarray(out), np.asarray(n)

    def test_full_accept_commits_k_plus_one(self):
        out, n = self._run([[7, 8, 9]], [[7, 8, 9, 4]], [4])
        assert n[0] == 4 and list(out[0]) == [7, 8, 9, 4]

    def test_partial_accept_bonus_from_verify(self):
        # draft diverges at lane 2: commit the 2 accepted + the bonus
        out, n = self._run([[7, 8, 5]], [[7, 8, 9, 4]], [4])
        assert n[0] == 3 and list(out[0][:3]) == [7, 8, 9]

    def test_zero_accept_is_plain_decode(self):
        out, n = self._run([[5, 5, 5]], [[7, 8, 9, 4]], [4])
        assert n[0] == 1 and out[0][0] == 7

    def test_divergence_not_resurrected(self):
        # lane 1 wrong, lane 2 "right again" — the prefix rule still
        # stops at the first divergence
        _, n = self._run([[7, 5, 9]], [[7, 8, 9, 4]], [4])
        assert n[0] == 2

    def test_cap_truncates(self):
        _, n = self._run([[7, 8, 9]], [[7, 8, 9, 4]], [2])
        assert n[0] == 2

    def test_cap_zero_silences_inactive_row(self):
        _, n = self._run([[7, 8, 9]], [[7, 8, 9, 4]], [0])
        assert n[0] == 0

    def test_eos_truncates_inside_window(self):
        _, n = self._run([[7, 8, 9]], [[7, 8, 9, 4]], [4], eos=[8])
        assert n[0] == 2                     # 7, then eos 8 — stop

    def test_eos_beyond_commit_ignored(self):
        # eos appears at lane 2 but the draft diverged at lane 1
        _, n = self._run([[7, 5, 9]], [[7, 8, 9, 4]], [4], eos=[9])
        assert n[0] == 2

    def test_force_reject(self):
        out, n = self._run([[7, 8, 9]], [[7, 8, 9, 4]], [4], force=1)
        assert n[0] == 1 and out[0][0] == 7

    def test_per_row_independence(self):
        _, n = self._run([[7, 8], [1, 1]], [[7, 8, 3], [9, 9, 9]],
                         [3, 3])
        assert list(n) == [3, 1]


# --------------------------------------------------------------------------
# pager: multi-token window append
# --------------------------------------------------------------------------

class TestEnsureAppendWindow:
    def test_window_allocates_crossed_pages(self):
        from paddle_tpu.inference.kv_pager import KVPager
        pg = KVPager(9, 4, slots=1, prefix_cache=False)
        pg.admit(0, np.arange(5))                 # 2 pages, tail holds 1
        pids, offs, cows = pg.ensure_append_window(0, 5, 5)   # 5..9
        assert offs == [1, 2, 3, 0, 1]
        assert pids[0] == pids[1] == pids[2] == pg.tables[0][1]
        assert pids[3] == pids[4] == pg.tables[0][2]
        assert cows == []
        # idempotent re-walk (preemption retry path)
        assert pg.ensure_append_window(0, 5, 5) == (pids, offs, [])

    def test_window_cows_shared_tail_once(self):
        from paddle_tpu.inference.kv_pager import KVPager
        pg = KVPager(17, 4, slots=2)
        prompt = np.arange(1, 7)                  # 1 full + 2-token tail
        pg.admit(0, prompt)
        pg.admit(1, prompt)
        old_tail = pg.tables[0][1]
        pids, offs, cows = pg.ensure_append_window(0, 6, 4)   # 6..9
        assert cows == [(old_tail, pids[0])]
        assert pg.tables[1][1] == old_tail        # peer untouched

    def test_window_rolls_into_exhaustion(self):
        from paddle_tpu.inference.kv_pager import KVPager, PagesExhausted
        pg = KVPager(4, 4, slots=1, prefix_cache=False)   # 3 usable
        pg.admit(0, np.arange(10))                # all 3 pages
        with pytest.raises(PagesExhausted):
            pg.ensure_append_window(0, 10, 4)     # needs a 4th page


# --------------------------------------------------------------------------
# engine: parity, eos, churn, int8 (lax fallback, CPU)
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
    from paddle_tpu.inference.speculative import SpeculativeServingEngine
    kw.setdefault("spec_mode", "ngram")
    kw.setdefault("spec_k", 3)
    kw.setdefault("slots", 3)
    kw.setdefault("max_len", 32)
    kw.setdefault("page_size", 8)
    kw.setdefault("seq_buckets", (8, 16))
    kw.setdefault("batch_buckets", (1, 2))
    return SpeculativeServingEngine(tiny_model, **kw)


def _self_draft(tiny_model):
    """Draft cfg == target cfg + same seed: the draft IS the target, so
    every candidate is accepted — the acceptance machinery's unit
    anchor."""
    import dataclasses
    _, cfg = tiny_model
    return {"spec_mode": "draft",
            "spec_draft_cfg": dataclasses.asdict(cfg),
            "spec_draft_seed": 0}


class TestSpeculativeEngine:
    def test_ngram_parity_across_churned_slots(self, tiny_model):
        eng = _make_engine(tiny_model, capture_logits=True)
        assert eng.warmup() >= 1
        rng = np.random.RandomState(3)
        reqs = [eng.submit(
            rng.randint(1, 256, rng.randint(3, 15)).astype(np.int32),
            int(rng.randint(3, 8))) for _ in range(10)]
        done = eng.run(max_steps=500)
        st = eng.stats()
        assert len(done) == 10
        assert st["decode_compiles"] == 1
        assert st["spec_draft_compiles"] == 0    # ngram adds NO executables
        assert st["spec_steps"] > 0
        assert st["drafted_tokens"] == 3 * st["spec_steps"] \
            or st["drafted_tokens"] > 0          # k per active row-step
        for r in reqs:
            want = _generate_ref(tiny_model, r.prompt, r.max_new_tokens)
            assert (np.asarray(r.tokens) == want).all(), r.id
        assert st["pages_in_use"] == 0
        # captured logits: one [V] row per COMMITTED token
        for r in reqs:
            assert len(r.logits) == len(r.tokens)

    def test_self_draft_full_acceptance(self, tiny_model):
        """Draft == target: acceptance must be near-perfect, proving
        the draft cache catch-up and the verify agree step after step."""
        eng = _make_engine(tiny_model, **_self_draft(tiny_model))
        eng.warmup()
        rng = np.random.RandomState(5)
        reqs = [eng.submit(
            rng.randint(1, 256, rng.randint(3, 12)).astype(np.int32), 12)
            for _ in range(4)]
        eng.run(max_steps=300)
        st = eng.stats()
        assert st["accepted_tokens_per_step"] > 1.5, st
        assert st["spec_draft_compiles"] <= 2    # prefill + fused step
        for r in reqs:
            want = _generate_ref(tiny_model, r.prompt, r.max_new_tokens)
            assert (np.asarray(r.tokens) == want).all(), r.id

    def test_small_draft_parity_despite_rejections(self, tiny_model):
        """A weak (independently seeded half-size) draft must not cost
        correctness — only acceptance rate."""
        eng = _make_engine(tiny_model, spec_mode="draft",
                           spec_draft_seed=7)
        eng.warmup()
        rng = np.random.RandomState(9)
        reqs = [eng.submit(
            rng.randint(1, 256, rng.randint(3, 12)).astype(np.int32),
            int(rng.randint(4, 9))) for _ in range(5)]
        eng.run(max_steps=400)
        assert eng.stats()["rejected_tokens"] > 0   # the draft DID miss
        for r in reqs:
            want = _generate_ref(tiny_model, r.prompt, r.max_new_tokens)
            assert (np.asarray(r.tokens) == want).all(), r.id

    def test_eos_inside_accepted_window(self, tiny_model):
        eng = _make_engine(tiny_model, spec_k=4)
        eng.warmup()
        want = _generate_ref(tiny_model, np.arange(1, 7), 12)
        eos = int(want[5])
        r = eng.submit(np.arange(1, 7, dtype=np.int32), 12,
                       eos_token=eos)
        eng.run(max_steps=200)
        first = int(np.nonzero(want == eos)[0][0])
        assert r.done and r.finish_reason == "eos"
        assert len(r.tokens) == first + 1
        assert (np.asarray(r.tokens) == want[:first + 1]).all()
        assert eng.stats()["pages_in_use"] == 0

    def test_chunked_prefill_composes(self, tiny_model):
        eng = _make_engine(tiny_model, prefill_chunk=8)
        eng.warmup()
        short = eng.submit(np.arange(1, 6, dtype=np.int32), 10)
        long_req = eng.submit(np.arange(40, 62, dtype=np.int32), 4)
        eng.run(max_steps=300)
        assert eng.stats()["prefill_chunks"] >= 3
        for r in (short, long_req):
            want = _generate_ref(tiny_model, r.prompt, r.max_new_tokens)
            assert (np.asarray(r.tokens) == want).all(), r.id

    @pytest.mark.parametrize("mode_kw", ["ngram", "self_draft"])
    def test_int8_kv_parity(self, tiny_model, mode_kw):
        kw = (_self_draft(tiny_model) if mode_kw == "self_draft"
              else {"spec_mode": "ngram"})
        from paddle_tpu.inference.serving import PagedServingEngine
        base = PagedServingEngine(tiny_model, slots=2, max_len=32,
                                  page_size=8, seq_buckets=(8, 16),
                                  batch_buckets=(1,), quant="int8",
                                  kv_dtype="int8")
        eng = _make_engine(tiny_model, slots=2, quant="int8",
                           kv_dtype="int8", batch_buckets=(1,), **kw)
        base.warmup()
        eng.warmup()
        rng = np.random.RandomState(11)
        pairs = [(rng.randint(1, 256, rng.randint(3, 12)).astype(np.int32),
                  int(rng.randint(4, 9))) for _ in range(4)]
        b = [base.submit(p, m) for p, m in pairs]
        base.run()
        s = [eng.submit(p, m) for p, m in pairs]
        eng.run(max_steps=300)
        # token-exact vs the non-speculative INT8 engine (the int8
        # numeric contract's own greedy stream, not the fp32 one)
        for x, y in zip(b, s):
            assert x.tokens == y.tokens, y.id

    def test_zero_steady_state_compiles(self, tiny_model):
        from paddle_tpu.observability import metrics
        eng = _make_engine(tiny_model)
        eng.warmup()
        before = metrics.counter("compile.count").value
        rng = np.random.RandomState(13)
        for _ in range(6):
            eng.submit(rng.randint(1, 256,
                                   rng.randint(3, 15)).astype(np.int32),
                       int(rng.randint(3, 8)))
        eng.run(max_steps=400)
        assert metrics.counter("compile.count").value == before, \
            "speculative steady state retraced after warmup"
        assert eng.stats()["decode_compiles"] == 1

    def test_spec_mode_env_default_and_validation(self, tiny_model):
        with pytest.raises(ValueError, match="spec_mode"):
            _make_engine(tiny_model, spec_mode="turbo")
        with pytest.raises(ValueError, match="spec_k"):
            _make_engine(tiny_model, spec_k=0)
        eng = _make_engine(tiny_model)
        assert eng.stats()["spec_mode"] == "ngram"
        assert eng.stats()["spec_k"] == 3

    def test_draft_vocab_mismatch_rejected(self, tiny_model):
        with pytest.raises(ValueError, match="vocab"):
            _make_engine(tiny_model, spec_mode="draft",
                         spec_draft_cfg={"vocab_size": 128,
                                         "hidden_size": 32,
                                         "num_layers": 1, "num_heads": 2,
                                         "dtype": "float32"})


# --------------------------------------------------------------------------
# spec_reject fault: all-reject must leave page bytes untouched
# --------------------------------------------------------------------------

class TestSpecRejectByteParity:
    """The satellite regression: after a forced all-reject verify (and
    around it), the paged pool's bytes — int8 pages AND scales — are
    byte-identical to a never-speculated run.  Single request, no
    warmup (warmup's synthetic pages would differ between engines),
    scratch page 0 excluded (it holds redirected garbage by design and
    is never read)."""

    def _run_pair(self, tiny_model, fault, **ekw):
        from paddle_tpu.inference.serving import PagedServingEngine
        from paddle_tpu.testing import faults
        kw = dict(slots=2, max_len=32, page_size=8, seq_buckets=(8, 16),
                  batch_buckets=(1,), **ekw)
        prompt = np.arange(1, 12, dtype=np.int32)
        base = PagedServingEngine(tiny_model, **kw)
        rb = base.submit(prompt, 8)
        base.run()
        faults.clear()
        faults.install(fault)
        try:
            spec = _make_engine(tiny_model, **kw)
            rs = spec.submit(prompt, 8)
            spec.run(max_steps=200)
        finally:
            faults.clear()
        assert rb.tokens == rs.tokens
        return base, spec

    @staticmethod
    def _assert_pools_identical(base, spec, n_arrays, when):
        """Every pool array but its scratch page, byte for byte."""
        pools = base._cache_operands(), spec._cache_operands()
        assert len(pools[0]) == len(pools[1]) == n_arrays
        for i, (a, b) in enumerate(zip(*pools)):
            assert (np.asarray(a)[:, 1:] == np.asarray(b)[:, 1:]).all(), \
                f"pool array {i} diverged from the never-speculated " \
                f"run {when}"

    def test_fp_pool_bytes_identical(self, tiny_model):
        base, spec = self._run_pair(tiny_model, "spec_reject:step=2")
        self._assert_pools_identical(base, spec, 2,
                                     "after an all-reject verify")

    def test_int8_pool_and_scales_identical(self, tiny_model):
        # repeat=1 with no step filter: EVERY verify all-rejects — the
        # spec engine degrades to exactly a one-token decoder and the
        # int8 pool (bytes and once-per-position scales) must not be
        # able to tell
        base, spec = self._run_pair(tiny_model, "spec_reject:repeat=1",
                                    quant="int8", kv_dtype="int8")
        assert spec.stats()["accepted_tokens"] == 0
        self._assert_pools_identical(base, spec, 4,  # pages and scales
                                     "under forced all-reject")

    def test_accepting_run_pool_bytes_identical(self, tiny_model):
        """Stronger than the fault case: even a NORMALLY-accepting spec
        run commits bitwise the bytes the sequential decode writes (the
        per-lane verify attention's whole point)."""
        from paddle_tpu.inference.serving import PagedServingEngine
        kw = dict(slots=2, max_len=32, page_size=8, seq_buckets=(8, 16),
                  batch_buckets=(1,))
        prompt = np.arange(1, 12, dtype=np.int32)
        base = PagedServingEngine(tiny_model, **kw)
        rb = base.submit(prompt, 8)
        base.run()
        spec = _make_engine(tiny_model, **kw)
        rs = spec.submit(prompt, 8)
        spec.run(max_steps=200)
        assert rb.tokens == rs.tokens
        assert spec.stats()["accepted_tokens"] > 0
        self._assert_pools_identical(base, spec, 2,
                                     "in a normally accepting run")


# --------------------------------------------------------------------------
# preemption / retry with speculation on
# --------------------------------------------------------------------------

class TestSpecPreemption:
    def test_reset_for_retry_clears_pending_draft(self):
        from paddle_tpu.inference.serving import Request
        r = Request(np.arange(1, 5), 4)
        r.pending_draft = [7, 8]
        r.reset_for_retry()
        assert r.pending_draft is None

    @pytest.mark.parametrize("mode_kw", ["ngram", "self_draft"])
    def test_injected_preemption_replays_token_exact(self, tiny_model,
                                                     mode_kw):
        """The satellite fix: a preempted-then-retried request must
        replay token-exact with speculation on — stale per-row draft
        state (the pending-draft backlog, the draft cache fill) would
        otherwise double-feed the draft model after re-admission."""
        from paddle_tpu.testing import faults
        kw = (_self_draft(tiny_model) if mode_kw == "self_draft"
              else {"spec_mode": "ngram"})
        faults.clear()
        faults.install("page_exhaustion:step=2")
        try:
            eng = _make_engine(tiny_model, slots=2, seq_buckets=(16,),
                               batch_buckets=(1,), **kw)
            eng.warmup()
            a = eng.submit(np.arange(1, 6, dtype=np.int32), 6)
            b = eng.submit(np.arange(2, 7, dtype=np.int32), 6)
            done = eng.run(max_steps=300)
            st = eng.stats()
            assert len(done) == 2 and a.done and b.done
            assert st["preemptions"] == 1
            assert a.preemptions + b.preemptions == 1
            for r in (a, b):
                want = _generate_ref(tiny_model, r.prompt,
                                     r.max_new_tokens)
                assert (np.asarray(r.tokens) == want).all(), r.id
        finally:
            faults.clear()

    def test_engine_error_abort_and_retry(self, tiny_model):
        """The slot-leak fix composes with speculation: a mid-verify
        failure frees slots, pages AND draft state; retries are
        token-exact."""
        from paddle_tpu.testing import faults
        faults.clear()
        faults.install("engine_error:step=2")
        try:
            eng = _make_engine(tiny_model, slots=2, batch_buckets=(1,),
                               **_self_draft(tiny_model))
            eng.warmup()
            # long enough that a second verify step exists even when the
            # window commits spec_k+1 tokens per step
            a = eng.submit(np.arange(1, 8, dtype=np.int32), 12)
            b = eng.submit(np.arange(2, 9, dtype=np.int32), 12)
            with pytest.raises(faults.InjectedFault):
                eng.run(max_steps=300)
            victims = eng.take_aborted()
            assert victims
            assert eng.stats()["pages_in_use"] == 0
            for v in victims:
                eng.submit(v.reset_for_retry())
            eng.run(max_steps=300)
            for r in (a, b):
                want = _generate_ref(tiny_model, r.prompt,
                                     r.max_new_tokens)
                assert (np.asarray(r.tokens) == want).all(), r.id
        finally:
            faults.clear()


# --------------------------------------------------------------------------
# fleet satellites: spec-mode contract
# --------------------------------------------------------------------------

class TestFleetSpecContract:
    def _fleet_stub(self, spec):
        from paddle_tpu.inference.fleet import ServingFleet
        fleet = ServingFleet.__new__(ServingFleet)
        fleet.model_spec = spec
        fleet._slots = 4
        fleet.dispatch_queue_depth = 4
        return fleet

    def test_spec_mode_mismatch_refused(self):
        fleet = self._fleet_stub({"paged": True, "spec_mode": "ngram"})
        ok = {"quant": None, "kv_dtype": None, "spec_mode": "ngram"}
        assert fleet._contract_mismatch(ok) is None
        bad = fleet._contract_mismatch(
            {"quant": None, "kv_dtype": None, "spec_mode": None})
        # the attestation tuple grew tp + role in ISSUE 15, pp in 20
        assert bad == ((None, None, None, 1, 1, "unified"),
                       (None, None, "ngram", 1, 1, "unified"))
        # differing spec MODES refuse each other too
        assert fleet._contract_mismatch(
            {"quant": None, "kv_dtype": None,
             "spec_mode": "draft"}) is not None
        # and a non-spec fleet refuses a speculating replica
        plain = self._fleet_stub({"paged": True})
        assert plain._contract_mismatch(ok) is not None

    def test_model_spec_validation(self):
        from paddle_tpu.inference.fleet import ServingFleet
        with pytest.raises(ValueError, match="spec_mode"):
            ServingFleet({"paged": True, "spec_mode": "turbo"},
                         replicas=1)
        with pytest.raises(ValueError, match="paged"):
            ServingFleet({"spec_mode": "ngram"}, replicas=1)
        # bad spec knobs fail at CONSTRUCTION, not as N replicas
        # crash-looping through their restart budget before any hello
        with pytest.raises(ValueError, match="spec_k"):
            ServingFleet({"paged": True, "spec_mode": "ngram",
                          "spec_k": 0}, replicas=1)
        with pytest.raises(ValueError, match="spec_draft_cfg"):
            ServingFleet({"paged": True, "spec_mode": "draft",
                          "spec_draft_cfg": "tiny"}, replicas=1)

    def test_worker_spec_builds_spec_engine(self, tiny_model):
        from paddle_tpu.inference.fleet_worker import _build_engine
        from paddle_tpu.inference.speculative import (
            SpeculativeServingEngine)
        eng = _build_engine({"cfg": {
            "vocab_size": 256, "hidden_size": 32, "num_layers": 2,
            "num_heads": 2, "max_seq_len": 64, "dtype": "float32",
            "use_flash": False, "remat": False},
            "paged": True, "slots": 2, "max_len": 32, "page_size": 8,
            "seq_buckets": [8, 16], "batch_buckets": [1],
            "spec_mode": "ngram", "spec_k": 2})
        assert isinstance(eng, SpeculativeServingEngine)
        st = eng.stats()
        assert st["spec_mode"] == "ngram" and st["spec_k"] == 2

    def test_worker_spec_requires_paged(self):
        from paddle_tpu.inference.fleet_worker import _build_engine
        with pytest.raises(ValueError, match="paged"):
            _build_engine({"spec_mode": "ngram"})
