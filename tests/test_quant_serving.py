"""Quantized serving path (ISSUE 9): int8 weight-only executables, the
int8 paged KV pool with per-position-per-head scales, prefix reuse /
COW / preemption on quantized pages, the fleet's numeric-contract
plumbing, and the fused dequant kernels.

Quantization is a BUDGET, not exact parity: the int8 engine is compared
against the fp32 paged engine under a declared logit-error budget plus
greedy-token match — the same gate bench.py --serving enforces.
Everything here runs the lax fallbacks (tier-1, CPU); the Pallas
kernels validate in interpret mode in the slow class at the bottom.
"""
import numpy as np
import pytest

# headroom over the 4.3e-3 the bench measures on gpt_tiny; way below
# any greedy-decision flip observed on these models
LOGIT_BUDGET = 0.05


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    from paddle_tpu.models import gpt as G
    cfg = G.GPTConfig(vocab_size=256, hidden_size=32, num_layers=2,
                      num_heads=2, max_seq_len=64, dtype="float32",
                      use_flash=False, remat=False)
    params = G.init_params(cfg, jax.random.PRNGKey(0))
    return params, cfg


def _make_engine(tiny_model, **kw):
    from paddle_tpu.inference.serving import PagedServingEngine
    kw.setdefault("slots", 3)
    kw.setdefault("max_len", 32)
    kw.setdefault("page_size", 8)
    kw.setdefault("seq_buckets", (8, 16))
    kw.setdefault("batch_buckets", (1, 2))
    return PagedServingEngine(tiny_model, **kw)


def _trace(n=8, seed=3, vocab=256):
    rng = np.random.RandomState(seed)
    return [(rng.randint(1, vocab, rng.randint(3, 15)).astype(np.int32),
             int(rng.randint(3, 8))) for _ in range(n)]


# --------------------------------------------------------------------------
# weight quantization units
# --------------------------------------------------------------------------

class TestQuantizeParams:
    def test_int8_leaves_and_reconstruction(self, tiny_model):
        import jax.numpy as jnp
        from paddle_tpu.models import gpt as G
        params, cfg = tiny_model
        qp = G.quantize_params(params, "int8")
        for name in ("qkv_w", "proj_w", "fc1_w", "fc2_w"):
            leaf = qp["blocks"][name]
            assert leaf["qw"].dtype == jnp.int8
            assert leaf["scale"].dtype == jnp.float32
            # per-output-channel: the contraction axis is size 1
            assert leaf["scale"].shape[1] == 1
            w = np.asarray(params["blocks"][name], np.float32)
            back = (np.asarray(leaf["qw"], np.float32)
                    * np.asarray(leaf["scale"]))
            # absmax int8 rounding: error bounded by scale/2 per entry
            bound = np.asarray(leaf["scale"]) / 2 + 1e-8
            assert (np.abs(w - back) <= bound).all(), name
        # untouched leaves stay untouched
        assert qp["wte"] is params["wte"]
        assert qp["blocks"]["qkv_b"] is params["blocks"]["qkv_b"]

    def test_dynamic_mode_marks_leaves(self, tiny_model):
        from paddle_tpu.models import gpt as G
        qp = G.quantize_params(tiny_model[0], "int8_dynamic")
        assert "qw_dyn" in qp["blocks"]["fc1_w"]
        assert "qw" not in qp["blocks"]["fc1_w"]

    def test_unknown_mode_raises(self, tiny_model):
        from paddle_tpu.models import gpt as G
        with pytest.raises(ValueError, match="quant mode"):
            G.quantize_params(tiny_model[0], "int4")

    def test_fp8_where_available(self, tiny_model):
        from paddle_tpu.framework import jax_compat
        from paddle_tpu.models import gpt as G
        qp = G.quantize_params(tiny_model[0], "fp8")
        leaf = qp["blocks"]["fc1_w"]
        assert leaf["qw"].dtype == jax_compat.fp8_dtype()
        w = np.asarray(tiny_model[0]["blocks"]["fc1_w"], np.float32)
        back = (np.asarray(leaf["qw"], np.float32)
                * np.asarray(leaf["scale"]))
        # e4m3 keeps ~2-3 mantissa bits: coarse but bounded
        assert float(np.abs(w - back).max()) < 0.1 * float(
            np.abs(w).max()) + 1e-6

    def test_quantize_kv_roundtrip(self):
        from paddle_tpu.models import gpt as G
        import jax.numpy as jnp
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(5, 4, 16).astype(np.float32))
        q, s = G.quantize_kv(x)
        assert q.dtype == jnp.int8 and s.shape == (5, 4)
        back = G.dequantize_kv(q, s, jnp.float32)
        err = np.abs(np.asarray(x) - np.asarray(back))
        # per-position-per-head absmax: error <= scale/2 per element
        assert (err <= np.asarray(s)[..., None] / 2 + 1e-7).all()
        # requantizing dequantized content is a fixed point (the chunk
        # path's safety property: bytes never drift)
        q2, s2 = G.quantize_kv(back)
        assert (np.asarray(q2) == np.asarray(q)).all()

    def test_int8_dynamic_matmul_matches_fp(self):
        import jax.numpy as jnp
        from paddle_tpu import quantization as Q
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(6, 32).astype(np.float32))
        w = rng.randn(32, 16).astype(np.float32)
        ws = np.abs(w).max(0) / 127.0
        wq = jnp.asarray(np.clip(np.round(w / ws), -127, 127)
                         .astype(np.int8))
        got = np.asarray(Q.int8_dynamic_matmul(x, wq, jnp.asarray(ws)))
        want = np.asarray(x) @ w
        assert np.abs(got - want).max() < 0.05 * np.abs(want).max() + 1e-3

    def test_int8_dynamic_scale_is_batch_invariant(self):
        """Regression (review finding): the dynamic activation scale is
        per-ROW — a row's output must not change when it shares a batch
        with a huge-magnitude neighbor, or retries in a different batch
        mix would break the token-exact retry guarantee."""
        import jax.numpy as jnp
        from paddle_tpu import quantization as Q
        rng = np.random.RandomState(2)
        row = rng.randn(1, 32).astype(np.float32)
        loud = 1000.0 * rng.randn(1, 32).astype(np.float32)
        w = rng.randn(32, 16).astype(np.float32)
        ws = jnp.asarray(np.abs(w).max(0) / 127.0)
        wq = jnp.asarray(np.clip(np.round(w / np.asarray(ws)), -127, 127)
                         .astype(np.int8))
        alone = np.asarray(Q.int8_dynamic_matmul(jnp.asarray(row), wq, ws))
        stacked = np.asarray(Q.int8_dynamic_matmul(
            jnp.asarray(np.concatenate([row, loud])), wq, ws))[:1]
        assert (alone == stacked).all()


# --------------------------------------------------------------------------
# quantized engine vs fp32 engine (the accuracy-budget gate)
# --------------------------------------------------------------------------

class TestQuantEngineBudget:
    def test_churn_parity_within_budget(self, tiny_model):
        """int8 weights + int8 KV vs the fp32 paged engine over churned
        mixed-length traffic (wave + chunked admissions): greedy tokens
        EXACT, per-token logits within the declared budget."""
        fp = _make_engine(tiny_model, capture_logits=True,
                          prefill_chunk=8)
        q = _make_engine(tiny_model, capture_logits=True, prefill_chunk=8,
                         quant="int8", kv_dtype="int8")
        fp.warmup()
        assert q.warmup() >= 1
        trace = _trace(10)
        rf = [fp.submit(p, m) for p, m in trace]
        rq = [q.submit(p, m) for p, m in trace]
        fp.run()
        q.run()
        st = q.stats()
        assert st["decode_compiles"] == 1
        assert st["slot_occupancy_peak"] >= 2      # churn really batched
        max_err = 0.0
        for a, b in zip(rf, rq):
            assert a.tokens == b.tokens, (a.id, a.tokens, b.tokens)
            for la, lb in zip(a.logits, b.logits):
                max_err = max(max_err, float(np.abs(la - lb).max()))
        assert 0 < max_err <= LOGIT_BUDGET, max_err
        assert st["pages_in_use"] == 0             # nothing leaked
        assert st["quant_matmuls"] > 0
        assert st["kv_quant_bytes_saved"] > 0

    def test_zero_steady_state_compiles(self, tiny_model):
        from paddle_tpu.observability import metrics
        q = _make_engine(tiny_model, prefill_chunk=8, quant="int8",
                         kv_dtype="int8")
        q.warmup()
        before = metrics.counter("compile.count").value
        for p, m in _trace(8, seed=11):
            q.submit(p, m)
        q.run()
        assert metrics.counter("compile.count").value == before, \
            "quantized steady state retraced"
        assert q.stats()["decode_compiles"] == 1

    def test_weight_only_quant_on_slot_engine(self, tiny_model):
        """quant= is engine-agnostic: the slot-contiguous engine's
        executables take the same quantized pytree."""
        from paddle_tpu.inference.serving import ServingEngine
        params, cfg = tiny_model
        fp = ServingEngine(tiny_model, slots=2, max_len=32,
                           seq_buckets=(8, 16), batch_buckets=(1, 2),
                           capture_logits=True)
        q = ServingEngine(tiny_model, slots=2, max_len=32,
                          seq_buckets=(8, 16), batch_buckets=(1, 2),
                          capture_logits=True, quant="int8")
        fp.warmup()
        q.warmup()
        trace = _trace(4, seed=5)
        rf = [fp.submit(p, m) for p, m in trace]
        rq = [q.submit(p, m) for p, m in trace]
        fp.run()
        q.run()
        for a, b in zip(rf, rq):
            assert a.tokens == b.tokens
            for la, lb in zip(a.logits, b.logits):
                assert float(np.abs(la - lb).max()) <= LOGIT_BUDGET

    def test_kv_accounting_matches_actual_dtypes(self, tiny_model):
        """Satellite: byte accounting derives from the REAL cache
        arrays — int8 pages + fp32 scale rows — never an assumed
        4-byte element."""
        params, cfg = tiny_model
        q = _make_engine(tiny_model, quant="int8", kv_dtype="int8")
        st = q.stats()
        P, ps = q._num_pages, q._page_size
        L, nh, hd = cfg.num_layers, cfg.num_heads, cfg.head_dim
        expect = 2 * L * P * ps * nh * (hd + 4)    # int8 k/v + f32 scales
        assert st["kv_bytes_total"] == expect
        fp = _make_engine(tiny_model)
        assert fp.stats()["kv_bytes_total"] == 2 * L * P * ps * nh * hd * 4
        # the saved-bytes counter is the honest difference
        assert st["kv_quant_bytes_saved"] == \
            fp.stats()["kv_bytes_total"] - st["kv_bytes_total"]
        # reserved bytes track in-use pages at the quantized page cost
        q.warmup()
        r = q.submit(np.arange(1, 10, dtype=np.int32), 4)
        q.step()
        st2 = q.stats()
        page_bytes = expect // P
        assert st2["kv_bytes_reserved"] == \
            st2["pages_in_use"] * page_bytes
        q.run()


# --------------------------------------------------------------------------
# quantized pages: prefix reuse, COW, preemption
# --------------------------------------------------------------------------

class TestQuantPages:
    def test_prefix_reuse_attestation_on_quant_pages(self, tiny_model):
        """The satellite's attestation: a second identical prompt on the
        int8 pool allocates ZERO new pages and decodes identically."""
        q = _make_engine(tiny_model, page_size=4, quant="int8",
                         kv_dtype="int8")
        q.warmup()
        sys_prompt = np.arange(1, 11, dtype=np.int32)   # 10 tokens, 3 pages
        r1 = q.submit(sys_prompt, 4)
        q.run()
        s1 = q.stats()
        r2 = q.submit(sys_prompt, 4)
        q.run()
        s2 = q.stats()
        assert s2["prefix_page_hits"] - s1["prefix_page_hits"] == 3
        assert s2["prefix_page_misses"] - s1["prefix_page_misses"] == 0
        assert r1.tokens == r2.tokens

    def test_cow_on_int8_scale_page_pairs(self, tiny_model):
        """Two in-flight requests sharing a quantized prefix: COW must
        copy the int8 bytes AND the scale rows (a page without its
        scales dequantizes to garbage) — caught by comparing both
        requests against an unshared run of the same prompt."""
        prompt = np.arange(20, 30, dtype=np.int32)
        solo = _make_engine(tiny_model, page_size=4, quant="int8",
                            kv_dtype="int8")
        solo.warmup()
        ref = solo.submit(prompt, 6)
        solo.run()
        q = _make_engine(tiny_model, page_size=4, quant="int8",
                         kv_dtype="int8")
        q.warmup()
        ra = q.submit(prompt, 6)
        rb = q.submit(prompt, 6)
        q.run()
        assert q.stats()["cow_copies"] >= 1
        assert ra.tokens == ref.tokens
        assert rb.tokens == ref.tokens

    def test_injected_exhaustion_preemption_retry_parity(self, tiny_model):
        """An injected page_exhaustion preempts a quantized request; its
        re-prefilled retry must land the SAME tokens a fault-free run
        produces (deterministic quantization => deterministic retry)."""
        from paddle_tpu.testing import faults
        trace = [(np.arange(1, 6, dtype=np.int32), 6),
                 (np.arange(2, 7, dtype=np.int32), 6)]
        clean = _make_engine(tiny_model, slots=2, seq_buckets=(16,),
                             quant="int8", kv_dtype="int8")
        clean.warmup()
        want = [clean.submit(p, m) for p, m in trace]
        clean.run()
        faults.clear()
        faults.install("page_exhaustion:step=2")
        try:
            q = _make_engine(tiny_model, slots=2, seq_buckets=(16,),
                             quant="int8", kv_dtype="int8")
            q.warmup()
            got = [q.submit(p, m) for p, m in trace]
            done = q.run(max_steps=200)
            st = q.stats()
            assert st["preemptions"] == 1
            assert len(done) == 2
            assert sum(r.preemptions for r in got) == 1
            for w, g in zip(want, got):
                assert w.tokens == g.tokens, (g.id, w.tokens, g.tokens)
            assert st["pages_in_use"] == 0
        finally:
            faults.clear()

    def test_engine_error_rebuilds_quant_pool(self, tiny_model):
        """The slot-leak fix on the int8 pool: a mid-step failure frees
        pages, rebuilds pool + scale arrays, and retries token-exact."""
        from paddle_tpu.testing import faults
        faults.clear()
        faults.install("engine_error:step=2")
        try:
            q = _make_engine(tiny_model, slots=2, quant="int8",
                             kv_dtype="int8")
            q.warmup()
            a = q.submit(np.arange(1, 8, dtype=np.int32), 5)
            b = q.submit(np.arange(2, 9, dtype=np.int32), 5)
            with pytest.raises(faults.InjectedFault):
                q.run()
            victims = q.take_aborted()
            assert victims
            assert q.stats()["pages_in_use"] == 0
            for v in victims:
                q.submit(v.reset_for_retry())
            q.run()
            faults.clear()
            clean = _make_engine(tiny_model, slots=2, quant="int8",
                                 kv_dtype="int8")
            clean.warmup()
            ca = clean.submit(a.prompt, a.max_new_tokens)
            cb = clean.submit(b.prompt, b.max_new_tokens)
            clean.run()
            assert a.tokens == ca.tokens
            assert b.tokens == cb.tokens
        finally:
            faults.clear()

    def test_hash_salt_separates_numeric_contracts(self):
        """Satellite: the prefix-page content keys are salted with the
        quant config — identical prompts under different contracts can
        never produce colliding keys (a mixed fleet comparing keys
        across replicas must not alias their pages)."""
        from paddle_tpu.inference.kv_pager import KVPager
        prompt = np.arange(1, 11)
        a = KVPager(17, 4, slots=1, hash_key="quant=none/kv=fp")
        b = KVPager(17, 4, slots=1, hash_key="quant=int8/kv=int8")
        c = KVPager(17, 4, slots=1)                 # legacy: unsalted
        ka, kb, kc = (p._prompt_keys(prompt) for p in (a, b, c))
        assert ka != kb
        assert kc not in (ka, kb)

    def test_engine_pager_carries_contract_salt(self, tiny_model):
        q = _make_engine(tiny_model, quant="int8", kv_dtype="int8")
        fp = _make_engine(tiny_model)
        assert q._pager.hash_key == "quant=int8/kv=int8"
        assert fp._pager.hash_key == "quant=none/kv=fp"
        assert q._pager.hash_key != fp._pager.hash_key


# --------------------------------------------------------------------------
# fleet satellites: numeric contract + capacity routing
# --------------------------------------------------------------------------

class TestFleetQuantContract:
    def _fleet_stub(self, spec):
        from paddle_tpu.inference.fleet import ServingFleet
        fleet = ServingFleet.__new__(ServingFleet)
        fleet.model_spec = spec
        fleet._slots = 4
        fleet.dispatch_queue_depth = 4
        return fleet

    def test_contract_match_and_mismatch(self):
        fleet = self._fleet_stub({"paged": True, "quant": "int8",
                                  "kv_dtype": "int8"})
        ok = {"quant": "int8", "kv_dtype": "int8"}
        assert fleet._contract_mismatch(ok) is None
        bad = fleet._contract_mismatch({"quant": None, "kv_dtype": None})
        # the attestation tuple grew tp + role in ISSUE 15, pp in 20
        assert bad == ((None, None, None, 1, 1, "unified"),
                       ("int8", "int8", None, 1, 1, "unified"))
        # fp32 fleet rejects a quantized replica too
        fp = self._fleet_stub({"paged": True})
        assert fp._contract_mismatch({"quant": None,
                                      "kv_dtype": None}) is None
        assert fp._contract_mismatch(ok) is not None

    def test_worker_spec_builds_quant_engine(self, tiny_model):
        """The replica spec's quant/kv_dtype reach the engine and echo
        back through stats (what the hello attestation reads)."""
        from paddle_tpu.inference.fleet_worker import _build_engine
        eng = _build_engine({"cfg": {
            "vocab_size": 256, "hidden_size": 32, "num_layers": 2,
            "num_heads": 2, "max_seq_len": 64, "dtype": "float32",
            "use_flash": False, "remat": False},
            "paged": True, "slots": 2, "max_len": 32, "page_size": 8,
            "seq_buckets": [8, 16], "batch_buckets": [1],
            "quant": "int8", "kv_dtype": "int8"})
        st = eng.stats()
        assert st["quant"] == "int8" and st["kv_dtype"] == "int8"

    def test_spec_kv_dtype_without_paged_fails_fast(self):
        """Regression (review finding): a spec the engine cannot honor
        must fail in the CALLER's process, not as N permanently-dead
        replicas after hello-attestation churn."""
        from paddle_tpu.inference.fleet import ServingFleet
        from paddle_tpu.inference.fleet_worker import _build_engine
        with pytest.raises(ValueError, match="paged"):
            ServingFleet({"quant": "int8", "kv_dtype": "int8"},
                         replicas=1)
        with pytest.raises(ValueError, match="paged"):
            _build_engine({"kv_dtype": "int8"})
        # a typo'd quant mode must fail at construction too, not as N
        # replicas crashing in gpt.quantize_params before hello
        with pytest.raises(ValueError, match="quant mode"):
            ServingFleet({"paged": True, "quant": "int4"}, replicas=1)
        with pytest.raises(ValueError, match="kv_dtype"):
            ServingFleet({"paged": True, "kv_dtype": "fp8"}, replicas=1)

    def test_engine_kv_dtype_rejects_cache_dtype(self, tiny_model):
        with pytest.raises(ValueError, match="mutually exclusive"):
            _make_engine(tiny_model, kv_dtype="int8",
                         cache_dtype="float32")

    def test_capacity_routing_in_page_units(self):
        """Satellite: routing math is PAGE-denominated, so an int8
        replica whose pool holds ~4x the tokens per byte budget routes
        exactly like its stats say — no 4-byte assumption anywhere."""
        fleet = self._fleet_stub({"paged": True, "quant": "int8",
                                  "kv_dtype": "int8"})

        class _R:
            def __init__(self, stats, inflight=0):
                self.last_stats = stats
                self.inflight = dict.fromkeys(range(inflight))

        # an int8 replica at the same BYTE budget reports ~4x the free
        # pages of its fp twin; capacity scales with it
        q = _R({"slots": 4, "pages_free": 96, "kv_dtype": "int8",
                "pages_per_request_est": 3})
        fp = _R({"slots": 4, "pages_free": 24, "kv_dtype": None,
                 "pages_per_request_est": 3})
        assert fleet._capacity(q) == 8               # slot bound wins
        assert fleet._capacity(fp) == 8
        starved_q = _R({"slots": 4, "pages_free": 9, "kv_dtype": "int8",
                        "pages_per_request_est": 3})
        assert fleet._capacity(starved_q) == 3       # 9 // 3


# --------------------------------------------------------------------------
# fused dequant kernels (interpret mode) — slow tier
# --------------------------------------------------------------------------

@pytest.mark.slow
class TestDequantMatmulKernel:
    @pytest.mark.parametrize("M,K,N", [
        (8, 128, 256),
        (128, 256, 128),
        (32, 128, 512),
    ])
    def test_kernel_matches_lax_fallback(self, M, K, N):
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.dequant_matmul import (
            _dqmm_tpu, _pick_blocks, _ref_dequant_matmul)
        rng = np.random.RandomState(M + N)
        x = jnp.asarray(rng.randn(M, K).astype(np.float32))
        wq = jnp.asarray(rng.randint(-127, 128, (K, N)).astype(np.int8))
        s = jnp.asarray((rng.rand(N).astype(np.float32) + 0.1) / 64)
        blocks = _pick_blocks(M, K, N, 4)
        assert blocks is not None
        ref = _ref_dequant_matmul(x, wq, s)
        got = _dqmm_tpu(x, wq, s, *blocks, interpret=True)
        denom = max(1e-6, float(jnp.abs(ref).max()))
        assert float(jnp.abs(ref - got).max()) / denom < 1e-5

    def test_public_entry_reshapes_and_counts(self):
        import jax.numpy as jnp
        from paddle_tpu.observability import metrics
        from paddle_tpu.ops.pallas.dequant_matmul import dequant_matmul
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(2, 4, 128).astype(np.float32))
        wq = jnp.asarray(rng.randint(-127, 128, (128, 128))
                         .astype(np.int8))
        s = jnp.asarray(np.full((128,), 0.01, np.float32))
        before = metrics.counter("serving.dequant_kernel_calls").value
        out = dequant_matmul(x, wq, s, interpret=True)
        assert out.shape == (2, 4, 128)
        assert metrics.counter("serving.dequant_kernel_calls").value \
            == before + 1

    def test_decode_sized_m_pads_into_kernel(self):
        """Regression (review finding): M = slots (a handful of decode
        lanes) sits below the sublane minimum — the kernel must pad
        rows up and slice back, not silently fall back to float weights
        on exactly the memory-bound path it exists for."""
        import jax.numpy as jnp
        from paddle_tpu.observability import metrics
        from paddle_tpu.ops.pallas.dequant_matmul import (
            _ref_dequant_matmul, dequant_matmul)
        rng = np.random.RandomState(4)
        x = jnp.asarray(rng.randn(3, 128).astype(np.float32))   # M=3
        wq = jnp.asarray(rng.randint(-127, 128, (128, 256))
                         .astype(np.int8))
        s = jnp.asarray((rng.rand(256).astype(np.float32) + 0.1) / 64)
        before = metrics.counter("serving.dequant_kernel_calls").value
        got = dequant_matmul(x, wq, s, interpret=True)
        assert metrics.counter("serving.dequant_kernel_calls").value \
            == before + 1, "decode-sized M fell back to the lax path"
        ref = _ref_dequant_matmul(x, wq, s)
        denom = max(1e-6, float(jnp.abs(ref).max()))
        assert float(jnp.abs(ref - got).max()) / denom < 1e-5


def _pool_of(pages):
    """One layer's [P, ps, nh, hd] pages as the one-layer pool the
    kernel takes: [1, P, ps, nh * hd], the same bytes."""
    return pages.reshape(1, *pages.shape[:2], -1)


@pytest.mark.slow
class TestPagedAttentionQuantKernel:
    @pytest.mark.parametrize("S,nh,hd,P,ps,maxP", [
        (4, 4, 16, 12, 8, 4),
        (2, 2, 64, 6, 16, 2),
        (3, 4, 32, 16, 8, 6),
    ])
    def test_kernel_matches_lax_fallback(self, S, nh, hd, P, ps, maxP):
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.paged_attn import (
            _paged_call, _ref_paged_attention_quant)
        rng = np.random.RandomState(S + P)
        q = jnp.asarray(rng.randn(S, 1, nh, hd).astype(np.float32))
        kq = jnp.asarray(rng.randint(-127, 128, (P, ps, nh, hd))
                         .astype(np.int8))
        vq = jnp.asarray(rng.randint(-127, 128, (P, ps, nh, hd))
                         .astype(np.int8))
        ks = jnp.asarray((rng.rand(P, ps, nh).astype(np.float32)
                          + 0.05) / 64)
        vs = jnp.asarray((rng.rand(P, ps, nh).astype(np.float32)
                          + 0.05) / 64)
        pt = jnp.asarray(rng.randint(0, P, (S, maxP)).astype(np.int32))
        lens = jnp.asarray(
            rng.randint(0, maxP * ps, (S,)).astype(np.int32))
        ref = _ref_paged_attention_quant(q, kq, ks, vq, vs, pt, lens)
        got = _paged_call(
            q, (_pool_of(kq), _pool_of(vq)), (ks, vs), pt, lens,
            jnp.int32(0), interpret=True)
        assert float(jnp.abs(ref - got).max()) < 1e-5

    def test_kernel_matches_fallback_bf16(self):
        """The compute-dtype casts around the probs @ V contraction must
        mirror the fallback's (vc.astype(cd)) — float32 tests cannot see
        a missing cast; bf16 can."""
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.paged_attn import (
            _paged_call, _ref_paged_attention_quant)
        rng = np.random.RandomState(9)
        q = jnp.asarray(rng.randn(3, 1, 2, 32)).astype(jnp.bfloat16)
        kq = jnp.asarray(rng.randint(-127, 128, (8, 8, 2, 32))
                         .astype(np.int8))
        vq = jnp.asarray(rng.randint(-127, 128, (8, 8, 2, 32))
                         .astype(np.int8))
        ks = jnp.asarray((rng.rand(8, 8, 2).astype(np.float32)
                          + 0.05) / 64)
        vs = jnp.asarray((rng.rand(8, 8, 2).astype(np.float32)
                          + 0.05) / 64)
        pt = jnp.asarray(rng.randint(0, 8, (3, 3)).astype(np.int32))
        lens = jnp.asarray(rng.randint(0, 24, (3,)).astype(np.int32))
        ref = _ref_paged_attention_quant(q, kq, ks, vq, vs, pt, lens)
        got = _paged_call(
            q, (_pool_of(kq), _pool_of(vq)), (ks, vs), pt, lens,
            jnp.int32(0), interpret=True)
        diff = jnp.abs(ref.astype(jnp.float32)
                       - got.astype(jnp.float32))
        # bf16 accumulate: identical dtype semantics, bf16-ulp noise
        assert float(diff.max()) < 2e-2

    def test_kernel_len_zero_lane(self):
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.paged_attn import (
            _paged_call, _ref_paged_attention_quant)
        rng = np.random.RandomState(7)
        q = jnp.asarray(rng.randn(2, 1, 2, 16).astype(np.float32))
        kq = jnp.asarray(rng.randint(-127, 128, (5, 8, 2, 16))
                         .astype(np.int8))
        vq = jnp.asarray(rng.randint(-127, 128, (5, 8, 2, 16))
                         .astype(np.int8))
        ks = jnp.asarray(np.full((5, 8, 2), 0.02, np.float32))
        vs = jnp.asarray(np.full((5, 8, 2), 0.02, np.float32))
        pt = jnp.asarray(rng.randint(0, 5, (2, 2)).astype(np.int32))
        lens = jnp.asarray(np.array([0, 9], np.int32))
        ref = _ref_paged_attention_quant(q, kq, ks, vq, vs, pt, lens)
        got = _paged_call(
            q, (_pool_of(kq), _pool_of(vq)), (ks, vs), pt, lens,
            jnp.int32(0), interpret=True)
        assert float(jnp.abs(ref - got).max()) < 1e-5
