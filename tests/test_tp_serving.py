"""Tensor-parallel serving + prefill/decode disaggregation (ISSUE 15).

The tp engines here run on 2 of the test harness's 8 virtual CPU
devices: params placed with the megatron column/row rules from
distributed/auto/rules.py, KV pools sharded over 'tp' on the head
axis, executables GSPMD-partitioned from the operand shardings.  The
contract under test is the ISSUE's: token-exact greedy parity with the
single-device reference through churn / chunked prefill / preemption
retry, per-shard page-byte determinism, mesh-aware compile-cache keys
and artifact topology attestation, KV handoff (prefill-only extraction
-> injection) with the ``handoff_drop`` fault's re-ship path, and the
fleet contract tuple grown to (quant, kv_dtype, spec_mode, tp, role)
— and, since ISSUE 20, to the 6-wide
(quant, kv_dtype, spec_mode, tp, pp, role) with the pipeline-stage
axis riding along.
"""
import os

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    from paddle_tpu.models import gpt as G
    cfg = G.GPTConfig(vocab_size=256, hidden_size=32, num_layers=2,
                      num_heads=2, max_seq_len=64, dtype="float32",
                      use_flash=False, remat=False)
    params = G.init_params(cfg, jax.random.PRNGKey(0))
    return params, cfg


def _tp_engine(tiny_model, **kw):
    from paddle_tpu.inference.serving import PagedServingEngine
    params, cfg = tiny_model
    kw.setdefault("tp", 2)
    kw.setdefault("slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("seq_buckets", (8, 16, 32))
    kw.setdefault("batch_buckets", (1, 2))
    kw.setdefault("max_queue", 64)
    return PagedServingEngine((params, cfg), **kw)


def _reference(tiny_model, prompt, n):
    import jax.numpy as jnp
    from paddle_tpu.models import gpt as G
    params, cfg = tiny_model
    out = G.generate(params, cfg, jnp.asarray(prompt, jnp.int32)[None], n)
    return list(np.asarray(out)[0, len(prompt):])


class TestTPEngine:
    def test_sharded_placement_and_memory(self, tiny_model):
        from paddle_tpu.distributed.auto import rules
        eng = _tp_engine(tiny_model)
        params, _cfg = tiny_model
        full = rules.bytes_per_device(params)
        per_dev = eng.param_bytes_per_device()
        # the megatron splits shard the overwhelming share of the bytes
        assert per_dev < 0.75 * full, (per_dev, full)
        assert eng.stats()["tp"] == 2
        # the pool really shards the head axis: each device holds the
        # nh/2 heads' contiguous half of every page row [ps, nh * hd]
        k_pool = eng._cache_operands()[0]
        shards = k_pool.addressable_shards
        cfg = tiny_model[1]
        assert len(shards) == 2
        assert k_pool.shape[2:] == (eng._page_size,
                                    cfg.num_heads * cfg.head_dim)
        assert shards[0].data.shape[3] == cfg.num_heads // 2 * cfg.head_dim

    @pytest.mark.slow      # ~18s; tier-1 budget (per-shard bytes
                           # + handoff roundtrip keep tp covered)
    def test_parity_churn_and_chunked(self, tiny_model):
        from paddle_tpu.observability import metrics as obs
        eng = _tp_engine(tiny_model, prefill_chunk=16)
        eng.warmup()
        c0 = obs.counter("compile.count").value
        rng = np.random.RandomState(3)
        reqs = []
        for _ in range(8):      # > slots: the pool churns; two prompts
            n = int(rng.randint(3, 30))     # land on the chunked path
            p = rng.randint(1, 256, n).astype(np.int32)
            reqs.append(eng.submit(p, int(rng.randint(4, 10))))
        done = eng.run()
        st = eng.stats()
        assert len(done) == 8
        assert st["decode_compiles"] == 1, st
        assert obs.counter("compile.count").value == c0, \
            "tp steady state retraced"
        for r in reqs:
            assert r.tokens == _reference(tiny_model, r.prompt,
                                          r.max_new_tokens), r.id

    def test_parity_through_preemption_retry(self, tiny_model):
        from paddle_tpu.testing import faults
        faults.clear()
        faults.install("page_exhaustion:step=2")
        try:
            eng = _tp_engine(tiny_model)
            eng.warmup()
            rng = np.random.RandomState(9)
            reqs = [eng.submit(rng.randint(1, 256, 7).astype(np.int32), 8)
                    for _ in range(3)]
            eng.run()
            assert eng.stats()["preemptions"] >= 1
            for r in reqs:
                assert r.tokens == _reference(tiny_model, r.prompt, 8)
        finally:
            faults.clear()

    def test_slot_engine_tp_parity(self, tiny_model):
        from paddle_tpu.inference.serving import ServingEngine
        params, cfg = tiny_model
        eng = ServingEngine((params, cfg), tp=2, slots=2, max_len=48,
                            seq_buckets=(8, 16), batch_buckets=(1, 2))
        eng.warmup()
        rng = np.random.RandomState(4)
        p = rng.randint(1, 256, 9).astype(np.int32)
        req = eng.submit(p, 8)
        eng.run()
        assert req.tokens == _reference(tiny_model, p, 8)

    def test_per_shard_page_bytes_deterministic(self, tiny_model):
        """The page-byte determinism contract, PER SHARD: two identical
        traces leave every device's slice of the pool byte-identical —
        including through an injected preemption retry (greedy retries
        replay the same bytes)."""
        from paddle_tpu.testing import faults

        def run_trace(with_fault):
            faults.clear()
            if with_fault:
                faults.install("page_exhaustion:step=2")
            try:
                eng = _tp_engine(tiny_model)
                eng.warmup()
                rng = np.random.RandomState(11)
                for _ in range(3):
                    eng.submit(rng.randint(1, 256, 7).astype(np.int32), 6)
                eng.run()
                return [[np.asarray(s.data).tobytes()
                         for s in op.addressable_shards]
                        for op in eng._cache_operands()], eng
            finally:
                faults.clear()

        a, _ = run_trace(False)
        b, _ = run_trace(False)
        assert a == b, "same trace produced different per-shard bytes"
        assert len(a[0]) == 2       # two shards per operand
        # determinism holds THROUGH the preemption retry too: a retry
        # may land pages differently than the clean run, but two
        # identical preempted traces replay byte-identical shards
        c, eng_c = run_trace(True)
        d, _ = run_trace(True)
        assert eng_c.stats()["preemptions"] >= 1
        assert c == d, "preempted trace produced different shard bytes"

    def test_tp_knob_validation(self, tiny_model):
        from paddle_tpu.inference.serving import PagedServingEngine
        params, cfg = tiny_model
        with pytest.raises(ValueError, match="num_heads"):
            PagedServingEngine((params, cfg), tp=4, slots=2, max_len=32,
                               page_size=8)       # 2 heads % 4 != 0
        with pytest.raises(ValueError, match="devices"):
            from paddle_tpu.models import gpt as G
            G.serving_mesh(64)

    def test_tp_composes_with_quant(self, tiny_model):
        """ISSUE 20 (flipped from "raises"): tp=2 + quant='int8' used
        to be guarded off; now the {'qw','scale'} dict leaves get
        megatron specs via rules.quantized_like and the engine
        constructs sharded.  (Token-exact serving parity is the slow
        suite's test_tp_int8_parity — this stays compile-free.)"""
        eng = _tp_engine(tiny_model, quant="int8")
        assert eng.stats()["tp"] == 2 and eng.quant == "int8"
        # the int8 qw really shards: each device holds out-cols/2
        qw = eng.params["blocks"]["fc1_w"]["qw"]
        shards = qw.addressable_shards
        assert len(shards) == 2
        assert shards[0].data.shape[-1] == qw.shape[-1] // 2
        # scale mirrors the weight's spec with its collapsed axis-1
        # part replicated — the same rank owns matching scale columns
        sc = eng.params["blocks"]["fc1_w"]["scale"]
        assert sc.addressable_shards[0].data.shape[-1] \
            == sc.shape[-1] // 2
        # qkv: weight parts on the last axis, scale mirrors
        qkv_s = eng.params["blocks"]["qkv_w"]["scale"]
        assert qkv_s.addressable_shards[0].data.shape[-1] \
            == qkv_s.shape[-1] // 2

    def test_quantized_like_rule(self, tiny_model):
        """The spec-expansion rule itself: fp leaves keep their spec,
        {'qw','scale'} leaves get (weight spec, weight spec with the
        collapsed contraction axis replicated)."""
        from paddle_tpu.distributed.auto import rules
        from paddle_tpu.models import gpt as G
        from paddle_tpu.models import gpt_hybrid
        import jax.tree_util as jtu
        params, cfg = tiny_model
        qparams = G.quantize_params(params, "int8")
        specs = gpt_hybrid.param_specs(cfg)
        out = rules.quantized_like(specs, qparams)
        fc1 = out["blocks"]["fc1_w"]
        assert tuple(fc1["qw"]) == tuple(specs["blocks"]["fc1_w"])
        # axis 1 (the dim quantization collapsed to 1) must not part
        assert fc1["scale"][1] is None
        assert tuple(fc1["scale"][2:]) == tuple(fc1["qw"][2:])
        # fp leaves pass through untouched
        assert out["wte"] == specs["wte"]
        # and the spec tree stays zippable with the quantized params
        jtu.tree_map(lambda s, p: None, out, qparams,
                     is_leaf=lambda x: isinstance(
                         x, type(specs["wte"])))

    def test_env_knob(self, tiny_model, monkeypatch):
        monkeypatch.setenv("PADDLE_SERVE_TP", "2")
        eng = _tp_engine(tiny_model, tp=None)
        assert eng.stats()["tp"] == 2


class TestMeshKeysAndTopology:
    def test_make_key_folds_mesh(self):
        from paddle_tpu.framework import compile_cache as cc
        plain = cc.make_key("decode", donate=(1, 2))
        meshed = cc.make_key("decode", donate=(1, 2),
                             mesh=("tp", 2, "cpu", 2))
        assert plain != meshed
        # None keys exactly as the pre-TP era (cross-PR stability)
        assert cc.make_key("decode", donate=(1, 2), mesh=None) == plain

    def test_artifact_topology_attestation(self, tmp_path):
        """A sharded artifact never deserializes onto a mismatched
        mesh (rejected as stale, rebuilt); single-device artifacts
        (topology None — including records written before the field
        existed) stay valid."""
        import jax
        from paddle_tpu.framework import compile_cache as cc
        store = cc.ArtifactStore(str(tmp_path))
        compiled = jax.jit(lambda x: x + 1).lower(1.0).compile()
        store.save("k1", compiled, topology="tp/2/cpu/2")
        ok, reason = store.validate("k1", topology="tp/2/cpu/2")
        assert ok, reason
        ok, reason = store.validate("k1", topology=None)
        assert not ok and reason == "stale"
        ok, reason = store.validate("k1", topology="tp/4/cpu/4")
        assert not ok and reason == "stale"
        # single-device: both sides None stays valid
        store.save("k2", compiled)
        ok, reason = store.validate("k2")
        assert ok, reason
        fn, reason = store.load("k2", topology="tp/2/cpu/2")
        assert fn is None and reason == "stale"

    def test_engine_keys_separate_by_tp(self, tiny_model):
        eng2 = _tp_engine(tiny_model)
        from paddle_tpu.inference.serving import PagedServingEngine
        params, cfg = tiny_model
        eng1 = PagedServingEngine((params, cfg), slots=3, max_len=64,
                                  page_size=8, seq_buckets=(8, 16, 32),
                                  batch_buckets=(1, 2))
        assert eng1._aot_key("decode") != eng2._aot_key("decode")
        assert eng1._mesh_key() is None
        assert eng2._mesh_key() == ("tp", 2, "cpu", 2)
        assert eng1._topology() is None
        assert eng2._topology() == "tp/2/cpu/2"

    def test_engine_keys_separate_by_pp(self, tiny_model):
        """pp joins the mesh key/topology (ISSUE 20); pp==1 keys stay
        byte-identical to the pre-pp era so yesterday's tp artifacts
        survive the field's introduction."""
        eng_tp = _tp_engine(tiny_model)                       # pp == 1
        eng_pp = _tp_engine(tiny_model, pp=2)                 # 2x2 mesh
        assert eng_tp._mesh_key() == ("tp", 2, "cpu", 2)
        assert eng_pp._mesh_key() == ("pp", 2, "tp", 2, "cpu", 4)
        assert eng_pp._topology() == "pp/2/tp/2/cpu/4"
        assert eng_tp._aot_key("decode") != eng_pp._aot_key("decode")
        assert "/pp=2" in eng_pp._aot_sig()
        assert eng_pp.stats()["pp"] == 2
        # per-stage accounting: one entry per stage, params + kv split
        sb = eng_pp.stats()["stage_bytes"]
        assert len(sb) == 2
        for st in sb:
            assert st["params"] > 0 and st["kv"] > 0

    def test_pp_artifact_rejected_on_tp_only_mesh(self, tmp_path):
        """A ('pp','tp')-mesh artifact deserialized onto a tp-only mesh
        is stale -> rebuilt, never loaded (the satellite's attestation:
        stage-partitioned executables can only revive on the exact
        (pp, tp) grid that built them)."""
        import jax
        from paddle_tpu.framework import compile_cache as cc
        store = cc.ArtifactStore(str(tmp_path))
        compiled = jax.jit(lambda x: x + 1).lower(1.0).compile()
        store.save("pp_decode", compiled, topology="pp/2/tp/2/cpu/4")
        ok, reason = store.validate("pp_decode", topology="pp/2/tp/2/cpu/4")
        assert ok, reason
        for wrong in ("tp/2/cpu/2", "pp/4/tp/1/cpu/4", None):
            ok, reason = store.validate("pp_decode", topology=wrong)
            assert not ok and reason == "stale", (wrong, reason)
        fn, reason = store.load("pp_decode", topology="tp/2/cpu/2")
        assert fn is None and reason == "stale"

    def test_pp_knob_validation(self, tiny_model):
        from paddle_tpu.inference.serving import (PagedServingEngine,
                                                  ServingEngine)
        params, cfg = tiny_model
        with pytest.raises(ValueError, match="paged"):
            ServingEngine((params, cfg), pp=2, slots=2, max_len=32)
        with pytest.raises(ValueError, match="num_layers"):
            # 2 layers % 3 stages != 0
            PagedServingEngine((params, cfg), pp=3, tp=1, slots=3,
                               max_len=32, page_size=8)
        with pytest.raises(ValueError, match="quant"):
            PagedServingEngine((params, cfg), pp=2, quant="int8",
                               slots=2, max_len=32, page_size=8)
        with pytest.raises(ValueError, match="kv_dtype"):
            PagedServingEngine((params, cfg), pp=2, kv_dtype="int8",
                               slots=2, max_len=32, page_size=8)
        with pytest.raises(ValueError, match="prefill_chunk"):
            PagedServingEngine((params, cfg), pp=2, prefill_chunk=8,
                               slots=2, max_len=32, page_size=8)

    def test_pp_env_knob(self, tiny_model, monkeypatch):
        monkeypatch.setenv("PADDLE_SERVE_PP", "2")
        eng = _tp_engine(tiny_model, pp=None)
        assert eng.stats()["pp"] == 2
        assert eng._mesh_key()[:2] == ("pp", 2)


class TestKVHandoff:
    def _pair(self, tiny_model, **kw):
        pe = _tp_engine(tiny_model, tp=1, kv_handoff=True, **kw)
        de = _tp_engine(tiny_model, tp=1, kv_handoff=True, **kw)
        pe.warmup()
        de.warmup()
        return pe, de

    def test_extract_inject_roundtrip_parity(self, tiny_model):
        from paddle_tpu.inference.serving import Request
        pe, de = self._pair(tiny_model)
        rng = np.random.RandomState(7)
        prompt = rng.randint(1, 256, 13).astype(np.int32)
        req = Request(prompt, 8)
        req.prefill_only = True
        pe.submit(req)
        pe.run()
        assert req.done and req.finish_reason == "prefill_done"
        assert req.kv_payload is not None and len(req.kv_payload) == 2
        assert req.kv_payload[0].shape[1] == pe._pager.pages_for(13)
        st = pe.stats()
        assert st["kv_extracts"] == 1
        assert st["kv_handoff_bytes"] == sum(
            a.nbytes for a in req.kv_payload)
        # the prefill side released its slot + pages
        assert not pe._active.any()

        d = Request(prompt, 8, request_id=req.id)
        de.submit_prefilled(d, req.tokens[0], req.kv_payload)
        de.run()
        assert d.done
        assert d.tokens == _reference(tiny_model, prompt, 8)
        assert de.stats()["kv_injects"] == 1

    def test_handoff_prefix_hit_and_second_request(self, tiny_model):
        """A second identical prompt injected into the decode engine
        re-acquires the SAME physical pages (prefix hit) — the shipped
        bytes rewrite what the shared page already holds."""
        from paddle_tpu.inference.serving import Request
        pe, de = self._pair(tiny_model)
        prompt = np.arange(1, 17, dtype=np.int32)    # 2 full pages

        def handoff(rid):
            r = Request(prompt, 4, request_id=rid)
            r.prefill_only = True
            pe.submit(r)
            pe.run()
            d = Request(prompt, 4, request_id=rid)
            de.submit_prefilled(d, r.tokens[0], r.kv_payload)
            de.run()
            return d

        d1 = handoff("a")
        hits0 = de.stats()["prefix_page_hits"]
        d2 = handoff("b")
        assert de.stats()["prefix_page_hits"] > hits0
        assert d1.tokens == d2.tokens == _reference(tiny_model, prompt, 4)

    def test_payload_validation(self, tiny_model):
        from paddle_tpu.inference.serving import Request
        pe, de = self._pair(tiny_model)
        prompt = np.arange(1, 10, dtype=np.int32)
        req = Request(prompt, 4)
        req.prefill_only = True
        pe.submit(req)
        pe.run()
        bad = [a[:, :0] for a in req.kv_payload]    # wrong page count
        with pytest.raises(ValueError, match="payload"):
            de.submit_prefilled(Request(prompt, 4), req.tokens[0], bad)
        with pytest.raises(ValueError, match="payload"):
            de.submit_prefilled(Request(prompt, 4), req.tokens[0],
                                req.kv_payload[:1])

    def test_prefill_only_rejected_without_handoff(self, tiny_model):
        from paddle_tpu.inference.serving import Request
        eng = _tp_engine(tiny_model, tp=1)          # kv_handoff off
        req = Request(np.arange(1, 8, dtype=np.int32), 4)
        req.prefill_only = True
        with pytest.raises(ValueError, match="kv_handoff"):
            eng.submit(req)

    def test_natural_finish_at_prefill_ships_no_pages(self, tiny_model):
        """max_new_tokens == 1 finishes AT the prefill — a final
        completion, not a handoff."""
        from paddle_tpu.inference.serving import Request
        pe, _de = self._pair(tiny_model)
        req = Request(np.arange(1, 8, dtype=np.int32), 1)
        req.prefill_only = True
        pe.submit(req)
        pe.run()
        assert req.done and req.finish_reason == "length"
        assert req.kv_payload is None

    def test_injected_preemption_reinjects(self, tiny_model):
        """A preempted injected request goes back through the INJECT
        queue (its shipped pages re-land), never the prefill path —
        and replays token-exact."""
        from paddle_tpu.inference.serving import Request
        from paddle_tpu.testing import faults
        pe, de = self._pair(tiny_model)
        rng = np.random.RandomState(5)
        prompt = rng.randint(1, 256, 9).astype(np.int32)
        req = Request(prompt, 8)
        req.prefill_only = True
        pe.submit(req)
        pe.run()
        faults.clear()
        faults.install("page_exhaustion:step=2")
        try:
            # an OLDER plain row first, so the injected request is the
            # newest in-flight work — the preemption policy's victim
            de.submit(rng.randint(1, 256, 5).astype(np.int32), 6)
            de.step()
            d = Request(prompt, 8)
            de.submit_prefilled(d, req.tokens[0], req.kv_payload)
            de.run()
            assert de.stats()["preemptions"] >= 1
            assert d.tokens == _reference(tiny_model, prompt, 8)
            assert de.stats()["kv_injects"] >= 2    # re-injected
        finally:
            faults.clear()

    def test_handoff_drop_fault_hook(self):
        from paddle_tpu.testing import faults
        faults.clear()
        faults.install("handoff_drop:nth=2")
        try:
            assert not faults.handoff_drop()
            assert faults.handoff_drop()
            assert not faults.handoff_drop()        # fired once
        finally:
            faults.clear()


class TestFleetContractAndRoles:
    def _fleet_stub(self, spec):
        from paddle_tpu.inference.fleet import ServingFleet
        fleet = ServingFleet.__new__(ServingFleet)
        fleet.model_spec = spec
        fleet._slots = 4
        fleet.dispatch_queue_depth = 4
        return fleet

    def test_contract_tuple_grew_tp_and_role(self):
        fleet = self._fleet_stub({"paged": True, "tp": 2})
        ok = {"quant": None, "kv_dtype": None, "spec_mode": None,
              "tp": 2, "role": "unified"}
        assert fleet._contract_mismatch(ok) is None
        # mixed tp refuses like mixed int8/fp32
        bad = fleet._contract_mismatch(dict(ok, tp=1))
        assert bad == ((None, None, None, 1, 1, "unified"),
                       (None, None, None, 2, 1, "unified"))
        # wrong role refuses too
        assert fleet._contract_mismatch(dict(ok, role="prefill")) \
            is not None
        assert fleet._contract_mismatch(
            dict(ok, role="prefill"), role="prefill") is None
        # a tp-less fleet refuses a sharded replica
        plain = self._fleet_stub({"paged": True})
        assert plain._contract_mismatch(ok) is not None
        # absent tp/pp/role keys normalize to (1, 1, "unified")
        assert plain._contract_mismatch(
            {"quant": None, "kv_dtype": None, "spec_mode": None}) is None

    def test_contract_tuple_grew_pp(self):
        """ISSUE 20: mixed-pp hellos refuse like mixed-tp — a replica
        running a different stage decomposition computes different
        partial-sum orders, so it can never absorb re-queued work."""
        from paddle_tpu.inference.fleet import ServingFleet
        fleet = self._fleet_stub({"paged": True, "tp": 2, "pp": 2})
        ok = {"quant": None, "kv_dtype": None, "spec_mode": None,
              "tp": 2, "pp": 2, "role": "unified"}
        assert fleet._contract_mismatch(ok) is None
        bad = fleet._contract_mismatch(dict(ok, pp=1))
        assert bad == ((None, None, None, 2, 1, "unified"),
                       (None, None, None, 2, 2, "unified"))
        # a pp-less fleet refuses a staged replica, and vice versa
        plain = self._fleet_stub({"paged": True, "tp": 2})
        assert plain._contract_mismatch(ok) is not None
        assert fleet._contract_mismatch(dict(ok, pp=1)) is not None
        # model_spec validation: pp must be a positive int, on paged
        with pytest.raises(ValueError, match="pp must be an int"):
            ServingFleet({"paged": True, "pp": 0}, replicas=1)
        with pytest.raises(ValueError, match="paged"):
            ServingFleet({"pp": 2}, replicas=1)

    def test_role_plan_validation(self):
        from paddle_tpu.inference.fleet import ServingFleet
        spec = {"paged": True}
        with pytest.raises(ValueError, match="incoherent"):
            ServingFleet(spec, roles=["unified", "prefill", "decode"])
        with pytest.raises(ValueError, match="at least one prefill"):
            ServingFleet(spec, roles=["prefill", "prefill"])
        with pytest.raises(ValueError, match="paged"):
            ServingFleet({}, roles=["prefill", "decode"])
        with pytest.raises(ValueError, match="unknown roles"):
            ServingFleet(spec, roles=["prefill", "verifier"])
        with pytest.raises(ValueError, match="agree"):
            ServingFleet(spec, roles=["prefill", "decode"], replicas=3)
        with pytest.raises(ValueError, match="tp"):
            ServingFleet({"paged": True, "tp": 0}, replicas=1)

    def test_role_dict_normalization(self):
        from paddle_tpu.inference.fleet import ServingFleet
        plan = ServingFleet._normalize_roles({"prefill": 1, "decode": 2})
        assert plan == ["prefill", "decode", "decode"]
        assert ServingFleet._normalize_roles(None) is None
        with pytest.raises(ValueError, match="unknown roles"):
            ServingFleet._normalize_roles({"oracle": 1})

    def test_worker_requires_paged_for_roles(self, tiny_model):
        from paddle_tpu.inference import fleet_worker as fw
        with pytest.raises(ValueError, match="paged"):
            fw._build_engine({"preset": "gpt_tiny"}, role="prefill")
        with pytest.raises(ValueError, match="role"):
            fw._build_engine({"preset": "gpt_tiny", "paged": True},
                             role="verifier")

    def test_kv_payload_wire_roundtrip(self):
        from paddle_tpu.inference import fleet_worker as fw
        rng = np.random.RandomState(2)
        arrays = [rng.randn(2, 3, 8, 2, 16).astype(np.float32),
                  rng.randn(2, 3, 8, 2, 16).astype(np.float32)]
        wire = fw._encode_kv_payload(arrays)
        tok, back = fw._decode_kv_payload({"first_token": 7, "kv": wire})
        assert tok == 7
        for a, b in zip(arrays, back):
            assert a.dtype == b.dtype and (a == b).all()


class FakeRoleFleet:
    """Role-aware surface for the per-pool autoscaler loops."""

    def __init__(self):
        self.counts = {"prefill": 1, "decode": 1}
        self.sig = {r: dict(backlog=0, pending=0, pending_fraction=0.0,
                            occupancy=0.0, p99_s=None, p50_s=None,
                            window_n=0, sheds=0,
                            accepted_tokens_per_step=0.0)
                    for r in ("prefill", "decode")}
        self.added = []
        self.removed = []

    def autoscale_signals(self, window_s, role=None):
        assert role in ("prefill", "decode")
        s = dict(self.sig[role])
        s["configured"] = self.counts[role]
        s["healthy"] = self.counts[role]
        s["role"] = role
        return s

    def add_replica(self, role="unified"):
        self.counts[role] += 1
        self.added.append(role)
        return 100 + len(self.added)

    def scaledown_victim(self, role=None):
        return 7 if self.counts[role] > 1 else None

    def remove_replica(self, rid):
        self.removed.append(rid)


class TestRoleAutoscalers:
    def test_per_role_loops_scale_their_own_pool(self):
        from paddle_tpu.inference.autoscale import role_autoscalers
        fleet = FakeRoleFleet()
        pre, dec = role_autoscalers(
            fleet,
            prefill={"up_backlog_per_replica": 2.0},
            decode={"up_backlog_per_replica": 2.0},
            min_replicas=1, max_replicas=4, cooldown_s=0.0)
        assert pre.role == "prefill" and dec.role == "decode"
        # prefill pool backlog breaches; decode stays idle
        fleet.sig["prefill"]["backlog"] = 10
        assert pre.tick() == "up"
        assert dec.tick() is None
        assert fleet.added == ["prefill"]
        assert fleet.counts == {"prefill": 2, "decode": 1}
        rec = pre.stats()["decisions"][-1]
        assert rec["role"] == "prefill"          # records carry the role
        # decode pool scales down after its idle streak — victims come
        # from ITS pool
        dec.down_ticks = 2
        dec._down_streak = 0
        fleet.counts["decode"] = 2
        assert dec.tick() is None
        assert dec.tick() == "down"
        assert fleet.removed == [7]
        assert dec.stats()["decisions"][-1]["role"] == "decode"

    def test_role_validation(self):
        from paddle_tpu.inference.autoscale import Autoscaler
        with pytest.raises(ValueError, match="role"):
            Autoscaler(FakeRoleFleet(), role="verifier")


class TestDisaggFleetE2E:
    """Subprocess fleet e2e: 1 prefill + 1 decode replica, the
    handoff_drop fault forcing a re-ship — zero lost, token parity."""

    @pytest.mark.slow      # ~20s subprocess e2e; tier-1 budget
    def test_handoff_drop_reships_zero_lost(self, tmp_path):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.inference.fleet import ServingFleet
        from paddle_tpu.models import gpt as G
        from paddle_tpu.testing.env import clean_cpu_env

        env = clean_cpu_env(REPO, device_count=1)
        env.pop("PADDLE_FAULTS", None)
        env["PADDLE_FAULTS"] = "handoff_drop:nth=1"
        spec = {"cfg": {"vocab_size": 256, "hidden_size": 32,
                        "num_layers": 2, "num_heads": 2,
                        "max_seq_len": 128, "dtype": "float32",
                        "use_flash": False, "remat": False},
                "seed": 0, "paged": True, "slots": 3, "max_len": 64,
                "page_size": 8, "seq_buckets": [8, 16],
                "batch_buckets": [1, 2]}
        rng = np.random.RandomState(7)
        prompts = [rng.randint(1, 256, int(rng.randint(3, 12)))
                   for _ in range(4)]
        fleet = ServingFleet(
            spec, roles=["prefill", "decode"], env_base=env,
            jit_cache_dir=str(tmp_path / "jit"),
            log_dir=str(tmp_path / "logs"),
            heartbeat_s=30, restart_backoff_s=0.2)
        try:
            assert fleet.await_healthy(timeout=180) == 2
            for i, p in enumerate(prompts):
                fleet.submit(p, 10, request_id=f"r{i}")
            done, failed = fleet.drain(timeout=180)
            st = fleet.stats()
        finally:
            fleet.close()
        assert not failed and len(done) == len(prompts)
        assert st["kv_handoffs"] == len(prompts)
        assert st["handoff_reships"] >= 1, st     # the drop re-shipped
        assert st["kv_handoff_bytes"] > 0
        cfg = G.GPTConfig(**spec["cfg"])
        params = G.init_params(cfg, jax.random.PRNGKey(0))
        for i, p in enumerate(prompts):
            want = np.asarray(G.generate(
                params, cfg, jnp.asarray(p, jnp.int32)[None], 10))[
                    0, len(p):]
            assert list(want) == done[f"r{i}"].tokens, i
