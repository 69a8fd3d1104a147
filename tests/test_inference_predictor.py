"""Serving engine + predictor (ISSUE 5): continuous batching over the
slot-pooled KV cache, bucketed prefill compile bounds, generate parity,
persistent-compile-cache warm restart, queue back-pressure, and the
generate() edge-case regressions."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import gpt as G
from paddle_tpu.inference.serving import (ServingEngine, ServingQueueFull,
                                          serving_stats)
from paddle_tpu.observability import metrics


TINY = dict(vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
            max_seq_len=64, dtype="float32", use_flash=False, remat=False)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = G.GPTConfig(**TINY)
    params = G.init_params(cfg, jax.random.PRNGKey(7))
    return params, cfg


def _mk_engine(tiny_model, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("seq_buckets", (8, 16))
    kw.setdefault("batch_buckets", (1, 2))
    return ServingEngine(tiny_model, **kw)


def _prompts(n, lo=3, hi=14, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, TINY["vocab_size"],
                        rng.randint(lo, hi)).astype(np.int32)
            for _ in range(n)]


# --------------------------------------------------------------------------
# generate() edge cases (satellite regressions)
# --------------------------------------------------------------------------

def test_generate_one_and_two_tokens(tiny_model):
    """max_new_tokens=1 used to trace a zero-length lax.scan; 1- and
    2-token generation must work and agree on the shared first token."""
    params, cfg = tiny_model
    prompt = jnp.asarray(_prompts(1, seed=3)[0])[None]
    one = np.asarray(G.generate(params, cfg, prompt, 1))
    two = np.asarray(G.generate(params, cfg, prompt, 2))
    T0 = prompt.shape[1]
    assert one.shape == (1, T0 + 1)
    assert two.shape == (1, T0 + 2)
    assert (one[:, :T0] == np.asarray(prompt)).all()
    # greedy decoding: the first generated token is sample-independent
    assert one[0, T0] == two[0, T0]


def test_generate_rejects_nonpositive(tiny_model):
    params, cfg = tiny_model
    prompt = jnp.asarray(_prompts(1)[0])[None]
    with pytest.raises(ValueError):
        G.generate(params, cfg, prompt, 0)


def test_trim_eos():
    seqs = np.array([[9, 9, 5, 2, 7, 7],     # eos(2) in generated region
                     [9, 9, 5, 6, 7, 2],     # eos at the very end
                     [9, 2, 5, 6, 7, 7]])    # eos only in the PROMPT
    out = G.trim_eos(seqs, prompt_len=2, eos_token=2)
    assert [o.tolist() for o in out] == [
        [9, 9, 5, 2], [9, 9, 5, 6, 7, 2], [9, 2, 5, 6, 7, 7]]
    out = G.trim_eos(seqs, prompt_len=2, eos_token=2, include_eos=False)
    assert out[0].tolist() == [9, 9, 5]


# --------------------------------------------------------------------------
# slot-cache functional core
# --------------------------------------------------------------------------

def test_slot_decode_matches_forward_cached(tiny_model):
    """decode_step_slots on slot 2-of-3 must match the per-request
    forward_cached path to 1e-5 at every step."""
    params, cfg = tiny_model
    T0, n, S, max_len = 5, 5, 3, 24
    prompt = jnp.asarray(_prompts(1, seed=5)[0][:T0])[None]

    cache = G.init_cache(cfg, 1, T0 + n)
    lg, cache = G.forward_cached(params, prompt, cfg, cache)
    ref = [np.asarray(lg[0, -1])]
    tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)
    for _ in range(n - 1):
        lg, cache = G.forward_cached(params, tok[:, None], cfg, cache)
        ref.append(np.asarray(lg[0, -1]))
        tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)

    sc = G.init_slot_cache(cfg, S, max_len)
    pc = G.init_cache(cfg, 1, 8)
    plg, pc = G.forward_cached(params, jnp.pad(prompt, ((0, 0), (0, 3))),
                               cfg, pc)
    k = jax.lax.dynamic_update_slice(sc["k"], pc["k"], (0, 2, 0, 0, 0))
    v = jax.lax.dynamic_update_slice(sc["v"], pc["v"], (0, 2, 0, 0, 0))
    lens = jnp.zeros((S,), jnp.int32).at[2].set(T0)
    active = jnp.zeros((S,), bool).at[2].set(True)
    got = [np.asarray(plg[0, T0 - 1])]
    toks = jnp.zeros((S,), jnp.int32).at[2].set(jnp.argmax(plg[0, T0 - 1]))
    cache_s = {"k": k, "v": v, "len": lens}
    for _ in range(n - 1):
        lg_s, cache_s = G.decode_step_slots(params, toks, cfg, cache_s,
                                            active)
        got.append(np.asarray(lg_s[2]))
        toks = jnp.argmax(lg_s, -1).astype(jnp.int32)
    for a, b in zip(ref, got):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_reset_slots_host_and_device():
    lens = np.array([3, 5, 7], np.int32)
    G.reset_slots(lens, 1)
    assert lens.tolist() == [3, 0, 7]
    dl = jnp.asarray([3, 5, 7], jnp.int32)
    assert G.reset_slots(dl, [0, 2]).tolist() == [0, 5, 0]


# --------------------------------------------------------------------------
# serving engine
# --------------------------------------------------------------------------

def test_staggered_admission_release_and_parity(tiny_model):
    """7 staggered-length requests through 2 slots: every slot is reused,
    finished slots re-admit immediately, decode compiles once, and each
    request's tokens equal per-request generate()."""
    params, cfg = tiny_model
    eng = _mk_engine(tiny_model)
    prompts = _prompts(7, seed=11)
    rng = np.random.RandomState(11)
    mnts = [int(rng.randint(2, 7)) for _ in prompts]
    reqs = [eng.submit(p, m) for p, m in zip(prompts, mnts)]
    done = eng.run()
    assert len(done) == 7 and all(r.done for r in reqs)
    st = eng.stats()
    assert st["slot_occupancy_peak"] == 2          # pool ran full
    assert st["decode_compiles"] == 1              # churn never retraced
    assert st["slot_occupancy"] == 0 and st["queue_depth"] == 0
    for p, m, r in zip(prompts, mnts, reqs):
        want = np.asarray(G.generate(params, cfg, jnp.asarray(p)[None],
                                     m))[0, len(p):]
        assert (np.asarray(r.tokens) == want).all(), r.id
        assert r.finish_reason == "length"
        assert r.latency() is not None and r.latency() >= 0
        assert (r.output[:len(p)] == p).all()


def test_prefill_bucket_ladder_bounds_compiles(tiny_model):
    """warmup() compiles every ladder executable; arbitrary traffic after
    it adds ZERO prefill compiles (the bound the bench asserts)."""
    eng = _mk_engine(tiny_model)
    ladder = len(eng.seq_buckets) * len(eng.batch_buckets)
    compiled = eng.warmup()
    before = serving_stats()["prefill_compiles"]
    assert compiled <= ladder
    for p in _prompts(9, lo=3, hi=16, seed=13):
        eng.submit(p, 2)
    eng.run()
    assert serving_stats()["prefill_compiles"] == before
    assert eng.stats()["decode_compiles"] == 1


def test_warmup_covers_tight_top_rung(tiny_model):
    """A top rung whose prompts only fit with a smaller max_new_tokens
    (prompt 15 + 1 new on a max_len-16 ladder) must still be warmed:
    the legal request afterwards may not compile anything new."""
    eng = _mk_engine(tiny_model, max_len=16, seq_buckets=(8, 14, 16),
                     batch_buckets=(1,))
    eng.warmup()
    before = serving_stats()["prefill_compiles"]
    req = eng.submit(np.ones((15,), np.int32), 1)   # lands in the 16 rung
    eng.run()
    assert req.done and len(req.tokens) == 1
    assert serving_stats()["prefill_compiles"] == before


def test_warmup_ignores_small_max_queue(tiny_model):
    """Back-pressure is for traffic, not boot: a deliberately small
    admission queue must not reject warmup's compile waves (each wave
    queues a whole batch-bucket group at once), and the cap must come
    back afterwards."""
    eng = ServingEngine(tiny_model, slots=4, max_len=48, seq_buckets=(8,),
                        batch_buckets=(1, 2, 4), max_queue=2)
    eng.warmup()                    # 4-wide wave > max_queue: must not raise
    assert eng.max_queue == 2
    assert eng.stats()["queue_rejects"] == 0
    p = _prompts(1, seed=23)[0]
    for _ in range(eng.max_queue):
        eng.submit(p, 2)
    with pytest.raises(ServingQueueFull):
        eng.submit(p, 2)
    eng.run()


def test_queue_backpressure(tiny_model):
    eng = _mk_engine(tiny_model, slots=1, max_queue=2)
    p = _prompts(1, seed=17)[0]
    eng.submit(p, 2)
    eng.submit(p, 2)
    with pytest.raises(ServingQueueFull):
        eng.submit(p, 2)
    assert eng.stats()["queue_rejects"] >= 1
    eng.run()                       # drain frees the queue again
    eng.submit(p, 2)
    eng.run()


def test_generate_larger_than_queue(tiny_model):
    """generate() must absorb batches beyond max_queue by stepping the
    engine between submissions — not surface online back-pressure."""
    eng = _mk_engine(tiny_model, slots=1, max_queue=2)
    outs = eng.generate(_prompts(6, seed=37), max_new_tokens=2)
    assert len(outs) == 6 and all(len(t) == 2 for t in outs)
    assert eng.stats()["queue_rejects"] == 0


def test_submit_validation(tiny_model):
    eng = _mk_engine(tiny_model)
    with pytest.raises(ValueError):        # prompt + new > max_len
        eng.submit(np.ones((16,), np.int32), eng.max_len)
    with pytest.raises(ValueError):        # prompt beyond largest bucket
        eng.submit(np.ones((eng.seq_buckets[-1] + 1,), np.int32), 1)
    with pytest.raises(ValueError):
        eng.submit(np.ones((4,), np.int32), 0)
    with pytest.raises(ValueError):
        eng.submit(np.asarray([], np.int32), 2)
    from paddle_tpu.inference.serving import Request
    with pytest.raises(ValueError):        # limits on a prepared Request
        eng.submit(Request(np.ones((4,), np.int32), 2), max_new_tokens=8)
    req = eng.submit(Request(np.ones((4,), np.int32), 2))
    eng.run()
    assert req.done and len(req.tokens) == 2


def test_eos_early_stop_frees_slot(tiny_model):
    """A request whose eos_token the model is known to emit must finish
    at its FIRST occurrence with reason 'eos' and a freed slot."""
    params, cfg = tiny_model
    p = _prompts(1, seed=19)[0]
    eng = _mk_engine(tiny_model)
    [toks] = eng.generate([p], max_new_tokens=4)   # probe, same engine
    eos = int(toks[-1])
    want = toks[:toks.index(eos) + 1]      # up to the first occurrence
    req = eng.submit(p, 4, eos_token=eos)
    eng.run()
    assert req.done and req.finish_reason == "eos"
    assert req.tokens == want
    assert eng.stats()["slot_occupancy"] == 0


def test_prefill_finished_requests_are_returned(tiny_model):
    """A request satisfied by its prefill's FIRST token (max_new_tokens=1)
    must come back from step()/run(), not only via its handle."""
    eng = _mk_engine(tiny_model)
    req = eng.submit(_prompts(1, seed=29)[0], 1)
    done = eng.run()
    assert req.done and req in done and len(req.tokens) == 1
    assert eng.stats()["slot_occupancy"] == 0


def test_persistent_cache_warm_restart(tiny_model, tmp_path, monkeypatch):
    """A second engine over the same PADDLE_JIT_CACHE_DIR compiles 0 new
    executables: every prefill bucket + the decode step reload from the
    persistent cache."""
    from paddle_tpu.framework import jax_compat
    monkeypatch.setenv("PADDLE_JIT_CACHE_DIR", str(tmp_path))
    prev = jax_compat._persistent_cache_dir[0]
    try:
        hits = metrics.counter("compile.persistent_cache_hits")
        misses = metrics.counter("compile.persistent_cache_misses")
        ladder = dict(seq_buckets=(8,), batch_buckets=(1,))
        eng1 = _mk_engine(tiny_model, **ladder)
        eng1.warmup()
        m1 = misses.value
        assert m1 > 0                  # cold engine populated the cache
        # fresh engine object => fresh jit closures => jax's in-memory
        # executable cache can't serve them; only the persistent cache can
        h0 = hits.value
        eng2 = _mk_engine(tiny_model, **ladder)
        eng2.warmup()
        for p in _prompts(3, lo=3, hi=8, seed=23):
            eng2.submit(p, 3)
        eng2.run()
        assert misses.value == m1, (
            f"warm restart recompiled {misses.value - m1} executables")
        assert hits.value > h0
    finally:
        # detach the per-test tmp dir so later tests don't write into it
        jax_compat._persistent_cache_dir[0] = prev
        import jax as _jax
        _jax.config.update("jax_compilation_cache_dir", prev)


# --------------------------------------------------------------------------
# predictor + standalone artifact satellites
# --------------------------------------------------------------------------

def test_predictor_from_layer():
    from paddle_tpu.inference import Predictor
    net = paddle.nn.Sequential(paddle.nn.Linear(4, 8), paddle.nn.ReLU(),
                               paddle.nn.Linear(8, 3))
    pred = Predictor.from_layer(net)
    x = np.random.RandomState(0).randn(2, 4).astype(np.float32)
    want = net(paddle.to_tensor(x)).numpy()
    h = pred.get_input_handle("x0")
    h.copy_from_cpu(x)
    pred.run()
    out = pred.get_output_handle("out0").copy_to_cpu()
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)


def test_predictor_requires_path_or_layer():
    from paddle_tpu.inference import Config, create_predictor
    with pytest.raises(ValueError, match="model_path"):
        create_predictor(Config())


def test_standalone_signature_cache_static(tmp_path):
    """Repeated same-shape calls are ONE compile; a new shape is counted,
    not silent (serving.standalone_compiles)."""
    from paddle_tpu.inference import save_inference_model, StandaloneModel
    net = paddle.nn.Sequential(paddle.nn.Linear(4, 3))
    prefix = str(tmp_path / "sig")
    save_inference_model(prefix, net, [((2, 4), "float32")])
    m = StandaloneModel(prefix)
    c0 = serving_stats()["standalone_compiles"]
    x = np.random.RandomState(0).randn(2, 4).astype(np.float32)
    m(x)
    m(x + 1)
    assert serving_stats()["standalone_compiles"] == c0 + 1


def test_engine_stats_are_per_engine(tiny_model):
    """Two coexisting engines: traffic through B must not appear in
    A.stats() (the registry family is global; stats() is not)."""
    a = _mk_engine(tiny_model)
    b = _mk_engine(tiny_model)
    b.generate(_prompts(1, seed=31), max_new_tokens=3)
    sa, sb = a.stats(), b.stats()
    assert sa["requests_completed"] == 0 and sa["tokens_generated"] == 0
    assert sa["decode_compiles"] == 0 and sa["prefill_compiles"] == 0
    assert sb["requests_completed"] == 1 and sb["tokens_generated"] == 3


def test_standalone_aggregating_output_not_bucketed(tmp_path):
    """A symbolic-batch output that AGGREGATES over the batch dim (no
    dynamic axis in the manifest) must bypass pad-bucketing — zero pad
    rows would silently corrupt it."""
    from paddle_tpu.inference import save_inference_model, StandaloneModel
    prefix = str(tmp_path / "agg")
    save_inference_model(prefix, lambda x: x.mean(),
                         [((None, 4), "float32")])
    m = StandaloneModel(prefix)
    out, = m(np.full((3, 4), 2.0, np.float32))   # 3 pads to 4 if bucketed
    np.testing.assert_allclose(np.asarray(out), 2.0, rtol=1e-6)


def test_standalone_row_mixing_output_detected(tmp_path):
    """A model that mixes rows but KEEPS the batch axis (x - mean over
    the batch) defeats the manifest gate; the first-padded-call probe
    must catch it, return the exact result, and disable bucketing."""
    import paddle_tpu.tensor.math as _m
    from paddle_tpu.inference import save_inference_model, StandaloneModel
    prefix = str(tmp_path / "mix")
    save_inference_model(prefix, lambda x: x - _m.mean(x, 0, True),
                         [((None, 4), "float32")])
    m = StandaloneModel(prefix)
    x = np.random.RandomState(3).randn(3, 4).astype(np.float32)
    out, = m(x)                     # 3 pads to 4: probe must fire
    np.testing.assert_allclose(np.asarray(out), x - x.mean(0),
                               rtol=1e-5, atol=1e-6)
    assert m._bucketing is False    # permanently exact from here on
    out2, = m(x)
    np.testing.assert_allclose(np.asarray(out2), x - x.mean(0),
                               rtol=1e-5, atol=1e-6)


def test_standalone_inconclusive_probe_serves_exact(tmp_path):
    """When constant- and edge-replicated pads build IDENTICAL inputs
    (the last real row is all zeros), the probe proves nothing — that
    call must be answered at the EXACT shape, not with the unverified
    bucketed slice, or a row-mixing model returns silently wrong rows."""
    import paddle_tpu.tensor.math as _m
    from paddle_tpu.inference import save_inference_model, StandaloneModel
    prefix = str(tmp_path / "mix0")
    save_inference_model(prefix, lambda x: x - _m.mean(x, 0, True),
                         [((None, 4), "float32")])
    m = StandaloneModel(prefix)
    x = np.random.RandomState(5).randn(3, 4).astype(np.float32)
    x[-1] = 0.0                     # degenerate edge row: probe pending
    out, = m(x)
    np.testing.assert_allclose(np.asarray(out), x - x.mean(0),
                               rtol=1e-5, atol=1e-6)
    assert m._bucket_probed is False
    y = np.random.RandomState(6).randn(3, 4).astype(np.float32)
    out2, = m(y)                    # informative call: probe fires
    np.testing.assert_allclose(np.asarray(out2), y - y.mean(0),
                               rtol=1e-5, atol=1e-6)
    assert m._bucketing is False


def test_standalone_zero_batch_takes_exact_path(tmp_path):
    """Batch 0 must bypass bucketing (edge pads can't even be built from
    an empty axis): jax's shape-poly export contract requires symbolic
    dims >= 1, so the call must surface THAT clear ValueError — not a
    pad crash — and leave the probe untouched."""
    from paddle_tpu.inference import save_inference_model, StandaloneModel
    prefix = str(tmp_path / "zb")
    save_inference_model(prefix, lambda x: x * 2.0,
                         [((None, 4), "float32")])
    m = StandaloneModel(prefix)
    with pytest.raises(ValueError, match="polymorphic shape"):
        m(np.zeros((0, 4), np.float32))
    assert m._bucket_probed is False   # nothing was probed on the way


def test_standalone_symbolic_batch_one_compile(tmp_path):
    """Symbolic-batch artifact called at two batch sizes in one pad
    bucket: ONE compile, outputs sliced back to the true batch."""
    from paddle_tpu.inference import save_inference_model, StandaloneModel
    net = paddle.nn.Sequential(paddle.nn.Linear(4, 8), paddle.nn.Tanh(),
                               paddle.nn.Linear(8, 3))
    net.eval()
    prefix = str(tmp_path / "poly")
    meta = save_inference_model(prefix, net, [((None, 4), "float32")])
    assert meta["dynamic_batch"] is True
    m = StandaloneModel(prefix)
    c0 = serving_stats()["standalone_compiles"]
    rng = np.random.RandomState(1)
    for b in (5, 7):                   # both pad to the 8-bucket
        x = rng.randn(b, 4).astype(np.float32)
        out, = m(x)
        assert out.shape == (b, 3)
        want = net(paddle.to_tensor(x)).numpy()
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5,
                                   atol=1e-6)
    assert serving_stats()["standalone_compiles"] == c0 + 1
