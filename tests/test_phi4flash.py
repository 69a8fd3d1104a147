"""The ``phi4flash`` family (models/phi4flash.py: state-space layers,
differential attention over a window, one full-attention layer whose K/V
the later attention layers read, gated memory units) held to its plain
float32 reference (testing/reference_phi4flash.py) on seeded random
weights, at a small size on the CPU (8 layers: two (state-space, window)
pairs, the memory and full layers, one (memory unit, cross) pair; 8
query and 4 key/value heads of 8; a WINDOW OF 8, so that a request of a
few dozen positions wraps every ring several times): LOGITS, never
sampled tokens.

Tolerances, each with its reason.

``TOL`` 5e-6 — float32 everywhere.  Program and reference both run
float32 with matmuls at ``highest`` and differ in the ORDER of the same
float32 sums (batched einsums against one head at a time, the blocked
scan against one position at a time, softmax over a ring's rows in ring
order, the kernel's fallback over a padded view): 2e-7..1e-6 read on
logits of deviation 0.43.  5e-6 is five times that, and a ten-thousandth
of what a planted fault reads: a window one row short 0.77, a slot's
state not reset 0.036, a zero memory 0.045.

``TOL_KERNEL`` 2e-5 — the Pallas kernel in interpret mode against its lax
fallback: float32 products on both sides, online softmax over page
groups against one softmax over the view (2e-7..4e-7 read on outputs of
size 1.8).
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference import serving
from paddle_tpu.inference.serving import (PagedServingEngine, Request,
                                          ServingEngine)
from paddle_tpu.models import gpt, phi4flash
from paddle_tpu.ops.pallas import paged_diff_attn as pda
from paddle_tpu.testing import reference_phi4flash as ref

TOL = 5e-6
TOL_KERNEL = 2e-5
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W = 8       # the tiny model's window


@pytest.fixture(scope="module")
def tiny():
    """Seeded weights with every bias and gain moved off its initial 0
    or 1, so that none of them can be dropped unseen."""
    cfg = phi4flash.phi4flash_tiny()
    params = phi4flash.init_params(cfg, jax.random.PRNGKey(0))
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    leaves = [x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
              if x.shape[-1] <= 128 else x for x, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(tree, leaves), cfg


def _hp(cfg):
    return dataclasses.asdict(cfg)


_REF_JITS = {}


def _ref(params, cfg, tokens, module=ref):
    """Reference logits [n, V] over ``tokens``, padded to one width so
    that calls share a compile: the model is causal, so padding behind a
    row cannot reach it."""
    width = -(-len(tokens) // 64) * 64
    key = (module.__name__, width, cfg.sliding_window)
    if key not in _REF_JITS:
        hp = _hp(cfg)
        _REF_JITS[key] = jax.jit(lambda p, t: module.logits(p, t, hp))
    padded = np.zeros((width,), np.int32)
    padded[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        return np.asarray(_REF_JITS[key](params, jnp.asarray(padded)))[
            :len(tokens)]


def _engine(model, **kw):
    args = dict(slots=3, max_len=64, page_size=4, num_pages=40,
                seq_buckets=(8, 16, 32), batch_buckets=(1, 2),
                capture_logits=True)
    args.update(kw)
    with jax.default_matmul_precision("highest"):
        return PagedServingEngine(model, **args)


def _run(eng, reqs):
    with jax.default_matmul_precision("highest"):
        for r in reqs:
            eng.submit(r)
        eng.run()
    return reqs


def _gap(model, req):
    """Worst |logit| difference between what the engine computed for
    each token it emitted and the reference's full forward over the
    request's own history."""
    params, cfg = model
    hist = np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)])
    want = _ref(params, cfg, hist)
    rows = len(req.prompt) - 1 + np.arange(len(req.tokens))
    return float(np.abs(np.stack(req.logits) - want[rows]).max())


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, n).astype(np.int32)


# --------------------------------------------------------------------------
# the model on the normal path
# --------------------------------------------------------------------------

def test_layer_kinds_follow_the_published_pattern():
    kinds = phi4flash.layer_kinds(phi4flash.Phi4FlashConfig())
    assert len(kinds) == 32
    assert kinds[:16] == ["ssm", "window"] * 8
    assert kinds[16:18] == ["ssm", "full"]
    assert kinds[18:] == ["gmu", "cross"] * 7
    assert [kinds.count(k) for k in ("ssm", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    tiny_kinds = phi4flash.layer_kinds(phi4flash.phi4flash_tiny())
    assert tiny_kinds == ["ssm", "window", "ssm", "window", "ssm", "full",
                          "gmu", "cross"]      # a period of every kind


def test_forward_matches_the_reference(tiny):
    params, cfg = tiny
    toks = np.stack([_prompt(40, 1), _prompt(40, 2)])
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(
            lambda p, t: phi4flash.forward(p, t, cfg))(params,
                                                       jnp.asarray(toks)))
    for b in range(2):
        want = _ref(params, cfg, toks[b])
        assert np.abs(got[b] - want).max() <= 1e-4    # the issue's limit
        assert np.abs(got[b] - want).max() <= TOL     # and what it reads
    assert got.std() > 0.1


def test_the_benchmarks_copy_of_the_reference_is_the_same_file():
    with open(os.path.join(ROOT, "paddle_tpu", "testing",
                           "reference_phi4flash.py")) as f:
        ours = f.read()
    with open(os.path.join(ROOT, "benchmark", "lib",
                           "reference_phi4flash.py")) as f:
        assert f.read() == ours


def test_layer_at_a_time_is_the_same_forward(tiny):
    params, cfg = tiny
    toks = _prompt(64, 3)
    rows = np.asarray([0, 7, 8, 30, 63], np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.layer_at_a_time(_hp(cfg))(
            params, jnp.asarray(toks), jnp.asarray(rows)))
    assert np.abs(got - _ref(params, cfg, toks)[rows]).max() <= TOL


def test_the_reference_sees_a_shorter_window_and_a_zero_memory(tiny):
    """What the benchmark's controls plant must be visible at all."""
    params, cfg = tiny
    toks = _prompt(40, 4)
    want = _ref(params, cfg, toks)
    short = _ref(params, dataclasses.replace(cfg, sliding_window=4), toks)
    assert np.abs(short[:4] - want[:4]).max() <= TOL   # inside both windows
    assert np.abs(short[8:] - want[8:]).max() > 1e-2
    zero = jax.tree_util.tree_map(lambda x: x, params)
    zero["second"]["gmu"]["w_in"] = jnp.zeros_like(
        params["second"]["gmu"]["w_in"])
    assert np.abs(_ref(zero, cfg, toks) - want).max() > 1e-2


# --------------------------------------------------------------------------
# through the paged engine: the window's wrap, reuse, preemption, chunks
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n_prompt,n_new", [
    (3, 4),      # ends under the window
    (5, 3),      # ends AT the window: the ring is full, nothing wrapped
    (7, 20),     # a prompt under the window, decode wraps it
    (8, 14),     # a prompt of exactly the window
    (13, 14),    # prefill wraps the ring, decode wraps it again
    (29, 30),    # several times round, past a bucket and many pages
])
def test_prefill_then_decode_match_the_reference_across_the_wrap(
        tiny, n_prompt, n_new):
    eng = _engine(tiny)
    (req,) = _run(eng, [Request(_prompt(n_prompt, n_prompt), n_new)])
    assert len(req.tokens) == n_new
    assert _gap(tiny, req) <= TOL
    assert eng.stats()["decode_compiles"] == 1


def test_a_batch_of_unequal_prompts_and_a_reused_slot(tiny):
    """Five requests on three slots: the later ones run on slots the
    first ones left, and read the logits a fresh engine gives."""
    eng = _engine(tiny)
    reqs = _run(eng, [Request(_prompt(n, 10 + n), 12)
                      for n in (3, 9, 16, 21, 6)])
    assert max(_gap(tiny, r) for r in reqs) <= TOL
    fresh = _run(_engine(tiny), [Request(reqs[-1].prompt.copy(), 12)])[0]
    assert fresh.tokens == reqs[-1].tokens
    assert np.abs(np.stack(fresh.logits)
                  - np.stack(reqs[-1].logits)).max() <= TOL
    st = eng.stats()
    assert st["prefill_cross_rows"] < st["prefill_rows"]
    assert st["state_steps"] == sum(len(r.tokens) - 1 for r in reqs)
    assert st["window_rows_read"] <= W * st["state_steps"]


def test_a_stale_slot_state_would_be_seen(tiny, monkeypatch):
    """The control of the test above: with the prefill's reset of the
    state-space state taken out, a reused slot reads wrong logits."""
    real = phi4flash._ssm_seq

    def leaky(cfg, blk, h, state, tail, count):
        return real(cfg, blk, h, state + 0.5, tail, count)

    monkeypatch.setattr(phi4flash, "_ssm_seq", leaky)
    eng = _engine(tiny)
    (req,) = _run(eng, [Request(_prompt(9, 1), 6)])
    assert _gap(tiny, req) > 1e-2


@pytest.mark.parametrize("rounded", [False, True])
def test_a_slots_state_is_the_references_and_a_rounded_one_is_seen(
        tiny, monkeypatch, rounded):
    """``engine.slot_state``: the ``S`` of a request still running, on a
    slot an earlier request left and past the window, is the float32
    reference's state after the same positions (prefill, then a decode
    step a position; the same float32 sums in another order, 1e-7 read).
    Its control: ``S`` rounded to bf16 after every update, which no
    token of these requests shows, reads a thousand times further."""
    params, cfg = tiny
    if rounded:
        real = phi4flash._ssm_step

        def through_bf16(*args):
            mix, y, state, tail = real(*args)
            # not a pair of ``astype``: a compiler that is allowed excess
            # precision (the chip's) takes that round trip out
            return mix, y, jax.lax.reduce_precision(state, 8, 7), tail

        monkeypatch.setattr(phi4flash, "_ssm_step", through_bf16)
    eng = _engine(tiny, slots=1, capture_logits=False)
    first, req = Request(_prompt(5, 3), 4), Request(_prompt(11, 4), 40)
    with jax.default_matmul_precision("highest"):
        eng.submit(first)
        eng.submit(req)
        while len(req.tokens) < 3 * W:
            eng.step()
        folded, state = eng.slot_state(req.slot)
    history = np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)])
    assert first.done and not req.done and W < folded <= len(history)
    padded = np.zeros((64,), np.int32)
    padded[:folded] = history[:folded]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.states_at_a_time(_hp(cfg))(
            params, jnp.asarray(padded), folded))
    got = state["ssm_state"]
    assert got.shape == want.shape == (3, cfg.d_inner, cfg.mamba_d_state)
    err = (np.sqrt(np.square(got - want).sum((1, 2)))
           / np.sqrt(np.square(want).sum((1, 2))))
    if rounded:
        assert err.min() > 1e-3
    else:
        assert err.max() <= 1e-5


def test_a_preempted_request_emits_the_same_tokens(tiny):
    """A pool too small for two long requests: the newer one is
    preempted, re-admitted from its prompt on whatever slot is free, and
    its state is rebuilt by prefill."""
    reqs = [Request(_prompt(14, 5), 26), Request(_prompt(11, 6), 28)]
    eng = _engine(tiny, num_pages=15, slots=2)
    _run(eng, reqs)
    assert eng.stats()["preemptions"] >= 1
    assert sum(r.preemptions for r in reqs) >= 1
    for r in reqs:
        alone = _run(_engine(tiny, slots=2),
                     [Request(r.prompt.copy(), r.max_new_tokens)])[0]
        assert r.tokens == alone.tokens
        assert _gap(tiny, r) <= TOL


def test_chunked_prefill_over_three_chunks_equals_one_prefill(tiny):
    """A prompt of 21 in chunks of 8 (8 + 8 + 5: the last chunk is
    partly padding, and the ring wraps inside the second)."""
    prompt = _prompt(21, 7)
    chunked = _engine(tiny, prefill_chunk=8)
    (a,) = _run(chunked, [Request(prompt.copy(), 10)])
    (b,) = _run(_engine(tiny), [Request(prompt.copy(), 10)])
    assert chunked.stats()["prefill_chunks"] == 3
    assert a.tokens == b.tokens
    assert np.abs(np.stack(a.logits) - np.stack(b.logits)).max() <= TOL
    assert _gap(tiny, a) <= TOL


def test_decode_between_chunks_leaves_the_chunked_slots_state_alone(tiny):
    """A short request decodes while a long one is between its chunks:
    the decode step computes a row for the chunked slot too, and must
    not move its state."""
    eng = _engine(tiny, prefill_chunk=8)
    reqs = _run(eng, [Request(_prompt(5, 8), 20),
                      Request(_prompt(30, 9), 8)])
    assert max(_gap(tiny, r) for r in reqs) <= TOL


def test_the_prefill_saving_leaves_the_last_rows_logits_unchanged(tiny):
    """Layers 6..7 on the last row only against every layer over every
    row.  The saving is exact in the mathematics; what the two programs
    leave in the pool, the rings and the state is equal bit for bit (the
    stateful layers are the same code), and the last row's logits agree
    to the order of float32 sums (a matmul over 1 row and over 16 rows
    adds the same products in another order: 1e-7 read), not to the
    bit."""
    params, cfg = tiny
    toks = np.zeros((2, 16), np.int32)
    lens = np.asarray([16, 11], np.int32)
    toks[0], toks[1, :11] = _prompt(16, 10), _prompt(11, 11)
    ptab = np.asarray([[1, 2, 3, 4], [5, 6, 7, 0]], np.int32)
    slots = jnp.asarray([0, 2], jnp.int32)

    def prefill(all_rows):
        pools = phi4flash.init_paged_pools(cfg, 10, 4, slots=3)
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda p, pl: phi4flash.prefill_paged(
                p, cfg, pl, jnp.asarray(toks), jnp.asarray(lens),
                jnp.asarray(ptab), slots=slots, all_rows=all_rows))(
                    params, pools)

    last, pools_a, rows_a = prefill(False)
    every, pools_b, rows_b = prefill(True)
    # the rows the program put through the stateful layers and through
    # the stateless ones: what ``prefill_cross_rows`` counts
    assert rows_a.tolist() == [32, 2] and rows_b.tolist() == [32, 32]
    for r in range(2):
        assert np.abs(np.asarray(last[r])
                      - np.asarray(every[r, lens[r] - 1])).max() <= 1e-6
    for a, b in zip(pools_a, pools_b):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # slot 1 was given to no row: its state is still zero
    assert not np.asarray(pools_a[4][:, 1]).any()
    assert np.asarray(pools_a[4][:, 0]).any()


# --------------------------------------------------------------------------
# the decode kernel
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_diff_kernel_interpret_matches_the_lax_fallback(dtype):
    """GQA pairs (8 query heads on 4 key/value heads of 32), lengths that
    end mid-page and mid-group, an idle slot (length 0, table all
    scratch), two layers in the pool."""
    rng = np.random.default_rng(0)
    S, nq, hd, ps, P, maxP = 4, 8, 32, 8, 24, 4
    C = (nq // 2) * hd
    q = jnp.asarray(rng.normal(size=(S, nq, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(2, P, ps, C)), dtype)
    v = jnp.asarray(rng.normal(size=(2, P, ps, C)), dtype)
    table = rng.permutation(np.arange(1, P))[:S * maxP].reshape(S, maxP)
    table[1] = 0                                     # the idle slot
    lens = jnp.asarray([5, 0, 13, 31], jnp.int32)
    table = jnp.asarray(table, jnp.int32)
    for layer in (0, 1):
        want = pda._ref_paged_diff_attention(
            q.astype(jnp.float32), k[layer].astype(jnp.float32),
            v[layer].astype(jnp.float32), table, lens, 0.37)
        got = pda._paged_diff_call(q, (k, v), table, lens, jnp.int32(layer),
                                   jnp.float32(0.37), interpret=True)
        assert got.shape == (S, nq // 2, 2 * hd) and got.dtype == jnp.float32
        assert float(jnp.abs(got - want).max()) <= TOL_KERNEL


def test_diff_attention_is_the_papers_pairing():
    """Query pair p = (2p, 2p + 1) reads K heads (2p', 2p' + 1) and the V
    pair p' = p // 2, written out one head at a time."""
    rng = np.random.default_rng(1)
    n, nq, hd = 6, 8, 4
    q = rng.normal(size=(n, nq, hd))
    k = rng.normal(size=(n, nq // 2, hd))
    v = rng.normal(size=(n, nq // 2, hd))
    mask = np.tril(np.ones((n, n), bool))

    def softmax(x):
        x = np.where(mask, x, -np.inf)
        e = np.exp(x - x.max(-1, keepdims=True))
        return e / e.sum(-1, keepdims=True)

    want = np.zeros((n, nq // 2, 2 * hd))
    for p in range(nq // 2):
        g = p // 2
        a1 = softmax(q[:, 2 * p] @ k[:, 2 * g].T / 2.0)
        a2 = softmax(q[:, 2 * p + 1] @ k[:, 2 * g + 1].T / 2.0)
        both = np.concatenate([v[:, 2 * g], v[:, 2 * g + 1]], -1)
        want[:, p] = (a1 - 0.3 * a2) @ both
    got = pda.diff_attention(*(jnp.asarray(x[None], jnp.float32)
                               for x in (q, k, v)),
                             jnp.asarray(mask)[None], 0.3)[0]
    assert np.abs(np.asarray(got) - want).max() <= 1e-5


# --------------------------------------------------------------------------
# the family's interface and accounting
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name,build", [
    ("the slot engine (ServingEngine)",
     lambda m: ServingEngine(m, slots=2, max_len=32)),
    ("speculative decoding", lambda m: phi4flash.check_serving(
        m[1], engine="SpeculativeServingEngine")),
    ("quant=", lambda m: _engine(m, quant="int8")),
    ("kv_dtype='int8'", lambda m: _engine(m, kv_dtype="int8")),
    ("tp > 1", lambda m: _engine(m, tp=2)),
    ("pp > 1", lambda m: _engine(m, pp=2)),
    ("kv_handoff", lambda m: _engine(m, kv_handoff=True)),
    ("the host KV tier", lambda m: _engine(m, host_tier_mb=1.0)),
])
def test_check_serving_raises_by_name(tiny, name, build):
    with pytest.raises(ValueError, match="phi4flash does not compose with "
                       + name.replace("(", r"\(").replace(")", r"\)")):
        build(tiny)


@pytest.mark.parametrize("kw,name", [
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(tie_word_embeddings=False), "tie_word_embeddings"),
    (dict(mb_per_layer=4), "mb_per_layer"),
    (dict(sliding_window=None), "sliding_window"),
    (dict(num_hidden_layers=10), "num_hidden_layers"),
    (dict(num_key_value_heads=8), "num_key_value_heads"),
    (dict(mlp_bias=True), "mlp_bias"),
])
def test_the_config_refuses_what_is_not_built(kw, name):
    with pytest.raises(ValueError, match=name):
        phi4flash.phi4flash_tiny(**kw)


def test_family_interface_salt_and_counters(tiny):
    _, cfg = tiny
    assert serving.family_of(cfg) is phi4flash
    assert phi4flash.slot_state_arrays(cfg) == 4
    assert "phi4flash" in phi4flash.prefix_salt(cfg)
    assert phi4flash.prefix_salt(cfg) != phi4flash.prefix_salt(
        dataclasses.replace(cfg, num_hidden_layers=12))
    assert gpt.prefix_salt(gpt.gpt_tiny()) == ""
    eng = _engine(tiny)
    assert phi4flash.prefix_salt(cfg) in eng._pager.hash_key
    assert phi4flash.decode_extra_stats(cfg, np.asarray([5, 37])) == {
        "state_steps": 5, "window_rows_read": 37}
    assert phi4flash.prefill_extra_stats(cfg, np.asarray([2048, 4])) == {
        "prefill_rows": 2048, "prefill_cross_rows": 4}
    # a family with no per-slot state is handed nothing new
    assert not hasattr(gpt, "slot_state_arrays")
    gpt_eng = PagedServingEngine((gpt.init_params(
        gpt.gpt_tiny(), jax.random.PRNGKey(0)), gpt.gpt_tiny()),
        slots=2, max_len=32, page_size=8)
    assert gpt_eng._n_slot_state == 0
    assert gpt_eng.stats()["slot_state_bytes"] == 0
    assert len(gpt_eng._page_pools()) == gpt_eng._n_cache


def test_stats_report_the_slot_state_beside_the_pool(tiny):
    _, cfg = tiny
    eng = _engine(tiny)
    st = eng.stats()
    pools = eng._cache_operands()
    assert st["slot_state_bytes"] == sum(int(a.nbytes) for a in pools[2:])
    assert st["kv_bytes_total"] == sum(int(a.nbytes) for a in pools[:2])
    assert eng._pager.stats()["slot_state_bytes"] == st["slot_state_bytes"]
    assert st["kv_bytes_per_position"] == 2 * 4 * 8 * 4     # K, V x 4 x 8, f32
    # 2 window layers x 8 rows x (K + V) x 32 values + 3 state-space
    # layers x (128 x 4 float32 + 3 x 128) values, float32, 3 slots
    assert st["slot_state_bytes"] == 3 * 4 * (2 * 8 * 2 * 32
                                              + 3 * (128 * 4 + 3 * 128))


def test_the_published_widths_hold_the_issues_bytes_and_parameters():
    cfg = phi4flash.Phi4FlashConfig()
    assert phi4flash.kv_bytes_per_position(cfg, 2) == 5120
    slots = 176
    shapes = phi4flash.slot_state_shapes(cfg, slots, 64)
    assert [s for s, _ in shapes] == [
        (8, slots * 8, 64, 1280), (8, slots * 8, 64, 1280),
        (9, slots, 5120, 16), (9, slots, 3, 5120)]
    sizes = [int(np.prod(s)) * jnp.dtype(d).itemsize // slots
             for s, d in shapes]
    assert sizes[0] + sizes[1] == 8 * 512 * 5120 == 20_971_520   # 21.0 MB
    assert sizes[2] + sizes[3] == 9 * (327_680 + 30_720)         # 3.2 MB
    made = jax.eval_shape(lambda: phi4flash.init_paged_pools(
        cfg, 4562, 64, slots=slots))
    assert [tuple(a.shape) for a in made[:2]] == [(1, 4562, 64, 1280)] * 2
    assert [(tuple(a.shape), a.dtype) for a in made[2:]] == [
        (s, d) for s, d in shapes]
    params = jax.eval_shape(lambda k: phi4flash.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    assert n == 3_852_562_944 and round(n / 1e6) == 3853
    assert round(2 * n / 1e9, 2) == 7.71                         # bf16 GB


def test_the_family_is_imported_only_where_it_is_used():
    """``import paddle_tpu`` (and the engine) must not pay for a family
    a process does not serve: set-up time is judged in every cell."""
    import subprocess
    import sys
    code = ("import sys, paddle_tpu, paddle_tpu.inference.serving\n"
            "print([m for m in sys.modules if 'phi4flash' in m "
            "or 'paged_diff' in m])")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-1000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"
