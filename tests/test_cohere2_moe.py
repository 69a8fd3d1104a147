"""The ``cohere2_moe`` family (models/cohere2_moe.py: grouped-query
attention over three rope window layers and one NoPE full layer a
period, the parallel block, an expert share beside averaged shared
experts) held to its plain float32 reference
(testing/reference_cohere2_moe.py) on seeded random weights, at a small
size on the CPU (one period; 16 query heads on 2 K/V heads of 16; a
WINDOW OF 8, so that a request of a few dozen positions wraps every
ring several times, and rotated keys are read out of their order; 4 of
8 routed experts held from the 3rd, top-2; 2 shared experts): LOGITS,
never sampled tokens.  Also the grouped-query decode kernel and the
prefill forward's group factor in interpret mode.

Tolerances, each with its reason.

``TOL`` 5e-6 — float32 everywhere.  Program and reference both run
float32 with matmuls at ``highest`` and differ in the ORDER of the same
float32 sums (grouped einsums against K/V repeated over the group, a
ring's rows in ring order, the expert layer's grouped matmul against a
masked loop): 2e-7..4e-7 read on logits of size 1.2.  5e-6 is ten times
that, and a ten-thousandth of what a planted fault reads (a window that
reads the whole history 0.1..0.4).

``TOL_KERNEL`` 2e-5 — the Pallas kernels in interpret mode against their
lax (XLA) paths: float32 products on both sides, online softmax over
blocks against one softmax over the view.
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
from paddle_tpu.models import cohere2_moe as cm
from paddle_tpu.models import deepseek_v3 as ds
from paddle_tpu.ops.pallas import flash_prefill, paged_attn
from paddle_tpu.testing import reference_cohere2_moe as ref

TOL = 5e-6
TOL_KERNEL = 2e-5
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W = 8       # the tiny model's window


@pytest.fixture(scope="module")
def tiny():
    """Seeded weights with every gain moved off its initial 1, so that
    none of them can be dropped unseen."""
    cfg = cm.cohere2_moe_tiny()
    params = cm.init_params(cfg, jax.random.PRNGKey(0))
    for stack in ("window", "full"):
        params[stack]["ln"] = params[stack]["ln"] + 0.1 * jax.random.normal(
            jax.random.PRNGKey(1), params[stack]["ln"].shape)
    params["norm_f"] = params["norm_f"] + 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), params["norm_f"].shape)
    return params, cfg


_REF_JITS = {}


def _ref(params, cfg, tokens, control=None):
    """Reference logits [n, V] over ``tokens``, padded to one width so
    that calls share a compile: the model is causal, so padding behind a
    row cannot reach it."""
    width = -(-len(tokens) // 64) * 64
    key = (width, control)
    if key not in _REF_JITS:
        hp = dataclasses.asdict(cfg)
        if control == "full_history":
            hp["sliding_window"] = 10 ** 6
        _REF_JITS[key] = jax.jit(lambda p, t: ref.logits(p, t, hp))
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
    cfg = cm.Cohere2MoeConfig()
    assert cfg.layer_types[:8] == [cm.SLIDING] * 3 + [cm.FULL] + \
        [cm.SLIDING] * 3 + [cm.FULL]
    assert (cfg.periods, cfg.q_group, cfg.kv_width) == (8, 16, 1024)
    assert cm.shared_down_scale(cfg) == 0.25
    share = cfg.expert_share
    assert (share.first, share.held, share.routed, share.zero) == (
        0, 128, 128, 0)


def test_forward_matches_the_reference_past_the_window(tiny):
    params, cfg = tiny
    toks = _prompt(3 * W + 5, 1)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(cm.forward(params, jnp.asarray(toks)[None], cfg))[0]
    want = _ref(params, cfg, toks)
    assert np.abs(got - want).max() <= TOL
    # the window is what the reference reads: the whole history differs
    far = _ref(params, cfg, toks, "full_history")
    assert np.abs(far[W:] - want[W:]).max() > 1000 * TOL
    assert np.abs(far[:W] - want[:W]).max() <= TOL


def test_the_benchmarks_copy_of_the_reference_is_the_same_file():
    with open(os.path.join(ROOT, "paddle_tpu", "testing",
                           "reference_cohere2_moe.py")) as a, \
            open(os.path.join(ROOT, "benchmark", "lib",
                              "reference_cohere2_moe.py")) as b:
        assert a.read() == b.read()


def test_run_at_a_time_is_the_same_forward_and_taps_each_layer(tiny):
    params, cfg = tiny
    hp = dataclasses.asdict(cfg)
    toks = _prompt(40, 2)
    rows = jnp.asarray([3, 17, 39], jnp.int32)
    with jax.default_matmul_precision("highest"):
        logits, h, a = ref.run_at_a_time(hp)(params, jnp.asarray(toks), rows)
        eps = cfg.layer_norm_eps
        x = params["embed"][toks]
        blk = ref.layer_of(params, 0)
        h0 = ref.layer_norm(x, blk["ln"], eps)
        a0 = ref.attention(h0, blk, hp, 0)
    assert np.abs(np.asarray(logits) - _ref(params, cfg, toks)[
        np.asarray(rows)]).max() <= TOL
    assert h.shape == (4, 40, cfg.hidden_size)
    assert a.shape == (4, 3, cfg.hidden_size)
    assert np.abs(np.asarray(h[0] - h0)).max() <= TOL
    assert np.abs(np.asarray(a[0] - a0[rows])).max() <= TOL


def test_prefill_then_decode_match_the_reference_across_the_wrap(tiny):
    """Four requests of different lengths share waves and decode steps;
    every one ends past the window, three of them several windows past
    (the rings wrap and hold rotated keys out of their order)."""
    reqs = _run(_engine(tiny), [Request(_prompt(n, 10 + i), m) for i, (n, m)
                                in enumerate(((5, 20), (8, 12), (3, 30),
                                              (7, 9)))])
    for r in reqs:
        assert len(r.prompt) + len(r.tokens) > W
        assert _gap(tiny, r) <= TOL


def test_a_reused_slot_and_unequal_prompts_in_one_wave(tiny):
    """Two slots for five requests: later requests take slots whose
    rings an earlier one left behind, from prompts of several buckets."""
    reqs = _run(_engine(tiny, slots=2), [Request(_prompt(n, 20 + i), m)
                                         for i, (n, m) in enumerate(
                                             ((6, 14), (16, 7), (11, 20),
                                              (2, 25), (30, 6)))])
    assert max(_gap(tiny, r) for r in reqs) <= TOL


def test_chunked_prefill_over_three_chunks_equals_one_prefill(tiny):
    """A prompt of 21 in chunks of 8 (8 + 8 + 5: the last chunk partly
    padding, the rings wrapping inside the second)."""
    prompt = _prompt(21, 7)
    chunked = _engine(tiny, prefill_chunk=8)
    (a,) = _run(chunked, [Request(prompt.copy(), 10)])
    (b,) = _run(_engine(tiny), [Request(prompt.copy(), 10)])
    assert chunked.stats()["prefill_chunks"] == 3
    assert a.tokens == b.tokens
    assert np.abs(np.stack(a.logits) - np.stack(b.logits)).max() <= TOL
    assert _gap(tiny, a) <= TOL


def test_decode_counts_the_rows_each_kind_of_layer_read(tiny):
    """``attn_window_rows`` / ``attn_full_rows``: an active slot at
    position ``p`` reads ``min(p + 1, W)`` rows in each of the 3 window
    layers and ``p + 1`` in the full layer."""
    params, cfg = tiny
    eng = _engine(tiny)
    req = _run(eng, [Request(_prompt(5, 3), 12)])[0]
    steps = range(5, 5 + len(req.tokens) - 1)       # the decode positions
    st = eng.stats()
    assert st["attn_full_rows"] == sum(p + 1 for p in steps)
    assert st["attn_window_rows"] == 3 * sum(min(p + 1, W) for p in steps)
    assert st["moe_assignments"] + st["moe_remote_assignments"] == (
        len(steps) * cfg.num_hidden_layers * cfg.num_experts_per_tok)


def test_a_prefill_bucket_longer_than_the_window_stays_off_the_flash_path(
        tiny, monkeypatch):
    """The flash forward masks causality, not the window: a wave longer
    than the window on that path is refused by name."""
    params, cfg = tiny
    monkeypatch.setattr(cm, "prefill_uses_flash", lambda c, b, s: True)
    pools = cm.init_paged_pools(cfg, 8, 4, slots=1)
    with pytest.raises(ValueError, match="longer than the window"):
        cm.prefill_paged(params, cfg, pools, jnp.zeros((1, 16), jnp.int32),
                         jnp.asarray([16]), jnp.zeros((1, 4), jnp.int32),
                         slots=jnp.asarray([0]))


def test_the_attention_sublayer_check_is_the_references_past_the_window(
        tiny):
    """``attention_sublayer`` — the decode kernel's path over a window's
    ring and over the pool, at rows past the window — against the
    reference's sublayer fed the same normed input; and against a
    reference that reads the whole history, far off in window layers."""
    params, cfg = tiny
    hp = dataclasses.asdict(cfg)
    toks = _prompt(32, 4)
    rows = jnp.asarray([3, 9, 20, 31], jnp.int32)
    with jax.default_matmul_precision("highest"):
        _, h, a = ref.run_at_a_time(hp)(params, jnp.asarray(toks), rows)
        _, _, far = ref.run_at_a_time(hp, "full_history")(
            params, jnp.asarray(toks), rows)
        for layer in range(4):
            got = cm.attention_sublayer(params, cfg, h[layer], layer, rows, 4)
            assert np.abs(np.asarray(got - a[layer])).max() <= TOL, layer
    assert np.abs(np.asarray(far[0, 1:] - a[0, 1:])).max() > 1000 * TOL
    assert np.abs(np.asarray(far[0, 0] - a[0, 0])).max() <= TOL


# --------------------------------------------------------------------------
# the expert layer
# --------------------------------------------------------------------------

def test_the_expert_sublayer_check_is_the_references(tiny):
    """``expert_sublayer`` — the walk's expert layer over the share and
    the shared experts, and its router's choice — against the
    reference's ``expert_layer`` in every layer; the reference with its
    expert weights rounded to int8 far off."""
    params, cfg = tiny
    hp = dataclasses.asdict(cfg)
    h = jax.random.normal(jax.random.PRNGKey(9), (24, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        for layer in range(4):
            got, chosen = cm.expert_sublayer(params, cfg, h, layer)
            name, i = ref.stack_of(layer)
            stack = {k: params[name][k] for k in ref.EXPERT_LEAVES}
            want, want_c = ref.expert_layer(hp)(h, stack, jnp.int32(i))
            off = np.abs(np.asarray(got - want)).max()
            assert off <= TOL, layer
            assert (np.sort(np.asarray(chosen), -1)
                    == np.sort(np.asarray(want_c), -1)).all()
            far, _ = ref.expert_layer(hp, "int8")(h, stack, jnp.int32(i))
            assert np.abs(np.asarray(far - want)).max() > 100 * off


def test_slot_state_is_each_window_layers_ring_of_the_slot(tiny):
    """``slot_state_of``: a slot's strip of ring pages, row ``j`` of
    each window layer's ring [3, W, C]."""
    params, cfg = tiny
    pools = cm.init_paged_pools(cfg, 8, 4, slots=3)
    ring = jnp.arange(np.prod(pools[2].shape), dtype=jnp.float32).reshape(
        pools[2].shape)
    got = cm.slot_state_of(cfg, (pools[0], pools[1], ring, -ring), 1)
    n = W // ring.shape[2]
    want = np.asarray(ring)[:, n:2 * n].reshape(3, W, -1)
    assert (np.asarray(got["ring_k"]) == want).all()
    assert (np.asarray(got["ring_v"]) == -want).all()



def test_eight_shares_and_the_averaged_shared_term_are_the_uncut_layer():
    """Eight chips' shares of 16 routed experts (2 each) and the shared
    experts' mean added once equal the layer that holds all 16 — the
    program's and the reference's."""
    whole = cm.cohere2_moe_tiny(num_experts=16, num_experts_total=16,
                                first_held_expert=0)
    params = cm.init_params(whole, jax.random.PRNGKey(5))
    blk = {k: v[0] for k, v in params["window"].items()}
    h = jax.random.normal(jax.random.PRNGKey(6), (24, whole.hidden_size))
    with jax.default_matmul_precision("highest"):
        uncut, counts = ds.moe_ffn(whole, h, {k: blk[k] for k in (
            "wr", "eg", "eu", "ed")})
        parts = []
        for i in range(8):
            cfg = dataclasses.replace(whole, num_experts=2,
                                      first_held_expert=2 * i)
            part, c = ds.moe_ffn(cfg, h, dict(
                wr=blk["wr"], **{k: blk[k][2 * i:2 * i + 2]
                                 for k in ds.EXPERT_STACKS}))
            parts.append(part)
            assert int(c[:2].sum() + c[3]) == 24 * whole.num_experts_per_tok
        shared = ds._gated_mlp(h, blk["sg"], blk["su"], blk["sd"], h.dtype)
        want = ref.moe(h, blk, dataclasses.asdict(whole))
    got = sum(parts) + shared
    assert np.abs(np.asarray(got - (uncut + shared))).max() <= TOL
    assert np.abs(np.asarray(got - want)).max() <= TOL
    # the shared experts' term is their MEAN: the down-projections hold
    # its 1/2, and the sum of the published experts is twice it here
    n_s = whole.num_shared_experts
    summed = ds._gated_mlp(h, blk["sg"], blk["su"], blk["sd"] * n_s, h.dtype)
    assert np.abs(np.asarray(summed - n_s * shared)).max() <= TOL
    hp_sum = dict(dataclasses.asdict(whole),
                  shared_expert_combination_strategy="sum")
    summed_ref = ref.moe(h, dict(blk, sd=blk["sd"] * n_s), hp_sum)
    assert np.abs(np.asarray(summed_ref - want - (n_s - 1) * shared)
                  ).max() <= TOL


def test_routing_takes_no_bias_and_renormalises_the_chosen():
    cfg = cm.cohere2_moe_tiny()
    h = jax.random.normal(jax.random.PRNGKey(7), (5, cfg.hidden_size))
    wr = jax.random.normal(jax.random.PRNGKey(8), (cfg.hidden_size, 8))
    with jax.default_matmul_precision("highest"):
        chosen, w = ds.route(cfg, h, wr, None)
        want_c, want_w = ref.route(h, wr, dataclasses.asdict(cfg))
    assert (np.asarray(chosen) == np.asarray(want_c)).all()
    assert np.allclose(np.asarray(w).sum(-1), 1.0)
    assert np.abs(np.asarray(w - want_w)).max() <= TOL


# --------------------------------------------------------------------------
# the kernels, interpret mode against the lax path
# --------------------------------------------------------------------------

@pytest.mark.parametrize("group", [1, 4, 16])
def test_grouped_query_decode_kernel_interpret_matches_the_lax_path(group):
    nkv, hd, ps, S, P, maxP = 2, 128, 8, 3, 20, 6
    nq = nkv * group
    keys = jax.random.split(jax.random.PRNGKey(group), 4)
    q = jax.random.normal(keys[0], (S, 1, nq, hd))
    kp = jax.random.normal(keys[1], (2, P, ps, nkv * hd))
    vp = jax.random.normal(keys[2], (2, P, ps, nkv * hd))
    pt = jax.random.randint(keys[3], (S, maxP), 1, P)
    lens = jnp.asarray([0, 13, maxP * ps - 1], jnp.int32)
    got = paged_attn._paged_call(q, (kp, vp), (), pt, lens, jnp.int32(1),
                                 interpret=True)
    with jax.default_matmul_precision("highest"):
        want = paged_attn._ref_paged_attention(q, kp[1], vp[1], pt, lens)
    assert np.abs(np.asarray(got - want)).max() <= TOL_KERNEL


@pytest.mark.parametrize("group", [1, 4])
def test_prefill_forward_group_factor_interpret_matches_xla(group):
    """``flash_prefill``'s group factor against the family's XLA
    attention over the causal mask (rows at or past the length are
    padding, not compared)."""
    cfg = cm.cohere2_moe_tiny(num_attention_heads=2 * group,
                              num_key_value_heads=2, head_dim=128)
    b, s = 2, 256
    keys = jax.random.split(jax.random.PRNGKey(group + 10), 3)
    q = jax.random.normal(keys[0], (b, s, cfg.num_attention_heads, 128))
    k = jax.random.normal(keys[1], (b, s, 2, 128))
    v = jax.random.normal(keys[2], (b, s, 2, 128))
    lens = jnp.asarray([s, 77], jnp.int32)
    got = flash_prefill._flash_prefill_tpu(
        q.reshape(b, s, -1), k.reshape(b, s, -1),
        jnp.swapaxes(v.reshape(b, s, -1), 1, 2), lens,
        heads=cfg.num_attention_heads, scale=128 ** -0.5, block_q=128,
        block_k=128, interpret=True, group=group)
    with jax.default_matmul_precision("highest"):
        want = cm._attend(cfg, q, k, v, ds._causal(s, s))
    got = np.asarray(jnp.swapaxes(got, 1, 2))
    want = np.asarray(want)
    assert np.abs(got[0] - want[0]).max() <= TOL_KERNEL
    assert np.abs(got[1, :77] - want[1, :77]).max() <= TOL_KERNEL


# --------------------------------------------------------------------------
# the family interface
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name,build", [
    ("the slot engine (ServingEngine)",
     lambda m: ServingEngine(m, slots=2, max_len=32)),
    ("speculative decoding", lambda m: cm.check_serving(
        m[1], engine="SpeculativeServingEngine")),
    ("quant=", lambda m: _engine(m, quant="int8")),
    ("kv_dtype='int8'", lambda m: _engine(m, kv_dtype="int8")),
    ("tp > 1", lambda m: _engine(m, tp=2)),
    ("pp > 1", lambda m: _engine(m, pp=2)),
    ("kv_handoff", lambda m: _engine(m, kv_handoff=True)),
    ("the host KV tier", lambda m: _engine(m, host_tier_mb=1.0)),
])
def test_check_serving_raises_by_name(tiny, name, build):
    with pytest.raises(ValueError, match="cohere2_moe does not compose with "
                       + name.replace("(", r"\(").replace(")", r"\)")):
        build(tiny)


@pytest.mark.parametrize("kw,name", [
    (dict(layer_switch=2), "layer_types"),
    (dict(num_hidden_layers=6), "layer_types"),
    (dict(first_k_dense_replace=1), "first_k_dense_replace"),
    (dict(use_parallel_block=False), "use_parallel_block"),
    (dict(use_qk_norm=True), "use_qk_norm"),
    (dict(tie_word_embeddings=False), "tie_word_embeddings"),
    (dict(expert_selection_fn="softmax"), "expert_selection_fn"),
    (dict(rotary_pct=0.5), "rotary_pct"),
    (dict(shared_expert_combination_strategy="gate"),
     "shared_expert_combination_strategy"),
    (dict(num_experts=9), "expert share"),
])
def test_the_config_refuses_what_is_not_built(kw, name):
    with pytest.raises(ValueError, match=name):
        cm.cohere2_moe_tiny(**kw)


def test_family_interface_salt_counters_and_bytes(tiny):
    _, cfg = tiny
    assert serving.family_of(cfg) is cm
    assert cm.slot_state_arrays(cfg) == 2
    assert "cohere2_moe" in cm.prefix_salt(cfg)
    assert cm.prefix_salt(cfg) != cm.prefix_salt(
        dataclasses.replace(cfg, first_held_expert=0))
    width = cfg.expert_share.count_width
    flat = np.zeros((4 * width + 2,), np.int32)
    flat[-2:] = (30, 12)
    out = cm.decode_extra_stats(cfg, flat)
    assert (out["attn_window_rows"], out["attn_full_rows"]) == (30, 12)
    assert "moe_remote_assignments" in out
    eng = _engine(tiny)
    st = eng.stats()
    pools = eng._cache_operands()
    assert st["slot_state_bytes"] == sum(int(a.nbytes) for a in pools[2:])
    # one full layer's K and V: 2 x 2 heads x 16, float32
    assert st["kv_bytes_per_position"] == 2 * 32 * 4
    # 3 window layers x 8 rows x (K + V) x 32 values, float32, 3 slots
    assert st["slot_state_bytes"] == 3 * 3 * 8 * 2 * 32 * 4


def test_the_published_widths_hold_the_reckoned_bytes():
    cfg = cm.Cohere2MoeConfig(num_hidden_layers=4, num_experts=16,
                              num_experts_total=128, vocab_size=32768)
    assert cm.kv_bytes_per_position(cfg, 2) == 4096
    shapes = cm.slot_state_shapes(cfg, 64, 64)
    assert [s for s, _ in shapes] == [(3, 64 * 64, 64, 1024)] * 2
    per_slot = 2 * 3 * 4096 * 1024 * 2
    assert per_slot == 48 << 20
    params = jax.eval_shape(lambda k: cm.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    # 4 x 1,149.77M a layer + the tied table, gains included
    assert round(n / 1e9, 3) == 4.733


def test_the_family_is_imported_only_where_it_is_used():
    """``import paddle_tpu`` (and the engine) must not pay for a family
    a process does not serve."""
    import subprocess
    import sys
    code = ("import sys, paddle_tpu, paddle_tpu.inference.serving\n"
            "print([m for m in sys.modules if 'cohere2' in m])")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-1000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"
