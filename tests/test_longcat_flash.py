"""The ``longcat_flash`` family (models/longcat_flash.py) held to its
plain float32 reference (testing/reference_longcat_flash.py) on seeded
random weights, at a small size on the CPU: LOGITS, never sampled
tokens; and the expert layer it shares with ``deepseek_v3``
(``deepseek_v3.moe_ffn``), told which experts it holds.

Tolerances.  Program and reference both run float32 with matmuls at
``highest``; they differ in the ORDER of the same float32 sums
(absorbed products, grouped matmuls, online softmax), which reads
about 1e-7 on logits of deviation 0.2.  ``TOL`` = 5e-6 is far above
that and far below what bf16 compute does to the same logits (asserted
below): a lower precision where float32 is stated fails every
comparison here.
"""
import dataclasses
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference import serving
from paddle_tpu.inference.serving import PagedServingEngine, ServingEngine
from paddle_tpu.models import deepseek_v3 as ds
from paddle_tpu.models import longcat_flash as lc
from paddle_tpu.ops.pallas import grouped_matmul
from paddle_tpu.testing import reference_longcat_flash as ref

TOL = 5e-6
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny():
    cfg = lc.longcat_flash_tiny()
    return lc.init_params(cfg, jax.random.PRNGKey(0)), cfg


def _hp(cfg):
    return dataclasses.asdict(cfg)


def _grouped_kernel_on(monkeypatch):
    """The chip's branch of the experts' grouped matmul, interpreted."""
    monkeypatch.setattr(grouped_matmul, "pallas_enabled", lambda: True)
    monkeypatch.setattr(grouped_matmul, "_grouped_tpu", functools.partial(
        grouped_matmul._grouped_tpu, interpret=True))


@pytest.fixture(params=["ragged_dot", "kernel"])
def moe_path(request, monkeypatch):
    if request.param == "kernel":
        _grouped_kernel_on(monkeypatch)
    return request.param


_REF_JITS = {}


def _ref_logits(params, cfg, tokens, module=ref):
    """The reference over ``tokens``, padded to one width (causal:
    padding behind a row cannot reach it)."""
    width = -(-len(tokens) // 64) * 64
    key = (module.__name__, width, cfg.first_held_expert)
    if key not in _REF_JITS:
        hp = _hp(cfg)
        _REF_JITS[key] = jax.jit(lambda p, t: module.logits(p, t, hp))
    padded = np.zeros((width,), np.int32)
    padded[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        return np.asarray(_REF_JITS[key](params, jnp.asarray(padded)))[
            :len(tokens)]


def _engine(model, **kw):
    args = dict(slots=3, max_len=64, page_size=8, num_pages=25,
                seq_buckets=(16, 32), batch_buckets=(1, 2),
                capture_logits=True)
    args.update(kw)
    return PagedServingEngine(model, **args)


def _assert_request_matches(params, cfg, req):
    """Every generated position's logits against the reference's full
    forward over prompt + generated (teacher-forced on the engine's own
    tokens)."""
    history = np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)])
    want = _ref_logits(params, cfg, history)
    n = len(req.prompt)
    got = np.stack(req.logits)
    assert got.shape[0] == len(req.tokens)
    err = np.abs(got - want[n - 1:n - 1 + len(req.tokens)]).max()
    assert err < TOL, (req.id, err)


def _tokens(seed, n, vocab=256):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


# --------------------------------------------------------------------------
# the forward pass and the reference
# --------------------------------------------------------------------------

class TestForward:
    def test_full_forward_matches_reference(self, tiny, moe_path):
        params, cfg = tiny
        toks = np.stack([_tokens(0, 40), _tokens(1, 40)])
        with jax.default_matmul_precision("highest"):
            got = np.asarray(lc.forward(params, jnp.asarray(toks), cfg))
        for row, t in zip(got, toks):
            assert np.abs(row - _ref_logits(params, cfg, t)).max() < TOL

    def test_bf16_compute_fails_the_tolerance(self, tiny):
        params, cfg = tiny
        low = dataclasses.replace(cfg, dtype="bfloat16")
        toks = _tokens(0, 40)
        got = np.asarray(lc.forward(params, jnp.asarray(toks[None]), low))[0]
        assert np.abs(got - _ref_logits(params, cfg, toks)).max() > 100 * TOL

    def test_benchmark_copy_of_the_reference_gives_the_same_numbers(
            self, tiny):
        params, cfg = tiny
        spec = importlib.util.spec_from_file_location(
            "bench_reference_longcat_flash", os.path.join(
                ROOT, "benchmark", "lib", "reference_longcat_flash.py"))
        copy = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(copy)
        toks = _tokens(3, 24)
        a = _ref_logits(params, cfg, toks)
        b = _ref_logits(params, cfg, toks, module=copy)
        assert np.array_equal(a, b)
        with open(ref.__file__) as f, open(copy.__file__) as g:
            assert f.read() == g.read()

    def test_layer_at_a_time_upcasts_bf16_weights_to_the_same_rows(
            self, tiny, monkeypatch):
        """What the benchmark's check calls: bf16 weights in, float32
        arithmetic inside one sublayer at a time, rows only — with the
        attention's query blocks smaller than the sequence."""
        params, cfg = tiny
        monkeypatch.setattr(ref, "Q_BLOCK", 7)
        low = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)
        bf16 = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), low)
        toks = _tokens(4, 24)
        rows = jnp.asarray([0, 11, 23])
        with jax.default_matmul_precision("highest"):
            got = np.asarray(ref.layer_at_a_time(_hp(cfg))(
                bf16, jnp.asarray(toks), rows))
            whole = np.asarray(ref.logits(low, jnp.asarray(toks), _hp(cfg)))
        assert got.dtype == np.float32
        assert np.abs(got - whole[np.asarray(rows)]).max() < TOL

    @pytest.mark.parametrize("control", ref.CONTROLS)
    def test_each_control_moves_the_rows(self, tiny, control):
        """The planted controls of the benchmark's comparison: int8
        weights, a held expert left out, the identity term left out —
        each moves the logits far past the tolerance."""
        params, cfg = tiny
        toks = _tokens(5, 24)
        rows = jnp.arange(24)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref.layer_at_a_time(_hp(cfg))(
                params, jnp.asarray(toks), rows))
            got = np.asarray(ref.layer_at_a_time(_hp(cfg), control)(
                params, jnp.asarray(toks), rows))
        assert np.abs(got - want).max() > 100 * TOL
        with pytest.raises(ValueError, match="no such control"):
            ref.layer_at_a_time(_hp(cfg), "fp8")


# --------------------------------------------------------------------------
# the expert layer, told which experts it holds
# --------------------------------------------------------------------------

def _h0(cfg, n=24, seed=7):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, cfg.hidden_size))
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True))


def _moe_blk(params, li=0, first=None, held=None):
    """Layer ``li``'s expert leaves; of the experts ``[first, first +
    held)`` of the stack where given."""
    blk = {k: v[li] for k, v in params["layers"]["moe"].items()}
    if first is not None:
        for k in ds.EXPERT_STACKS:
            blk[k] = blk[k][first:first + held]
    return blk


class TestExpertLayer:
    def test_the_shares_add_up_to_the_uncut_layer(self, moe_path):
        """Each of three chips holds 4 of the 12 routed experts: their
        held parts, with the identity term every chip computes alike
        counted ONCE, are the reference's whole layer (every expert
        held), on either path of the routed products."""
        whole = lc.longcat_flash_tiny(n_routed_experts=12,
                                      first_held_expert=0)
        params = lc.init_params(whole, jax.random.PRNGKey(1))
        h0 = _h0(whole)
        blk = _moe_blk(params)
        with jax.default_matmul_precision("highest"):
            want = ref.moe(h0, blk, _hp(whole))
            identity = ref.moe(h0, _moe_blk(params, first=0, held=0),
                               _hp(whole))
            parts = []
            for first in (0, 4, 8):
                share = dataclasses.replace(whole, n_routed_experts=4,
                                            first_held_expert=first)
                y, counts = ds.moe_ffn(share, h0, _moe_blk(
                    params, first=first, held=4))
                parts.append(np.asarray(y) - np.asarray(identity))
                # every assignment is held, identity or remote (the two
                # last entries are the combine's rows)
                assert int(counts[:-2].sum()) == h0.shape[0] * whole.moe_topk
            got = sum(parts) + np.asarray(identity)
        assert np.abs(got - np.asarray(want)).max() < TOL
        assert np.abs(np.asarray(identity)).max() > 0.01

    def test_the_program_matches_the_reference_on_its_share(self, tiny,
                                                             moe_path):
        params, cfg = tiny
        h0 = _h0(cfg)
        with jax.default_matmul_precision("highest"):
            y, counts = ds.moe_ffn(cfg, h0, _moe_blk(params, li=1))
            want = ref.moe(h0, _moe_blk(params, li=1), _hp(cfg))
        assert np.abs(np.asarray(y) - np.asarray(want)).max() < TOL
        chosen, _ = ref.route(h0, *(_moe_blk(params, li=1)[k]
                                    for k in ("wr", "b")), _hp(cfg))
        chosen = np.asarray(chosen)
        first, held = cfg.first_held_expert, cfg.n_routed_experts
        loads = [(chosen == first + i).sum() for i in range(held)]
        zero = (chosen >= cfg.n_routed_experts_total).sum()
        tm = grouped_matmul.window_rows(chosen.size, 4)
        assert list(np.asarray(counts)) == loads + [
            zero, chosen.size - sum(loads) - zero,
            -(-sum(loads) // tm) * tm, -(-chosen.size // tm) * tm]

    def test_a_router_forced_onto_identity_experts_gives_the_weighted_input(
            self, tiny, moe_path):
        """Router weights 0 make every softmax score 1 / width; a bias on
        the identity columns alone chooses them: the layer is (sum of
        the k weights) * h0 = k * scale / width * h0, no expert runs."""
        params, cfg = tiny
        h0 = _h0(cfg)
        width = cfg.expert_share.width
        blk = _moe_blk(params)
        blk["wr"] = jnp.zeros_like(blk["wr"])
        blk["b"] = jnp.where(jnp.arange(width) >= cfg.n_routed_experts_total,
                             1e-3, 0.0)
        y, counts = ds.moe_ffn(cfg, h0, blk)
        k = cfg.moe_topk
        want = k * cfg.routed_scaling_factor / width * np.asarray(h0)
        assert np.abs(np.asarray(y) - want).max() < 1e-5
        # no held row: the combine walks no window
        assert list(np.asarray(counts)) == [0] * cfg.n_routed_experts + [
            h0.shape[0] * k, 0, 0, h0.shape[0] * k]

    def test_the_expert_sublayer_alone_against_the_reference(self, tiny,
                                                             moe_path):
        """What the benchmark's sixth check compares: the program's
        expert sublayer of a layer (the stacks whole, the layer's index)
        and the reference's, the reference's controls far off it."""
        params, cfg = tiny
        h0 = _h0(cfg)
        blk = _moe_blk(params, li=1)
        got = np.asarray(lc.expert_layer(params, cfg, h0, 1))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref.expert_layer(_hp(cfg))(h0, blk))
            assert np.abs(got - want).max() < TOL
            for control in ("drop_held_expert", "drop_identity"):
                off = np.asarray(ref.expert_layer(_hp(cfg), control)(h0, blk))
                assert np.abs(got - off).max() > 100 * TOL, control

    def test_masked_rows_count_nothing(self, tiny):
        params, cfg = tiny
        h0 = _h0(cfg)
        mask = jnp.arange(h0.shape[0]) < 10
        _, c_all = ds.moe_ffn(cfg, h0, _moe_blk(params))
        _, c_some = ds.moe_ffn(cfg, h0[:10], _moe_blk(params))
        y, c = ds.moe_ffn(cfg, h0, _moe_blk(params), row_mask=mask)
        # (the combine's two counts are over every row)
        assert np.array_equal(np.asarray(c)[:-2], np.asarray(c_some)[:-2])
        assert np.array_equal(np.asarray(c)[-2:], np.asarray(c_all)[-2:])
        assert int(c_all[:-2].sum()) == h0.shape[0] * cfg.moe_topk

    def test_no_share_that_is_not_one(self):
        for bad in (dict(first_held_expert=10), dict(n_routed_experts=0),
                    dict(first_held_expert=-1)):
            with pytest.raises(ValueError, match="no such expert share"):
                lc.longcat_flash_tiny(**bad)


def _moe_ffn_as_pr38(cfg, h2, blk, row_mask=None):
    """``deepseek_v3.moe_ffn`` as it stood before the share (PR 38),
    frozen: the share (0, E) with no identity expert must still give
    this, bit for bit."""
    gmm = grouped_matmul
    cd = jnp.dtype(cfg.dtype)
    T, H = h2.shape
    K, E = cfg.num_experts_per_tok, cfg.n_routed_experts
    chosen, w = ds.route(cfg, h2, blk["wr"], blk["b"])
    flat = chosen.reshape(-1)
    sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1)
    if row_mask is None:
        counts = sizes
    else:
        counts = jnp.zeros((E,), jnp.int32).at[flat].add(
            jnp.repeat(row_mask.astype(jnp.int32), K))
    order = jnp.argsort(flat, stable=True)
    xs = h2[order // K]
    li = blk.get("li")
    mid = gmm.grouped_gate_up(xs, sizes, blk["eg"].astype(cd),
                              blk["eu"].astype(cd), li)
    y = gmm.grouped_matmul(mid, sizes, blk["ed"].astype(cd), li)
    y = y[jnp.argsort(order)].reshape(T, K, H)
    y = jnp.einsum("tkh,tk->th", y, w).astype(cd)
    return y + ds._gated_mlp(h2, blk["sg"], blk["su"], blk["sd"], cd), counts


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kanana2s_expert_layer_is_bit_identical_through_the_shared_function(
        moe_path, dtype):
    """The deepseek_v3 family is the share (0, E) with no identity
    expert: through the shared layer its output and counts are the ones
    it gave before the share was built, to the last bit."""
    cfg = ds.deepseek_v3_tiny(dtype=dtype, param_dtype=dtype)
    assert cfg.expert_share == ds.ExpertShare(0, 8, 8, 0)
    params = ds.init_params(cfg, jax.random.PRNGKey(2))
    blk = {k: v if k in ds.EXPERT_STACKS else v[1]
           for k, v in params["moe"].items()}
    blk["li"] = jnp.int32(1)
    h2 = _h0(cfg).astype(dtype)
    mask = jnp.arange(h2.shape[0]) % 3 > 0
    for rm in (None, mask):
        y, c = jax.jit(lambda h, b: ds.moe_ffn(cfg, h, b, rm))(h2, blk)
        y0, c0 = jax.jit(lambda h, b: _moe_ffn_as_pr38(cfg, h, b, rm))(
            h2, blk)
        assert np.array_equal(np.asarray(y), np.asarray(y0))
        # the held experts' loads, then no identity and no remote one
        assert np.array_equal(np.asarray(c), np.append(np.asarray(c0),
                                                       [0, 0]))


# --------------------------------------------------------------------------
# a partial share combines only the windows its held rows reach
# --------------------------------------------------------------------------

def _routed_to(cfg, per_token, seed=0):
    """(h2 [T, H], blk): layer 0's experts behind a router that gives
    token ``t`` exactly ``per_token[t]`` held assignments of its k.
    Feature ``(n, r)`` of a row scores n held columns and k - n others
    (turned by r, so tokens spread over the experts); the rest of the
    row is noise the router does not see and the experts do."""
    share, K, H = cfg.expert_share, cfg.moe_topk, cfg.hidden_size
    held = np.arange(share.first, share.first + share.held)
    other = np.setdiff1d(np.arange(share.width), held)
    feats = (K + 1) * share.held
    wr = np.zeros((H, share.width), np.float32)
    for n in range(K + 1):
        for r in range(share.held):
            d = n * share.held + r
            wr[d, held[(r + np.arange(n)) % len(held)]] = 8.0
            wr[d, other[(r + np.arange(K - n)) % len(other)]] = 8.0
    rng = np.random.default_rng(seed)
    h2 = np.zeros((len(per_token), H), np.float32)
    h2[:, feats:] = rng.standard_normal((len(per_token), H - feats))
    h2[np.arange(len(per_token)),
       np.asarray(per_token) * share.held
       + rng.integers(0, share.held, len(per_token))] = 1.0
    params = lc.init_params(cfg, jax.random.PRNGKey(seed))
    blk = dict(_moe_blk(params), wr=jnp.asarray(wr),
               b=jnp.zeros((share.width,), jnp.float32))
    return jnp.asarray(h2), blk


def _per_token(T, K, n_held, seed=0):
    """Held assignments a token so that the T tokens hold ``n_held``."""
    per = np.zeros((T,), np.int64)
    for t in np.random.default_rng(seed).permutation(T):
        per[t] = min(K, n_held - per.sum())
    assert per.sum() == n_held
    return per


def _whole_combine(cfg, h2, blk):
    """The layer as a whole share combines, over the partial share's own
    grouped-matmul output: every sorted row unsorted and summed with
    the held weights, plus the identity term — float32, before the
    cast."""
    share, K = cfg.expert_share, cfg.moe_topk
    T, H = h2.shape
    chosen, w = ds.route(cfg, h2, blk["wr"], blk["b"])
    local = chosen.reshape(-1) - share.first
    held = (local >= 0) & (local < share.held)
    flat = jnp.where(held, local, share.held)
    sizes = jnp.zeros((share.held,), jnp.int32).at[flat].add(1, mode="drop")
    order = jnp.argsort(flat, stable=True)
    tm = grouped_matmul.window_rows(T * K, h2.dtype.itemsize)
    R = -(-T * K // tm) * tm
    xs = h2[jnp.pad(order // K, (0, R - T * K))]
    mid = grouped_matmul.grouped_gate_up(xs, sizes, blk["eg"], blk["eu"])
    y = grouped_matmul.grouped_matmul(mid, sizes, blk["ed"])[:T * K]
    y = y[jnp.argsort(order)].reshape(T, K, H)
    y = jnp.einsum("tkh,tk->th", y, jnp.where(held.reshape(T, K), w, 0.0))
    zero = chosen >= share.routed
    return y + jnp.sum(jnp.where(zero, w, 0.0), -1)[:, None] * h2


@pytest.mark.parametrize("masked", [False, True], ids=["all-rows", "masked"])
@pytest.mark.parametrize("n_held", [0, 127, 128, 129, "all"])
@pytest.mark.parametrize("T", [100, 2 * 128],
                         ids=["decode-100", "prefill-2x128"])
def test_a_partial_share_combines_its_live_windows_as_the_whole_output(
        tiny, moe_path, T, n_held, masked):
    """The partial share's layer (only the windows its held rows reach,
    added into their tokens) against the whole-share combine of the same
    grouped-matmul output: equal to float32 rounding, with no held row,
    at a window's edge and with every assignment held; the combine's
    counts are its windows and the output's rows."""
    _, cfg = tiny
    K = cfg.moe_topk
    n = T * K if n_held == "all" else n_held
    h2, blk = _routed_to(cfg, _per_token(T, K, n))
    mask = (jnp.arange(T) % 3 > 0) if masked else None
    y, counts = jax.jit(lambda h, b: ds.moe_ffn(cfg, h, b, mask))(h2, blk)
    want = np.asarray(_whole_combine(cfg, h2, blk))
    scale = np.abs(want).max()
    assert np.abs(np.asarray(y) - want).max() <= 1e-6 * scale
    tm = grouped_matmul.window_rows(T * K, 4)
    counts = np.asarray(counts)
    rows = np.ones(T, bool) if mask is None else np.asarray(mask)
    assert counts[:cfg.n_routed_experts].sum() == _per_token(T, K, n)[
        rows].sum()
    assert list(counts[-2:]) == [-(-n // tm) * tm, -(-T * K // tm) * tm]


def test_the_controls_tool_notes_the_combines_rows():
    """``tools/scmoe_controls.py`` hands the driver's record on and
    notes the rows the combine walked of the output's rows."""
    spec = importlib.util.spec_from_file_location(
        "scmoe_controls", os.path.join(ROOT, "tools", "scmoe_controls.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    notes = []
    ctx = type("Ctx", (), {"note": lambda self, **f: notes.append(f)})()
    record = {"moe": {"moe_combine_rows": 128 * 3,
                      "moe_output_rows": 2432 * 3}}
    assert tool.run_noting_the_combine(ctx, lambda c: record) is record
    assert notes == [{"phase": "moe_combine", "moe_combine_rows": 384,
                      "moe_output_rows": 7296,
                      "moe_combine_share": 384 / 7296}]


def test_only_a_partial_share_builds_the_combine_loop(tiny):
    """A share that holds every column keeps the unsort and the einsum:
    no loop in its layer; a partial share's layer has one."""
    def text(cfg, params, blk):
        h2 = _h0(cfg).astype(cfg.dtype)
        return jax.jit(lambda h, b: ds.moe_ffn(cfg, h, b)).lower(
            h2, blk).as_text()

    whole = ds.deepseek_v3_tiny()
    assert not whole.expert_share.partial
    params = ds.init_params(whole, jax.random.PRNGKey(2))
    blk = {k: v[0] for k, v in params["moe"].items()}
    assert "while" not in text(whole, params, blk)
    params, cfg = tiny
    assert cfg.expert_share.partial
    assert "while" in text(cfg, params, _moe_blk(params))
    # the counts a full share hands the engine keep their keys
    flat = np.zeros((2 * whole.expert_share.count_width,), np.int32)
    assert set(ds.decode_extra_stats(whole, flat)) == {
        "moe_assignments", "moe_experts_touched", "moe_max_expert_load",
        "moe_zero_assignments", "moe_remote_assignments"}


# --------------------------------------------------------------------------
# through the paged engine
# --------------------------------------------------------------------------

class TestPagedEngine:
    def test_the_engine_finds_the_family_by_its_config(self, tiny):
        _, cfg = tiny
        assert serving.family_of(cfg) is lc

    def test_prefill_then_decode_through_the_pool(self, tiny):
        """Four requests of different lengths share waves and decode
        steps; prompts end inside a page (5), on a boundary (16) and
        answers cross one or more 8-position pages: every generated
        row's logits against the reference's full forward."""
        params, cfg = tiny
        eng = _engine(tiny)
        reqs = [eng.submit(_tokens(10 + i, n), m) for i, (n, m) in
                enumerate(((5, 6), (16, 12), (30, 4), (9, 20)))]
        eng.run(max_steps=200)
        for r in reqs:
            assert r.done and not r.failed
            _assert_request_matches(params, cfg, r)
        st = eng.stats()
        assert st["decode_compiles"] == 1
        decoded = sum(len(r.tokens) - 1 for r in reqs)
        every = decoded * cfg.moe_topk * cfg.num_layers
        assert st["moe_assignments"] + st["moe_zero_assignments"] \
            + st["moe_remote_assignments"] == every
        assert min(st["moe_assignments"], st["moe_zero_assignments"],
                   st["moe_remote_assignments"]) > 0
        assert 0 < st["moe_experts_touched"] <= st["moe_assignments"]
        # 2 attention blocks x 2 layers x (32 + 8) float32 values
        assert st["kv_bytes_per_position"] == 4 * (32 + 8) * 4
        assert st["pages_in_use"] == 0

    def test_the_combine_counts_its_windows_through_stats(
            self, tiny, monkeypatch):
        """Every slot busy in every decode step (one wave of 40, answers
        of one length): each layer's combine walks ceil(held rows / 128)
        windows of 128 of its 256 rows, and ``stats()`` sums them."""
        params, cfg = tiny
        seen = []

        def spy(c, flat):
            seen.append(np.asarray(flat).copy())
            return ds.decode_extra_stats(c, flat)

        monkeypatch.setattr(lc, "decode_extra_stats", spy)
        slots, K, E = 40, cfg.moe_topk, cfg.n_routed_experts
        eng = _engine(tiny, slots=slots, num_pages=90, max_len=32,
                      batch_buckets=(1, slots), seq_buckets=(16,))
        reqs = [eng.submit(_tokens(200 + i, 8), 6) for i in range(slots)]
        eng.run(max_steps=50)
        assert all(r.done and not r.failed for r in reqs)
        st = eng.stats()
        assert len(seen) == st["decode_steps"] > 0
        tm, R = 128, 256        # 40 x 4 assignments in whole windows
        walked = 0
        for flat in seen:
            rows = flat.reshape(cfg.num_layers, -1)
            # every slot counted: the held rows are all of them
            assert (rows[:, :E + 2].sum(1) == slots * K).all()
            n_held = rows[:, :E].sum(1)
            assert (rows[:, -2] == -(-n_held // tm) * tm).all()
            assert (rows[:, -1] == R).all()
            walked += int(rows[:, -2].sum())
        assert 0 < st["moe_combine_rows"] == walked
        assert st["moe_output_rows"] == st["decode_steps"] * cfg.num_layers * R

    def test_the_pool_holds_two_blocks_a_layer(self, tiny):
        _, cfg = tiny
        c, r = lc.init_paged_pools(cfg, 5, 8)
        assert c.shape == (4, 5, 8, 32) and r.shape == (4, 5, 8, 128)

    def test_absorbed_decode_matches_the_published_order(self, tiny):
        params, cfg = tiny
        ps, toks = 8, _tokens(50, 21)
        want = _ref_logits(params, cfg, toks)
        pools = lc.init_paged_pools(cfg, 6, ps)
        step = jax.jit(lambda pools, absorbed, *a: lc.decode_paged(
            params, cfg, pools, *a, absorbed=absorbed), static_argnums=1)
        with jax.default_matmul_precision("highest"):
            _, pools = lc.prefill_paged(
                params, cfg, pools, jnp.asarray(toks[None, :16]),
                jnp.asarray([16]), jnp.asarray([[1, 2]]))
            table = jnp.asarray([[1, 2, 3, 0]], jnp.int32)
            for pos in range(16, 21):
                args = (table, jnp.asarray([3]), jnp.asarray([pos - 16]),
                        jnp.asarray([pos]), jnp.asarray(toks[pos:pos + 1]))
                a, new_pools, counts = step(pools, True, *args)
                u, _, _ = step(pools, False, *args)
                assert np.abs(np.asarray(a) - np.asarray(u)).max() < TOL
                assert np.abs(np.asarray(a[0]) - want[pos]).max() < TOL
                assert counts.shape == (cfg.num_layers,
                                        cfg.n_routed_experts + 4)
                pools = new_pools

    def test_chunked_prefill(self, tiny):
        params, cfg = tiny
        eng = _engine(tiny, prefill_chunk=16)
        r = eng.submit(_tokens(40, 30), 6)
        eng.run(max_steps=100)
        assert eng.stats()["prefill_chunks"] == 2
        _assert_request_matches(params, cfg, r)

    def test_on_a_prefix_cache_hit(self, tiny):
        params, cfg = tiny
        eng = _engine(tiny)
        shared = _tokens(30, 24)
        a = eng.submit(np.concatenate([shared, _tokens(31, 3)]), 5)
        eng.run(max_steps=100)
        b = eng.submit(np.concatenate([shared, _tokens(32, 5)]), 7)
        eng.run(max_steps=100)
        assert eng.stats()["prefix_page_hits"] >= 3
        for r in (a, b):
            _assert_request_matches(params, cfg, r)

    def test_through_the_grouped_matmul_kernel_in_interpret_mode(
            self, tiny, monkeypatch):
        """Prefill waves and decode steps with the held experts' products
        in the Pallas kernel, whose sizes here sum to a fraction of its
        rows; the engine's stats say it engaged."""
        params, cfg = tiny
        _grouped_kernel_on(monkeypatch)
        eng = _engine(tiny, slots=2)
        before = eng.stats()["grouped_matmul_kernel_calls"]
        reqs = [eng.submit(_tokens(70 + i, n), 5) for i, n in
                enumerate((7, 18))]
        eng.run(max_steps=50)
        for r in reqs:
            _assert_request_matches(params, cfg, r)
        assert eng.stats()["grouped_matmul_kernel_calls"] > before

    def test_the_new_scopes_are_in_the_program(self, tiny):
        params, cfg = tiny
        pools = lc.init_paged_pools(cfg, 6, 8)
        ints = jnp.zeros((2,), jnp.int32)
        text = jax.jit(lambda *a: lc.decode_paged(params, cfg, *a)).lower(
            pools, jnp.zeros((2, 4), jnp.int32), ints, ints, ints + 3,
            ints).as_text(debug_info=True)
        for scope in ("mla_q_lora", "scmoe_shortcut", "moe_held",
                      "moe_zero", "moe_router", "mla_attn", "dense_mlp"):
            assert scope in text, scope


class TestUnbuiltCompositions:
    @pytest.mark.parametrize("kw, name", [
        (dict(quant="int8"), "quant="),
        (dict(kv_dtype="int8"), "kv_dtype='int8'"),
        (dict(tp=2), "tp > 1"),
        (dict(pp=2), "pp > 1"),
        (dict(kv_handoff=True), "kv_handoff"),
        (dict(host_tier_mb=4), "host KV tier"),
    ])
    def test_paged_engine_option(self, tiny, kw, name):
        with pytest.raises(ValueError, match=f"longcat_flash .*{name}"):
            _engine(tiny, **kw)

    def test_slot_engine(self, tiny):
        with pytest.raises(ValueError, match="slot engine"):
            ServingEngine(tiny, slots=2, max_len=32)

    def test_speculative_decoding(self, tiny):
        from paddle_tpu.inference.speculative import SpeculativeServingEngine
        with pytest.raises(ValueError, match="speculative decoding"):
            SpeculativeServingEngine(tiny, spec_mode="ngram", slots=2,
                                     max_len=32)

    @pytest.mark.parametrize("kw, name", [
        (dict(zero_expert_type="zero"), "zero_expert_type"),
        (dict(q_lora_rank=None), "q_lora_rank"),
        (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
        (dict(router_bias=True), "router_bias"),
        (dict(attention_method="DSA"), "attention_method"),
    ])
    def test_config_refuses_what_is_not_built(self, kw, name):
        with pytest.raises(ValueError, match=name):
            lc.longcat_flash_tiny(**kw)


def test_prefix_salt_keeps_families_and_shares_apart(tiny):
    _, cfg = tiny
    other = dataclasses.replace(cfg, first_held_expert=0)
    assert "longcat_flash" in lc.prefix_salt(cfg)
    assert lc.prefix_salt(cfg) != lc.prefix_salt(other)
    assert lc.prefix_salt(cfg) != ds.prefix_salt(ds.deepseek_v3_tiny())
    eng = _engine(tiny)
    assert lc.prefix_salt(cfg) in eng._pager.hash_key
