"""The ``ouro`` family (models/ouro.py: a looped language model — the
stacked layers run ``total_ut_steps`` times over shared weights) held to
its plain float32 reference (testing/reference_ouro.py) on seeded random
weights, at a small size on the CPU (3 layers x 3 passes, 4 heads x 32,
vocabulary 512): LOGITS, never sampled tokens.

Tolerances, each with its reason.

``TOL`` 5e-6 — float32 pool.  Program and reference both run float32
with matmuls at ``highest`` and differ in the ORDER of the same float32
sums (batched einsums, the kernel's fallback softmax over a padded
view): 2e-7..6e-7 read on logits of deviation 0.16.  5e-6 is ten times
that and a thousandth of what bf16 compute does to the same logits
(4e-3, asserted below).

``TOL_BF16_POOL`` 5e-3 — float32 compute over a bf16 pool: only the
cached K/V are rounded (2**-9 relative), which reads 3e-4..1.4e-3 on
the logits over a dozen requests.

``TOL_INT8_POOL`` 1.5e-2 — the int8 pool's absmax rounding of K/V
(2**-8 of a head's largest value): 7e-4..3.9e-3 read.

The shared-pages fault reads 0.29..0.66 through either pool: twenty
times the loosest of the three.
"""
import dataclasses
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.serving import PagedServingEngine, ServingEngine
from paddle_tpu.models import gpt, ouro
from paddle_tpu.ops.pallas import paged_attn
from paddle_tpu.testing import reference_ouro as ref

TOL = 5e-6
TOL_BF16_POOL = 5e-3
TOL_INT8_POOL = 1.5e-2
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny():
    cfg = ouro.ouro_tiny()
    return ouro.init_params(cfg, jax.random.PRNGKey(0)), cfg


def _hp(cfg):
    return dataclasses.asdict(cfg)


_REF_JITS = {}


def _ref(params, cfg, tokens, module=ref):
    """(logits [n, V], gates [T, n]) of the reference over ``tokens``,
    padded to one width so that every call shares one compile: the model
    is causal, so padding behind a row cannot reach it."""
    width = -(-len(tokens) // 64) * 64
    key = (module.__name__, width, cfg.total_ut_steps,
           cfg.early_exit_threshold)
    if key not in _REF_JITS:
        hp = _hp(cfg)
        _REF_JITS[key] = jax.jit(lambda p, t: module.logits(p, t, hp))
    padded = np.zeros((width,), np.int32)
    padded[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        logits, gates = _REF_JITS[key](params, jnp.asarray(padded))
    return np.asarray(logits)[:len(tokens)], np.asarray(gates)[
        :, :len(tokens)]


def _engine(model, **kw):
    args = dict(slots=3, max_len=64, page_size=8, num_pages=25,
                seq_buckets=(16, 32), batch_buckets=(1, 2),
                capture_logits=True)
    args.update(kw)
    return PagedServingEngine(model, **args)


def _request_error(params, cfg, req):
    """The worst difference of any generated position's logits from the
    reference's full forward over prompt + generated (teacher-forced on
    the engine's own tokens)."""
    history = np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)])
    want, _ = _ref(params, cfg, history)
    n = len(req.prompt)
    got = np.stack(req.logits)
    assert got.shape[0] == len(req.tokens)
    return np.abs(got - want[n - 1:n - 1 + len(req.tokens)]).max()


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(0, 512, n).astype(np.int32)


def _share_pass_one_pages(monkeypatch):
    """The looped family's own fault, planted: every pass reads and
    writes pass 1's pages (layer index l for t * L + l)."""
    real = ouro._passes

    def faulty(params, cfg, x, pools, layer_body, **kw):
        L = cfg.num_hidden_layers
        return real(params, cfg, x, pools,
                    lambda y, blk, vl, pp: layer_body(y, blk, vl % L, pp),
                    **kw)
    monkeypatch.setattr(ouro, "_passes", faulty)


class TestForward:
    def test_logits_and_gates_match_the_reference(self, tiny):
        params, cfg = tiny
        toks = np.stack([_tokens(0, 40), _tokens(1, 40)])
        with jax.default_matmul_precision("highest"):
            logits, gates = ouro.forward(params, jnp.asarray(toks), cfg)
        assert gates.shape == (cfg.total_ut_steps, 2, 40)
        for b, t in enumerate(toks):
            want, want_gates = _ref(params, cfg, t)
            assert np.abs(np.asarray(logits[b]) - want).max() < TOL
            # a sigmoid of an O(1) float32 sum: one rounding step
            assert np.abs(np.asarray(gates[:, b]) - want_gates).max() < 1e-6
        assert 0.2 < float(gates.min()) and float(gates.max()) < 0.8

    def test_bf16_compute_fails_the_tolerance(self, tiny):
        """The tolerance separates the stated precision from the next
        one down: the same weights computed in bf16 miss it by far."""
        params, cfg = tiny
        low = dataclasses.replace(cfg, dtype="bfloat16")
        toks = _tokens(0, 40)
        got, _ = ouro.forward(params, jnp.asarray(toks[None]), low)
        want, _ = _ref(params, cfg, toks)
        assert np.abs(np.asarray(got[0]) - want).max() > 100 * TOL

    def test_one_pass_is_the_same_stack_run_once(self, tiny):
        """T = 1: the layers once, the final norm, the head — written
        out here from the family's own layer."""
        params, cfg = tiny
        once = dataclasses.replace(cfg, total_ut_steps=1)
        toks = jnp.asarray(_tokens(2, 24)[None])
        pos = jnp.arange(24, dtype=jnp.int32)[None]
        with jax.default_matmul_precision("highest"):
            got, gates = ouro.forward(params, toks, once)
            x = params["embed"][toks]
            for i in range(cfg.num_hidden_layers):
                blk = {k: v[i] for k, v in params["layers"].items()}
                x, _ = ouro._layer(
                    cfg, x, blk, pos,
                    lambda q, k, v: (ouro._attention(q, k, v), None))
            x = ouro._rmsnorm(x, params["norm_f"], cfg.rms_norm_eps)
            want = x @ params["head"]
        assert gates.shape[0] == 1
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOL
        three, _ = _ref(params, cfg, np.asarray(toks[0]))
        assert np.abs(np.asarray(got[0]) - three).max() > 1e-2

    def test_the_exit_rule_is_the_published_one(self, tiny):
        """Hand values: lam = (.5, .5, x): p = (.5, .25, .25), CDF =
        (.5, .75, 1).  Threshold 1 leaves at the last pass, 0.75 at the
        second, 0.5 and under at the first; a gate of exactly 1 leaves
        at once even at threshold 1."""
        lam = jnp.asarray([[.5, .5, 1.], [.5, .5, .2], [.9, .9, .9]])
        for thr, want in ((1.0, [3, 3, 1]), (0.75, [2, 2, 1]),
                          (0.5, [1, 1, 1]), (0.74, [2, 2, 1])):
            assert ouro.exit_steps(lam, thr).tolist() == want, thr
            assert (np.asarray(ref.exit_pass(lam, thr)) + 1).tolist() == want
        params, cfg = tiny
        toks = _tokens(5, 30)
        _, g = _ref(params, cfg, toks)
        # a threshold that half of these tokens reach after two passes
        thr = float(np.median(g[0] + g[1] * (1 - g[0])))
        early = dataclasses.replace(cfg, early_exit_threshold=thr)
        with jax.default_matmul_precision("highest"):
            got, gates = ouro.forward(params, jnp.asarray(toks[None]), early)
        leave = np.asarray(ouro.exit_steps(gates[:, 0], thr))
        assert set(leave.tolist()) == {2, 3}
        want, _ = _ref(params, early, toks)
        assert np.abs(np.asarray(got[0]) - want).max() < TOL

    def test_rope_is_the_half_split_rotation(self):
        """Column i turns with column i + hd/2 (not with its neighbour,
        as ``deepseek_v3._rope`` does), by pos * theta^(-2i/hd)."""
        x = jnp.zeros((1, 1, 8)).at[0, 0, 1].set(1.0)
        out = np.asarray(ouro._rope(x, jnp.asarray([3]), 100.0))[0, 0]
        ang = 3 * 100.0 ** (-2 / 8)
        want = np.zeros(8)
        want[1], want[5] = np.cos(ang), np.sin(ang)
        assert np.abs(out - want).max() < 1e-6

    def test_benchmark_copy_of_the_reference_gives_the_same_numbers(
            self, tiny):
        params, cfg = tiny
        spec = importlib.util.spec_from_file_location(
            "bench_reference_ouro", os.path.join(
                ROOT, "benchmark", "lib", "reference_ouro.py"))
        copy = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(copy)
        toks = _tokens(3, 24)
        for a, b in zip(_ref(params, cfg, toks),
                        _ref(params, cfg, toks, module=copy)):
            assert np.array_equal(a, b)
        with open(ref.__file__) as f, open(copy.__file__) as g:
            assert f.read() == g.read()

    def test_layer_at_a_time_upcasts_bf16_weights_to_the_same_rows(
            self, tiny):
        """What the benchmark's check calls: bf16 weights in, float32
        arithmetic inside, rows only."""
        params, cfg = tiny
        low = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)
        bf16 = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), low)
        toks = _tokens(4, 24)
        rows = jnp.asarray([0, 11, 23])
        with jax.default_matmul_precision("highest"):
            got = np.asarray(ref.layer_at_a_time(_hp(cfg))(
                bf16, jnp.asarray(toks), rows))
        want, _ = _ref(low, cfg, toks)
        assert got.dtype == np.float32
        assert np.abs(got - want[np.asarray(rows)]).max() < TOL


POOLS = [pytest.param(dict(), TOL, id="float32"),
         pytest.param(dict(cache_dtype="bfloat16"), TOL_BF16_POOL,
                      id="bf16"),
         pytest.param(dict(kv_dtype="int8", page_size=32, max_len=128,
                           num_pages=13, seq_buckets=(32, 64)),
                      TOL_INT8_POOL, id="int8")]


class TestPagedEngine:
    @pytest.mark.parametrize("pool, tol", POOLS)
    def test_prefill_then_decode_with_a_preemption_and_a_prefix_hit(
            self, tiny, pool, tol):
        """One engine, one sequence of events: two requests fill the
        pool until one is preempted and recomputed; then a third shares
        the first one's prompt pages.  Every generated row of every
        request against the reference's full forward."""
        params, cfg = tiny
        args = dict(slots=2, page_size=4, num_pages=12, seq_buckets=(16, 32),
                    batch_buckets=(1,))
        args.update(pool)
        if "kv_dtype" in pool:      # pages of 32: fewer, larger
            args.update(num_pages=5)
        eng = _engine(tiny, **args)
        shared = _tokens(20, 12)
        n_new = 60 if "kv_dtype" in pool else 14
        a = eng.submit(shared, n_new)
        b = eng.submit(_tokens(21, 12), n_new)
        eng.run(max_steps=600)
        st = eng.stats()
        assert st["preemptions"] >= 1 and a.preemptions + b.preemptions >= 1
        c = eng.submit(np.concatenate([shared, _tokens(22, 3)]), 6)
        eng.run(max_steps=200)
        st = eng.stats()
        if "kv_dtype" not in pool:      # a 12-token prompt is 3 whole pages
            assert st["prefix_page_hits"] >= 3
        worst = max(_request_error(params, cfg, r) for r in (a, b, c))
        assert worst < tol, worst
        assert st["decode_compiles"] == 1 and st["pages_in_use"] == 0
        # the gate's counters: every active slot ran every pass
        assert st["loop_tokens"] > 0
        assert st["loop_passes"] == cfg.total_ut_steps * st["loop_tokens"]

    def test_mixed_lengths_across_page_boundaries(self, tiny):
        """Four requests of different lengths share waves and decode
        steps; prompts end inside a page (5), on a boundary (16) and
        answers cross one or more 8-position pages."""
        params, cfg = tiny
        eng = _engine(tiny)
        reqs = [eng.submit(_tokens(10 + i, n), m) for i, (n, m) in
                enumerate(((5, 6), (16, 12), (30, 4), (9, 20)))]
        eng.run(max_steps=200)
        for r in reqs:
            assert r.done and not r.failed
            assert _request_error(params, cfg, r) < TOL
        st = eng.stats()
        decoded = sum(len(r.tokens) - 1 for r in reqs)
        assert st["loop_tokens"] == decoded
        assert st["loop_passes"] == 3 * decoded

    def test_kv_bytes_per_position_is_the_pools_bytes_over_positions(
            self, tiny):
        _, cfg = tiny
        eng = _engine(tiny)
        st = eng.stats()
        positions = st["num_pages"] * st["page_size"]
        assert st["kv_bytes_per_position"] == st["kv_bytes_total"] / positions
        assert st["kv_bytes_per_position"] == 2 * 9 * 128 * 4
        assert ouro.kv_bytes_per_position(ouro.OuroConfig(), 2) == 1_572_864
        assert eng._pools[0].shape == (9, 25, 8, 128)

    def test_chunked_prefill_matches_the_wave(self, tiny):
        """``chunk_paged`` against ``prefill_paged``: the same prompt
        through two chunks of 16 and through one wave leaves the same
        logits behind it, and both match the reference."""
        params, cfg = tiny
        prompt = _tokens(40, 30)
        chunked = _engine(tiny, prefill_chunk=16)
        r = chunked.submit(prompt, 6)
        chunked.run(max_steps=100)
        assert chunked.stats()["prefill_chunks"] == 2
        wave = _engine(tiny)
        w = wave.submit(prompt, 6)
        wave.run(max_steps=100)
        assert r.tokens == w.tokens
        assert np.abs(np.stack(r.logits) - np.stack(w.logits)).max() < TOL
        assert _request_error(params, cfg, r) < TOL

    def test_chunk_program_against_the_prefill_program(self, tiny):
        """The two programs themselves on one pool each: same pages
        written (the scratch page apart), same last-row logits."""
        params, cfg = tiny
        ps, toks = 8, _tokens(41, 32)
        with jax.default_matmul_precision("highest"):
            want, filled = ouro.prefill_paged(
                params, cfg, ouro.init_paged_pools(cfg, 6, ps),
                jnp.asarray(toks[None]), jnp.asarray([32]),
                jnp.asarray([[1, 2, 3, 4]]))
            pools = ouro.init_paged_pools(cfg, 6, ps)
            row = jnp.asarray([1, 2, 3, 4, 0, 0], jnp.int32)
            for off in (0, 16):
                got, pools = ouro.chunk_paged(
                    params, cfg, pools, jnp.asarray(toks[None, off:off + 16]),
                    row, jnp.int32(off))
        assert np.abs(np.asarray(got[0, -1]) - np.asarray(want[0])).max() \
            < TOL
        for a, b in zip(pools, filled):
            assert np.abs(np.asarray(a[:, 1:5]) - np.asarray(b[:, 1:5])
                          ).max() < 1e-6

    def test_passes_sharing_pass_one_pages_fail_the_tolerance(
            self, tiny, monkeypatch):
        """The control that must FAIL: with layer index l for t * L + l
        (also the paper's cache-sharing shortcut) a decode row reads the
        LAST pass's K/V where it needs its own pass's, and lands far
        outside even the loosest pool's limit."""
        params, cfg = tiny
        _share_pass_one_pages(monkeypatch)
        eng = _engine(tiny)
        r = eng.submit(_tokens(50, 20), 8)
        eng.run(max_steps=100)
        err = _request_error(params, cfg, r)
        assert err > 10 * TOL_INT8_POOL, err

    def test_through_the_kernel_in_interpret_mode(self, tiny, monkeypatch):
        """The engine's decode step with GPT's Pallas kernel in it, the
        virtual layer index on its scalar-prefetch channel."""
        params, cfg = tiny
        monkeypatch.setattr(paged_attn, "pallas_enabled", lambda: True)
        monkeypatch.setattr(paged_attn, "_over_heads", functools.partial(
            paged_attn._over_heads, interpret=True))
        eng = _engine(tiny, slots=2)
        reqs = [eng.submit(_tokens(60 + i, n), 5) for i, n in
                enumerate((7, 18))]
        eng.run(max_steps=50)
        for r in reqs:
            assert _request_error(params, cfg, r) < TOL


class TestUnbuiltCompositions:
    """Each raises by name at construction, before anything is built."""

    @pytest.mark.parametrize("kw, name", [
        (dict(quant="int8"), "quant="),
        (dict(tp=2), "tp > 1"),
        (dict(pp=2), "pp > 1"),
        (dict(kv_handoff=True), "kv_handoff"),
        (dict(host_tier_mb=4), "host KV tier"),
    ])
    def test_paged_engine_option(self, tiny, kw, name):
        with pytest.raises(ValueError, match=f"ouro .*{name}"):
            _engine(tiny, **kw)

    def test_early_exit_threshold_under_one(self, tiny):
        params, cfg = tiny
        early = dataclasses.replace(cfg, early_exit_threshold=0.9)
        with pytest.raises(ValueError,
                           match="ouro .*early_exit_threshold < 1"):
            _engine((params, early))

    def test_slot_engine(self, tiny):
        with pytest.raises(ValueError, match="slot engine"):
            ServingEngine(tiny, slots=2, max_len=32)

    def test_speculative_decoding(self, tiny):
        from paddle_tpu.inference.speculative import SpeculativeServingEngine
        with pytest.raises(ValueError, match="speculative decoding"):
            SpeculativeServingEngine(tiny, spec_mode="ngram", slots=2,
                                     max_len=32)

    @pytest.mark.parametrize("kw, name", [
        (dict(num_key_value_heads=2), "num_key_value_heads"),
        (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
        (dict(use_sliding_window=True), "sliding_window"),
        (dict(layer_types=["full_attention", "sliding_attention"]),
         "layer_types"),
        (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    ])
    def test_config_refuses_what_is_not_built(self, kw, name):
        with pytest.raises(ValueError, match=name):
            ouro.ouro_tiny(**kw)


def test_family_interface_and_salt(tiny):
    from paddle_tpu.inference import serving
    _, cfg = tiny
    assert serving.family_of(cfg) is ouro
    assert "ouro" in ouro.prefix_salt(cfg) and "3" in ouro.prefix_salt(cfg)
    assert ouro.prefix_salt(cfg) != ouro.prefix_salt(
        dataclasses.replace(cfg, total_ut_steps=2))
    assert gpt.prefix_salt(gpt.gpt_tiny()) == ""
    eng = _engine(tiny)
    assert ouro.prefix_salt(cfg) in eng._pager.hash_key
    assert ouro.decode_extra_stats(cfg, np.asarray([5, 5, 4])) == {
        "loop_tokens": 5, "loop_passes": 14}


def test_the_published_widths_count_2_668m_parameters():
    cfg = ouro.OuroConfig()
    shapes = jax.eval_shape(lambda k: ouro.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == 48 * (4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048) \
        + 2 * 49152 * 2048 + 2048 + 2048 + 1
    assert round(n / 1e6) == 2668


def test_the_family_is_imported_only_where_it_is_used():
    """``import paddle_tpu`` (and the engine) must not pay for a family
    a process does not serve: set-up time is judged in every cell."""
    import subprocess
    import sys
    code = ("import sys, paddle_tpu, paddle_tpu.inference.serving\n"
            "print([m for m in sys.modules if 'ouro' in m])")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-1000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"
