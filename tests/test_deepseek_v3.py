"""The ``deepseek_v3`` family (models/deepseek_v3.py) held to its plain
float32 reference (testing/reference_deepseek_v3.py) on seeded random
weights, at a small size on the CPU: LOGITS, never sampled tokens.

Tolerances.  Program and reference both run float32 with matmuls at
``highest``; they differ in the ORDER of the same float32 sums
(absorbed products, grouped matmuls, online softmax), which reads
1e-7..3e-7 on logits of deviation 0.16.  ``TOL`` = 5e-6 is some twenty
times that and a thousandth of what bf16 compute does to the same
logits (about 4e-3, asserted below): a lower precision where float32 is
stated fails every comparison here.
"""
import dataclasses
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from paddle_tpu.inference.serving import PagedServingEngine, ServingEngine
from paddle_tpu.models import deepseek_v3 as ds
from paddle_tpu.observability import metrics
from paddle_tpu.ops.pallas import grouped_matmul, paged_mla
from paddle_tpu.testing import reference_deepseek_v3 as ref

TOL = 5e-6
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny():
    cfg = ds.deepseek_v3_tiny()
    return ds.init_params(cfg, jax.random.PRNGKey(0)), cfg


def _hp(cfg):
    return dataclasses.asdict(cfg)


def _grouped_kernel_on(monkeypatch):
    """The chip's branch of the experts' grouped matmul, interpreted."""
    monkeypatch.setattr(grouped_matmul, "pallas_enabled", lambda: True)
    monkeypatch.setattr(grouped_matmul, "_grouped_tpu", functools.partial(
        grouped_matmul._grouped_tpu, interpret=True))


@pytest.fixture(params=["ragged_dot", "kernel"])
def moe_path(request, monkeypatch):
    """``moe_ffn``'s two implementations of the routed products: the
    off-chip ``ragged_dot`` and the Pallas kernel (interpret mode)."""
    if request.param == "kernel":
        _grouped_kernel_on(monkeypatch)
    return request.param


_REF_JITS = {}


def _ref_logits(params, cfg, tokens, module=ref):
    """The reference over ``tokens``, padded to one width so that every
    call shares one compile: the model is causal, so padding behind a
    row cannot reach it."""
    width = -(-len(tokens) // 64) * 64
    key = (module.__name__, width)
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


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(0, 512, n).astype(np.int32)


class TestForward:
    def test_full_forward_matches_reference(self, tiny):
        params, cfg = tiny
        toks = np.stack([_tokens(0, 40), _tokens(1, 40)])
        with jax.default_matmul_precision("highest"):
            got = np.asarray(ds.forward(params, jnp.asarray(toks), cfg))
        for row, t in zip(got, toks):
            assert np.abs(row - _ref_logits(params, cfg, t)).max() < TOL

    def test_bf16_compute_fails_the_tolerance(self, tiny):
        """The tolerance separates the stated precision from the next
        one down: the same weights computed in bf16 miss it by far."""
        params, cfg = tiny
        low = dataclasses.replace(cfg, dtype="bfloat16")
        toks = _tokens(0, 40)
        got = np.asarray(ds.forward(params, jnp.asarray(toks[None]), low))[0]
        assert np.abs(got - _ref_logits(params, cfg, toks)).max() > 100 * TOL

    def test_benchmark_copy_of_the_reference_gives_the_same_numbers(
            self, tiny):
        params, cfg = tiny
        spec = importlib.util.spec_from_file_location(
            "bench_reference_deepseek_v3", os.path.join(
                ROOT, "benchmark", "lib", "reference_deepseek_v3.py"))
        copy = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(copy)
        toks = _tokens(3, 24)
        a = _ref_logits(params, cfg, toks)
        b = _ref_logits(params, cfg, toks, module=copy)
        assert np.array_equal(a, b)
        with open(ref.__file__) as f, open(copy.__file__) as g:
            assert f.read() == g.read()

    def test_layer_at_a_time_upcasts_bf16_weights_to_the_same_rows(
            self, tiny):
        """What the benchmark's check and chip_smoke.py call: bf16
        weights in, float32 arithmetic inside, rows only."""
        params, cfg = tiny
        low = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)
        bf16 = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), low)
        toks = _tokens(4, 24)
        rows = jnp.asarray([0, 11, 23])
        with jax.default_matmul_precision("highest"):
            got = np.asarray(ref.layer_at_a_time(_hp(cfg))(
                bf16, jnp.asarray(toks), rows))
        want = _ref_logits(low, cfg, toks)[np.asarray(rows)]
        assert got.dtype == np.float32
        assert np.abs(got - want).max() < TOL


class TestRouting:
    def _layer(self, params, i=0):
        return {k: v[i] for k, v in params["moe"].items()}

    def test_ties_break_on_the_bias_as_in_the_reference(self, tiny):
        """h2 = 0 makes every score sigmoid(0) = 0.5: the bias alone
        chooses, equal biases go to the lower id, and the weights are
        the unbiased scores renormalised (0.5 / 1.0 * scale each)."""
        params, cfg = tiny
        blk = dict(self._layer(params))
        blk["b"] = jnp.asarray([0., .3, .1, .3, 0., .1, .2, .2], jnp.float32)
        h2 = jnp.zeros((5, cfg.hidden_size), jnp.float32)
        chosen, w = ds.route(cfg, h2, blk["wr"], blk["b"])
        r_chosen, r_w = ref.route(h2, blk["wr"], blk["b"], _hp(cfg))
        assert np.array_equal(np.asarray(chosen), np.asarray(r_chosen))
        assert np.array_equal(np.asarray(chosen[0]), [1, 3])
        assert np.allclose(np.asarray(w), np.asarray(r_w), atol=1e-7)
        assert np.allclose(np.asarray(w), cfg.routed_scaling_factor / 2)

    def test_chosen_and_weights_match_on_random_rows(self, tiny):
        params, cfg = tiny
        blk = self._layer(params, 1)
        h2 = jax.random.normal(jax.random.PRNGKey(5), (33, cfg.hidden_size))
        with jax.default_matmul_precision("highest"):
            chosen, w = ds.route(cfg, h2, blk["wr"], blk["b"])
            r_chosen, r_w = ref.route(h2, blk["wr"], blk["b"], _hp(cfg))
        assert np.array_equal(np.asarray(chosen), np.asarray(r_chosen))
        assert np.abs(np.asarray(w) - np.asarray(r_w)).max() < 1e-6

    def test_no_token_is_dropped_when_all_pick_the_same_experts(
            self, tiny, moe_path):
        """A capacity buffer would drop here: a bias of +10 sends all
        64 tokens to experts 2 and 5.  Every token's output equals the
        reference's, and the counts say 64 each."""
        params, cfg = tiny
        blk = dict(self._layer(params))
        blk["b"] = jnp.zeros((8,), jnp.float32).at[jnp.asarray([2, 5])].set(
            10.0)
        h2 = jax.random.normal(jax.random.PRNGKey(6), (64, cfg.hidden_size))
        with jax.default_matmul_precision("highest"):
            y, counts = ds.moe_ffn(cfg, h2, blk)
            want = ref.experts(h2, blk, _hp(cfg))
        # the held experts' loads, then no identity and no remote one
        assert np.array_equal(np.asarray(counts),
                              [0, 0, 64, 0, 0, 64, 0, 0, 0, 0])
        assert np.abs(np.asarray(y) - np.asarray(want)).max() < TOL

    def test_counts_leave_out_masked_rows(self, tiny, moe_path):
        params, cfg = tiny
        blk = self._layer(params)
        h2 = jax.random.normal(jax.random.PRNGKey(7), (6, cfg.hidden_size))
        mask = jnp.asarray([True, False, True, True, False, False])
        with jax.default_matmul_precision("highest"):
            y_all, c_all = ds.moe_ffn(cfg, h2, blk)
            y, c = ds.moe_ffn(cfg, h2, blk, row_mask=mask)
            want = ref.experts(h2, blk, _hp(cfg))
        assert int(c_all.sum()) == 12 and int(c.sum()) == 6
        assert np.array_equal(np.asarray(y), np.asarray(y_all))
        assert np.abs(np.asarray(y) - np.asarray(want)).max() < TOL

    def test_the_whole_stack_with_its_layer_index(self, tiny, moe_path):
        """As the layer scan hands them over: the experts' stacks whole,
        ``li`` traced, against the reference on that layer alone."""
        params, cfg = tiny
        h2 = jax.random.normal(jax.random.PRNGKey(8), (33, cfg.hidden_size))
        stacks = {k: params["moe"][k] for k in ds.EXPERT_STACKS}
        step = jax.jit(lambda li, blk: ds.moe_ffn(
            cfg, h2, dict(blk, li=li, **stacks))[0])
        for i in range(cfg.num_hidden_layers - 1):
            blk = self._layer(params, i)
            sliced = {k: v for k, v in blk.items() if k not in stacks}
            with jax.default_matmul_precision("highest"):
                y = step(jnp.int32(i), sliced)
                want = ref.experts(h2, blk, _hp(cfg))
            assert np.abs(np.asarray(y) - np.asarray(want)).max() < TOL


def _kernel_operands(dtype, lens, ps, maxP, layers=2, seed=0, nh=4,
                     rank=32):
    """Seeded operands of the latent kernel for ``lens``: 4 heads, rank
    32, rope 8 (by default), the pool's rows ``c`` and the rope key in
    a lane row of its own after it.  Each slot's live table entries
    name distinct pages; every entry past them holds a page id far out
    of range, and the pool's LAST page — where an out-of-range index
    lands when it is clamped — is NaN: a kernel that dereferences a
    dead entry returns NaN (0 x NaN in the weighted sum)."""
    rng = np.random.default_rng(seed)
    S, rope = len(lens), 8
    P = S * maxP + 2
    dt = jnp.dtype(dtype)
    qa = jnp.asarray(rng.normal(size=(S, nh, rank)), dt)
    qr = jnp.asarray(rng.normal(size=(S, nh, rope)), dt)
    pool = np.zeros((layers, P, ps, rank + 128))
    pool[..., :rank] = rng.normal(size=(layers, P, ps, rank))
    pool[..., rank:rank + rope] = rng.normal(size=(layers, P, ps, rope))
    pool[:, -1] = np.nan
    free = rng.permutation(np.arange(1, P - 1))[:S * maxP].reshape(S, maxP)
    live = np.asarray(lens)[:, None] // ps >= np.arange(maxP)[None, :]
    pt = np.where(live, free, 10 ** 6)
    return (qa, qr, jnp.asarray(pool, dt), jnp.asarray(pt, jnp.int32),
            jnp.asarray(lens, jnp.int32))


def _assert_kernel_matches(dtype, operands):
    """float32: kernel and fallback differ by summation order only
    (4e-7 read); bf16 pools: the kernel's products are exact and both
    round the output to bf16 once — one bf16 step of an O(1) value,
    8e-3."""
    qa, qr, pool, pt, lens = operands
    # the fallback gathers every entry of the table: hand it the dead
    # ones as page 0 (masked there), the kernel as they are
    seen = jnp.where(pt < pool.shape[1], pt, 0)
    for layer in range(pool.shape[0]):
        want = paged_mla._ref_paged_mla(qa, qr, pool[layer], seen, lens,
                                        0.2)
        got = paged_mla._paged_mla_tpu(qa, qr, pool, pt, lens,
                                       jnp.int32(layer), 0.2,
                                       interpret=True)
        err = jnp.abs(got.astype(jnp.float32)
                      - want.astype(jnp.float32)).max()
        assert float(err) < (2e-6 if dtype == "float32" else 8e-3)


# a step of 32 rows (two pages of 16) over a table of 8: four groups a
# slot; the new token sits AT lens, so rows 0..lens are live
_R, _VIEW = 32, 128
MOVER_CASES = {
    "every-slot-empty": [0, 0, 0],
    "under-at-over-a-group-boundary": [_R - 1, _R, _R + 1],
    "under-at-over-a-page-boundary": [14, 15, 16],
    "partial-last-group-beside-a-full-table": [_R + 5, _VIEW - 1, 3],
    "next-slot-prefetch-across-an-empty-slot": [2 * _R + 1, 0, _R],
    "full-tables-only": [_VIEW - 1, _VIEW - 1, _VIEW - 1],
    "last-group-reached-by-one-row": [3 * _R, 1, 3 * _R - 1],
}


class _Always:
    """A lengths ref that reads ``value`` for every slot."""
    def __init__(self, ref, value):
        self._ref, self._value = ref, value

    def __getitem__(self, idx):
        return self._ref[idx] * 0 + self._value


class TestKernel:
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_interpret_mode_matches_the_fallback(self, dtype):
        """The rule's own pick at these shapes: the whole table of
        8-row float32 pages in one step, a bf16 page of 8 rows (half a
        packed tile) one a step."""
        ops = _kernel_operands(dtype, [0, 13, 61], ps=8, maxP=8)
        _assert_kernel_matches(dtype, ops)

    @pytest.mark.parametrize("case", sorted(MOVER_CASES))
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_the_kernel_copies_its_live_pages_only(self, dtype, case,
                                                   monkeypatch):
        """What the page mover can get wrong, two layers each: which
        pages of a group are live, which step is next (the slot's next
        group or the next slot's first), which half a step reads, and
        that no dead table entry is followed."""
        monkeypatch.setattr(paged_mla, "GROUP_ROWS", _R)
        ops = _kernel_operands(dtype, MOVER_CASES[case], ps=16, maxP=8)
        assert paged_mla.group_pages(8, 16, 32 + 128, jnp.dtype(
            dtype).itemsize, 4) == 2
        _assert_kernel_matches(dtype, ops)

    def test_a_dead_entry_followed_would_show(self, monkeypatch):
        """The guard of the test above: the same operands through a
        mover that copies every page of a group gives NaN."""
        monkeypatch.setattr(paged_mla, "GROUP_ROWS", _R)
        qa, qr, pool, pt, lens = _kernel_operands(
            "float32", [_R + 5, 3, 0], ps=16, maxP=8)
        real = paged_mla.page_mover

        def greedy(pt_ref, lens_ref, *a, **kw):
            return real(pt_ref, _Always(lens_ref, _VIEW - 1), *a, **kw)

        monkeypatch.setattr(paged_mla, "page_mover", greedy)
        got = paged_mla._paged_mla_tpu(qa, qr, pool, pt, lens,
                                       jnp.int32(0), 0.2, interpret=True)
        assert bool(jnp.isnan(got).any())

    @pytest.mark.parametrize("width", [32, 16, 12, 6, 5, 24, 1])
    def test_group_is_a_rule_of_shapes(self, width):
        """Shapes in, G out: a power of two that divides the table's
        width and keeps a step's rows at or under ``GROUP_ROWS``; a page
        that is not whole packed tiles of its dtype goes one a step."""
        row = 512 + 128
        for ps, itemsize in ((64, 2), (16, 2), (8, 4), (16, 4), (128, 2)):
            g = paged_mla.group_pages(width, ps, row, itemsize, 32)
            assert g >= 1 and g & (g - 1) == 0 and width % g == 0
            assert g == 1 or g * ps <= paged_mla.GROUP_ROWS
            # the largest such: twice as many would break a condition
            assert (width % (2 * g) or 2 * g * ps > paged_mla.GROUP_ROWS)
        assert paged_mla.group_pages(width, 8, row, 2, 32) == 1
        assert paged_mla.group_pages(width, 4, row, 4, 32) == 1

    def test_group_at_the_serving_cell(self):
        """64 slots x 32 pages of 64 bf16 rows of 640: the pick of the
        chip's sweep (PERF.md section 6, PR 36)."""
        assert paged_mla.GROUP_ROWS == 1024
        assert paged_mla.group_pages(32, 64, 640, 2, 32) == 16
        assert paged_mla.group_pages(6, 64, 640, 2, 32) == 2

    def test_narrow_rank_falls_back(self, monkeypatch):
        """The rope key's lanes must start a whole 128-lane row into the
        pool's row: a latent rank that is not a multiple of 128 stays
        with the fallback on the chip."""
        monkeypatch.setattr(paged_mla, "pallas_enabled", lambda: True)
        pool = lambda w: jnp.zeros((1, 4, 16, w), jnp.bfloat16)
        assert paged_mla._use_pallas_mla(pool(512 + 128), 512, 32)
        assert not paged_mla._use_pallas_mla(pool(32 + 128), 32, 4)


# lengths over a table of 8 pages of 16: 0, 31, 32, 63, 64, 95 and the
# full table, n = 1, 2, 3, 4, 5, 6 and 8 live pages.  A step of 32 rows
# (G = 2) meets each of them as whole groups and a last group of one
# page; a step of 64 (G = 4) as last groups of 1, 2 or 3 pages, a whole
# one (n = 4) and a whole one and more (5, 6, 8)
GROUP_LENS = [0, _R - 1, _R, 2 * _R - 1, 2 * _R, 3 * _R - 1, _VIEW - 1]


class _Counted:
    """``pltpu`` whose copies report each start, and each wait with the
    semaphore of its half right after it, to the host."""
    def __init__(self, real, log):
        self._real, self._log = real, log

    def make_async_copy(self, src, dst, sem):
        real, log = self._real.make_async_copy(src, dst, sem), self._log

        class Copy:
            def start(self):
                real.start()
                jax.debug.callback(lambda: log["start"].append(1))

            def wait(self):
                real.wait()
                jax.debug.callback(
                    lambda v: log["after_wait"].append(int(v)),
                    pl.semaphore_read(sem))
        return Copy()

    def __getattr__(self, name):
        return getattr(self._real, name)


class TestOneCopyAPage:
    """The pool as ONE array of rows (``c``, then the rope key in a
    lane row of its own): a live page is one copy, a whole group one
    wait.  Held to the two-array kernel it replaced
    (``tests/latent_two_pool.py``) bit for bit on the same contents."""

    @pytest.mark.parametrize("rows,group", [(_R, 2), (2 * _R, 4)])
    @pytest.mark.parametrize("heads", [32, 64])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_the_kernel_is_the_two_array_kernel_bit_for_bit(
            self, dtype, heads, rows, group, monkeypatch):
        import latent_two_pool as two
        monkeypatch.setattr(paged_mla, "GROUP_ROWS", rows)
        qa, qr, pool, pt, lens = _kernel_operands(
            dtype, GROUP_LENS, ps=16, maxP=8, nh=heads, rank=512)
        assert paged_mla.group_pages(8, 16, pool.shape[-1], jnp.dtype(
            dtype).itemsize, heads) == group
        for layer in range(pool.shape[0]):
            li = jnp.int32(layer)
            got = paged_mla._paged_mla_tpu(qa, qr, pool, pt, lens, li, 0.2,
                                           interpret=True)
            want = two._paged_mla_tpu(qa, qr, pool[..., :512],
                                      pool[..., 512:], pt, lens, li, 0.2,
                                      interpret=True)
            assert got.dtype == want.dtype
            assert np.array_equal(np.asarray(got), np.asarray(want))
            assert not bool(jnp.isnan(got).any())

    @pytest.mark.parametrize("rows,group", [(_R, 2), (2 * _R, 4)])
    @pytest.mark.parametrize("case", sorted(MOVER_CASES) + ["group-lens"])
    def test_each_wait_is_for_what_was_started(self, case, rows, group,
                                               monkeypatch):
        """No wait leaves its half's semaphore below 0 (a wait for bytes
        never started: the chip would wait for ever), and every live
        step ends with it at 0 (no copy left unwaited); the starts and
        waits a call issues are :func:`paged_mla.copy_descriptors`'
        count."""
        monkeypatch.setattr(paged_mla, "GROUP_ROWS", rows)
        lens_ = MOVER_CASES.get(case, GROUP_LENS)
        qa, qr, pool, pt, lens = _kernel_operands(
            "bfloat16", lens_, ps=16, maxP=8, layers=1)
        log = {"start": [], "after_wait": [], "after_take": []}
        monkeypatch.setattr(paged_mla, "pltpu",
                            _Counted(paged_mla.pltpu, log))
        real = paged_mla.page_mover

        def mover(*a, **kw):
            prime, take = real(*a, **kw)
            sem = a[5]

            def checked(s, j):
                half = take(s, j)
                jax.debug.callback(
                    lambda v: log["after_take"].append(int(v)),
                    pl.semaphore_read(sem.at[half]))
                return half
            return prime, checked

        monkeypatch.setattr(paged_mla, "page_mover", mover)
        got = paged_mla._paged_mla_tpu(qa, qr, pool, pt, lens, jnp.int32(0),
                                       0.2, interpret=True)
        jax.block_until_ready(got)
        jax.effects_barrier()
        assert min(log["after_wait"]) == 0
        live = -(-(np.asarray(lens_) // 16 + 1) // group)
        assert log["after_take"] == [0] * int(live.sum())
        descriptors, pages = paged_mla.copy_descriptors(lens_, group, 16)
        assert len(log["start"]) == pages
        assert len(log["start"]) + len(log["after_wait"]) == descriptors

    @pytest.mark.parametrize("path", ["xla", "kernel"])
    def test_programs_equal_the_two_array_writers(self, tiny, path,
                                                  monkeypatch):
        """A bucketed wave, a chunked prefill and decode steps, through
        the decode kernel (interpret mode) or the XLA fallback."""
        import latent_two_pool as two
        if path == "kernel":
            two.kernels_on(monkeypatch)
        two.assert_held_to_two_arrays(ds, *tiny, monkeypatch)

    def test_descriptors_a_page_by_hand(self):
        """n = len // ps + 1 pages a slot, each started once; n // G
        whole groups waited for once each, the n % G pages of the last
        group one wait each."""
        ps, G = 64, 16
        # 1 page: 1 + 1; 16 pages: 16 + 1; 17: 17 + 2; 19: 19 + 4;
        # 64 (a full table of 64): 64 + 4
        lens = [0, 16 * ps - 1, 16 * ps, 18 * ps + 38, 64 * ps - 1]
        assert paged_mla.copy_descriptors(lens, G, ps) == (
            2 + 17 + 19 + 23 + 68, 1 + 16 + 17 + 19 + 64)
        assert paged_mla.copy_descriptors([5, 40], 1, 8) == (
            (1 + 1) + (6 + 6), 1 + 6)
        assert paged_mla.copy_descriptors([], G, ps) == (0, 0)
        cfg = ds.deepseek_v3_tiny()
        assert ds.decode_copy_descriptors(cfg, lens, G, ps) == \
            paged_mla.copy_descriptors(lens, G, ps)


class TestPagedEngine:
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
            _assert_request_matches(params, cfg, r)
        st = eng.stats()
        assert st["decode_compiles"] == 1
        decoded = sum(len(r.tokens) - 1 for r in reqs)
        assert st["moe_assignments"] == (decoded * cfg.num_experts_per_tok
                                         * (cfg.num_hidden_layers - 1))
        assert 0 < st["moe_experts_touched"] <= st["moe_assignments"]
        assert st["moe_max_expert_load"] >= st["decode_steps"] * 2
        assert st["kv_bytes_per_position"] == 3 * (32 + 8) * 4
        assert st["pages_in_use"] == 0

    def test_stats_report_the_decode_kernels_grid(self, tiny):
        """The family's ``decode_group_pages`` is all ``stats()`` needs:
        G by the latent kernel's rule of shapes, and the share of
        (slot, group) grid steps that are live — here the whole table
        of 8 pages is one step, so a live step an active slot."""
        params, cfg = tiny
        eng = _engine(tiny)
        assert "paged_attn_group_pages" in eng.stats()
        for i, n in enumerate((5, 20)):
            eng.submit(_tokens(70 + i, n), 30)
        for _ in range(4):
            eng.step()
        st = eng.stats()
        pools = ds.init_paged_pools(cfg, 25, 8)
        assert st["paged_attn_group_pages"] == ds.decode_group_pages(
            cfg, pools, 8) == paged_mla.group_pages(
                8, 8, cfg.kv_lora_rank + 128, 4, cfg.num_heads) == 8
        assert st["paged_attn_live_step_share"] == round(2 / 3, 4)
        eng.run(max_steps=200)
        assert eng.stats()["paged_attn_live_step_share"] == 0.0

    def test_stats_report_descriptors_a_live_page(self, tiny, monkeypatch):
        """``paged_mla_descriptors_per_page`` is the decode kernel's
        starts and waits over the active slots' live pages, from
        ``_lens``: one start a page, one wait a whole group of G and one
        a page of the last, partial group; 0 with no slot active.  Two
        pages of 8 a grid step, so both kinds of group occur."""
        monkeypatch.setattr(paged_mla, "GROUP_ROWS", 16)
        eng = _engine(tiny)
        G, ps = eng.stats()["paged_attn_group_pages"], 8
        assert G == 2 and eng.stats()["paged_mla_descriptors_per_page"] == 0
        for i, n in enumerate((5, 29)):
            eng.submit(_tokens(80 + i, n), 30)
        for _ in range(4):
            eng.step()
        lens = eng._lens[eng._active]
        assert len(lens) == 2 and lens.max() >= G * ps
        n = lens // ps + 1
        want = (n.sum() + (n // G).sum() + (n % G).sum()) / n.sum()
        assert eng.stats()["paged_mla_descriptors_per_page"] == round(
            float(want), 4)
        assert 1 + 1 / G <= want < 2
        eng.run(max_steps=200)
        assert eng.stats()["paged_mla_descriptors_per_page"] == 0.0

    @pytest.mark.parametrize("page_size,width,group", [(8, 8, 8), (16, 4, 4),
                                                       (4, 16, 1)])
    def test_group_pages_follow_the_engines_shapes(self, tiny, page_size,
                                                   width, group):
        """Page size and table width in, G out, through the engine: 4
        rows of float32 are half a packed tile and go one a step."""
        eng = _engine(tiny, page_size=page_size, num_pages=200 // page_size)
        assert eng.stats()["paged_attn_group_pages"] == group
        assert eng._pages_per_slot == width

    def test_after_a_preemption(self, tiny):
        params, cfg = tiny
        eng = _engine(tiny, slots=2, page_size=4, num_pages=9,
                      seq_buckets=(16,), batch_buckets=(1,),
                      prefix_cache=False)
        a = eng.submit(_tokens(20, 12), 16)
        b = eng.submit(_tokens(21, 12), 16)
        eng.run(max_steps=400)
        assert eng.stats()["preemptions"] >= 1
        assert a.preemptions + b.preemptions >= 1
        for r in (a, b):
            _assert_request_matches(params, cfg, r)

    def test_on_a_prefix_cache_hit(self, tiny):
        """The second request re-acquires the first one's prompt pages
        (latent pages hash like any other) and still matches."""
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

    def test_chunked_prefill(self, tiny):
        params, cfg = tiny
        eng = _engine(tiny, prefill_chunk=16)
        r = eng.submit(_tokens(40, 30), 6)
        eng.run(max_steps=100)
        assert eng.stats()["prefill_chunks"] == 2
        _assert_request_matches(params, cfg, r)

    def test_absorbed_decode_matches_unabsorbed(self, tiny):
        params, cfg = tiny
        ps, toks = 8, _tokens(50, 21)
        want = _ref_logits(params, cfg, toks)
        pools = ds.init_paged_pools(cfg, 6, ps)
        step = jax.jit(lambda pools, absorbed, *a: ds.decode_paged(
            params, cfg, pools, *a, absorbed=absorbed), static_argnums=1)
        with jax.default_matmul_precision("highest"):
            _, pools = ds.prefill_paged(
                params, cfg, pools, jnp.asarray(toks[None, :16]),
                jnp.asarray([16]), jnp.asarray([[1, 2]]))
            table = jnp.asarray([[1, 2, 3, 0]], jnp.int32)
            for pos in range(16, 21):
                args = (table, jnp.asarray([3]), jnp.asarray([pos - 16]),
                        jnp.asarray([pos]), jnp.asarray(toks[pos:pos + 1]))
                a, new_pools, _ = step(pools, True, *args)
                u, _, _ = step(pools, False, *args)
                assert np.abs(np.asarray(a) - np.asarray(u)).max() < TOL
                assert np.abs(np.asarray(a[0]) - want[pos]).max() < TOL
                pools = new_pools

    def test_through_the_kernel_in_interpret_mode(self, tiny, monkeypatch):
        """The engine's decode step with the Pallas kernel in it."""
        params, cfg = tiny
        monkeypatch.setattr(paged_mla, "_use_pallas_mla", lambda *a: True)
        monkeypatch.setattr(paged_mla, "_paged_mla_tpu", functools.partial(
            paged_mla._paged_mla_tpu, interpret=True))
        eng = _engine(tiny, slots=2)
        reqs = [eng.submit(_tokens(60 + i, n), 5) for i, n in
                enumerate((7, 18))]
        eng.run(max_steps=50)
        for r in reqs:
            _assert_request_matches(params, cfg, r)

    def test_through_the_grouped_matmul_kernel_in_interpret_mode(
            self, tiny, monkeypatch):
        """Prefill waves and decode steps with the experts' products in
        the Pallas kernel; the engine's stats say it engaged."""
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


class TestGroupedMatmulEngagement:
    """``serving.grouped_matmul_kernel_calls`` answers "did what XLA
    built contain the kernel?": a trace with the chip's branch taken
    holds the kernel and bumps it, the CPU's holds ``ragged_dot`` and
    bumps nothing."""
    COUNTER = "serving.grouped_matmul_kernel_calls"

    def _decode_jaxpr(self, tiny):
        params, cfg = tiny
        pools = ds.init_paged_pools(cfg, 6, 8)
        ints = jnp.zeros((2,), jnp.int32)
        return str(jax.make_jaxpr(lambda *a: ds.decode_paged(
            params, cfg, *a))(pools, jnp.zeros((2, 4), jnp.int32), ints,
                              ints, ints + 3, ints))

    def test_kernel_path_holds_the_calls_and_counts_them(self, tiny,
                                                         monkeypatch):
        _grouped_kernel_on(monkeypatch)
        before = metrics.counter(self.COUNTER).value
        text = self._decode_jaxpr(tiny)
        # the scan's body, once: gate and up share a call, then down —
        # the layer's three products in two kernels
        assert text.count("name=moe_grouped_matmul_gate_up") == 1
        assert text.count("name=moe_grouped_matmul\n") \
            + text.count("name=moe_grouped_matmul ") == 1
        assert "ragged_dot" not in text
        assert metrics.counter(self.COUNTER).value == before + 2

    def test_cpu_path_counts_nothing(self, tiny):
        before = metrics.counter(self.COUNTER).value
        text = self._decode_jaxpr(tiny)
        assert text.count("= ragged_dot") == 3   # a scan body's products
        assert "moe_grouped_matmul" not in text
        assert metrics.counter(self.COUNTER).value == before


class TestUnbuiltCompositions:
    """Each raises by name at construction (as gpt_pp.check_pp_config
    does), before anything is built."""

    @pytest.mark.parametrize("kw, name", [
        (dict(quant="int8"), "quant="),
        (dict(kv_dtype="int8"), "kv_dtype='int8'"),
        (dict(tp=2), "tp > 1"),
        (dict(pp=2), "pp > 1"),
        (dict(kv_handoff=True), "kv_handoff"),
        (dict(host_tier_mb=4), "host KV tier"),
    ])
    def test_paged_engine_option(self, tiny, kw, name):
        with pytest.raises(ValueError, match=f"deepseek_v3 .*{name}"):
            _engine(tiny, **kw)

    def test_slot_engine(self, tiny):
        with pytest.raises(ValueError, match="slot engine"):
            ServingEngine(tiny, slots=2, max_len=32)

    def test_speculative_decoding(self, tiny):
        from paddle_tpu.inference.speculative import SpeculativeServingEngine
        with pytest.raises(ValueError, match="speculative decoding"):
            SpeculativeServingEngine(tiny, spec_mode="ngram", slots=2,
                                     max_len=32)

    def test_config_refuses_what_is_not_built(self):
        with pytest.raises(ValueError, match="q_lora_rank"):
            ds.deepseek_v3_tiny(q_lora_rank=16)
        with pytest.raises(ValueError, match="n_group"):
            ds.deepseek_v3_tiny(n_group=2)

    def test_a_config_of_no_family_is_refused(self):
        from paddle_tpu.inference import serving

        @dataclasses.dataclass
        class Stray:
            hidden_size: int = 8

        with pytest.raises(TypeError, match="not a served model family"):
            serving.family_of(Stray())


def test_prefix_salt_keeps_families_apart(tiny):
    from paddle_tpu.models import gpt
    _, cfg = tiny
    assert gpt.prefix_salt(gpt.gpt_tiny()) == ""
    assert "deepseek_v3" in ds.prefix_salt(cfg)
    eng = _engine(tiny)
    assert ds.prefix_salt(cfg) in eng._pager.hash_key


def test_the_family_is_imported_only_where_it_is_used():
    """``import paddle_tpu`` (and the engine) must not pay for a family
    a process does not serve: set-up time is judged in every cell."""
    import subprocess
    import sys
    code = ("import sys, paddle_tpu, paddle_tpu.inference.serving\n"
            "print([m for m in sys.modules\n"
            "       if 'deepseek_v3' in m or 'paged_mla' in m])")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-1000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"
