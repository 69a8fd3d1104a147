"""The prefill flash forward (ops/pallas/flash_prefill.py) held to the
attention it replaces in a wave of the ``deepseek_v3`` family,
``deepseek_v3._mla_attend`` — XLA's, scores in HBM — on the CPU in
interpret mode, at toy sizes that keep the published widths' ratios
(nope 128, rope 64, v 128: a head's lanes must be whole 128-lane rows).

Tolerances.  In float32 kernel and reference run the same float32
products and differ in the ORDER of the softmax's sums and in where the
normalisation lands (after the product with V, not before): 1e-6 on
outputs of order 1, under ``test_deepseek_v3.py``'s ``TOL`` 5e-6 on
logits.  In bf16 both cast the probabilities to bf16 before the product
with V, the reference after dividing by the row's sum and the kernel
before, so an output differs by a bf16 step of its value (2^-8): that
is the difference blocking makes, and the chip's cell judges it.
"""
import dataclasses
import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.serving import PagedServingEngine
from paddle_tpu.models import deepseek_v3 as ds
from paddle_tpu.observability import metrics
from paddle_tpu.ops.pallas import flash_prefill as fp
from paddle_tpu.testing import reference_deepseek_v3 as ref

TOL = 5e-6
COUNTER = "serving.flash_prefill_kernel_calls"


def _cfg(**kw):
    base = dict(num_attention_heads=2, qk_nope_head_dim=128,
                qk_rope_head_dim=64, v_head_dim=128)
    base.update(kw)
    return ds.deepseek_v3_tiny(**base)


@pytest.fixture(scope="module")
def wide():
    """The tiny model with a head as wide as the published one."""
    cfg = _cfg()
    return ds.init_params(cfg, jax.random.PRNGKey(0)), cfg


@pytest.fixture
def kernel_on(monkeypatch):
    """The chip's branch of ``prefill_paged``, interpreted, at any
    length."""
    monkeypatch.setattr(fp, "pallas_enabled", lambda: True)
    monkeypatch.setattr(fp, "MIN_SCORE_BYTES", 0)
    monkeypatch.setattr(fp, "_flash_prefill_tpu", functools.partial(
        fp._flash_prefill_tpu, interpret=True))


def _operands(cfg, b, s, seed=0):
    cd = jnp.dtype(cfg.dtype)
    nh, rank = cfg.num_attention_heads, cfg.kv_lora_rank
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)

    def draw(key, shape, scale=1.0):
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(cd)

    blk = {"wukv": draw(keys[4], (rank, nh * (cfg.qk_nope_head_dim
                                              + cfg.v_head_dim)),
                        rank ** -0.5)}
    return blk, (draw(keys[0], (b, s, nh, cfg.qk_nope_head_dim)),
                 draw(keys[1], (b, s, nh, cfg.qk_rope_head_dim)),
                 draw(keys[2], (b, s, rank)),
                 draw(keys[3], (b, s, cfg.qk_rope_head_dim)))


def _both(cfg, b, s, lens, blocks):
    """(kernel, reference) outputs [b, s, nh * v] in float32, the kernel
    through the model's own call of it, interpreted."""
    blk, ops = _operands(cfg, b, s)
    want = ds._mla_attend(cfg, blk, *ops, ds._causal(s, s))
    with mock.patch.object(fp, "BLOCKS", blocks), mock.patch.object(
            fp, "_flash_prefill_tpu", functools.partial(
                fp._flash_prefill_tpu, interpret=True)):
        got = ds._mla_attend_flash(cfg, blk, *ops,
                                   jnp.asarray(lens, jnp.int32))
    return (np.asarray(got.astype(jnp.float32)),
            np.asarray(want.astype(jnp.float32)))


def _lens(b, s):
    """True lengths under ``s``: one row nearly full, the others
    shorter, the last a pad row of length 1 when there are four."""
    return [s - 19, s // 2 + 3, s // 3, 1][:b] if b > 1 else [s - 19]


class TestKernel:
    @pytest.mark.parametrize("b", [1, 4])
    @pytest.mark.parametrize("s,blocks", [
        (128, (128, 128)),      # one block, the diagonal's
        (256, (128, 128)),      # a clear block under the diagonal
        (384, (256, 256)),      # a K tail and q rows padded to the block
        (384, (128, 256)),      # the tail with q blocks that divide s
    ])
    def test_true_rows_match_mla_attend(self, b, s, blocks):
        lens = _lens(b, s)
        got, want = _both(_cfg(), b, s, lens, blocks)
        for row, n in enumerate(lens):
            assert np.abs(got[row, :n] - want[row, :n]).max() < TOL
        assert np.isfinite(got).all()

    @pytest.mark.parametrize("b,s,blocks", [(1, 256, (128, 128)),
                                            (4, 384, (256, 256))])
    def test_bf16_differs_by_a_rounding_of_the_output(self, b, s, blocks):
        """At most one bf16 step of the row's largest output apart, and
        the kernel no farther from the float32 attention than XLA's
        bf16 attention is."""
        lens = _lens(b, s)
        got, want = _both(_cfg(dtype="bfloat16", param_dtype="bfloat16"),
                          b, s, lens, blocks)
        exact = _both(_cfg(), b, s, lens, blocks)[1]
        for row, n in enumerate(lens):
            g, w, x = got[row, :n], want[row, :n], exact[row, :n]
            assert np.abs(g - w).max() <= 2 ** -7 * np.abs(w).max()
            assert np.abs(g - x).max() <= 1.25 * np.abs(w - x).max() + 1e-3

    def test_q_blocks_past_the_length_are_zeros(self):
        """Whole blocks of pad rows run nothing: zeros, not whatever
        the accumulator held — a later layer writes these rows' latents
        into the pool's last true page, where the decode kernel's
        weighted sum meets them at weight 0, so they must be finite."""
        lens = [130, 1, 256, 128]
        got, want = _both(_cfg(), 4, 384, lens, (128, 128))
        for row, n in enumerate(lens):
            first_dead = -(-n // 128) * 128
            assert not got[row, first_dead:].any()
            assert np.abs(got[row, :n] - want[row, :n]).max() < TOL
        assert got[0, 130:256].any()      # the live block's own pad rows

    def test_without_the_second_product(self):
        """No rope operands: a flash forward with a v width of its own
        (q/k 256 wide here, v 128)."""
        b, s, nh = 2, 256, 2
        keys = jax.random.split(jax.random.PRNGKey(1), 3)
        q, k = (jax.random.normal(key, (b, s, nh, 256)) for key in keys[:2])
        v = jax.random.normal(keys[2], (b, s, nh, 128))
        got = jnp.swapaxes(fp._flash_prefill_tpu(
            q.reshape(b, s, -1), k.reshape(b, s, -1),
            jnp.moveaxis(v, 1, 3).reshape(b, -1, s),
            jnp.full((b,), s, jnp.int32), heads=nh, scale=0.07,
            block_q=128, block_k=128, interpret=True), 1, 2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 0.07
        probs = jax.nn.softmax(jnp.where(ds._causal(s, s)[:, None], scores,
                                         -1e30), -1)
        want = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, -1)
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOL


class TestGate:
    @pytest.mark.parametrize("b,s,heads,qk,v,rope,enabled,engaged", [
        (1, 1024, 32, 128, 128, 128, True, True),   # 128 MiB of scores
        (4, 512, 32, 128, 128, 128, True, True),    # 128 MiB
        (4, 1024, 32, 128, 128, 128, True, True),
        (1, 1024, 32, 256, 128, 0, True, True),     # no second product
        (1, 512, 32, 128, 128, 128, True, False),   # 32 MiB: XLA's stay
        (1, 128, 32, 128, 128, 128, True, False),   # on the chip
        (4, 128, 32, 128, 128, 128, True, False),
        (1, 1024, 8, 128, 128, 128, True, False),   # few heads: 32 MiB
        (4, 1024, 32, 16, 16, 128, True, False),    # the tiny model's heads
        (4, 1024, 32, 128, 64, 128, True, False),   # v half a lane row
        (4, 1024, 32, 192, 128, 0, True, False),    # q/k in one piece: 1.5
        (4, 1024, 32, 128, 128, 64, True, False),   # the rope half unpadded
        (4, 1024, 32, 128, 128, 128, False, False),  # Pallas off / no chip
    ])
    def test_shapes_in_engaged_or_not_out(self, monkeypatch, b, s, heads, qk,
                                          v, rope, enabled, engaged):
        monkeypatch.setattr(fp, "pallas_enabled", lambda: enabled)
        assert fp.use_flash_prefill(b, s, heads, qk, v, rope) is engaged

    def test_off_the_chip_nothing_engages(self):
        assert not fp.use_flash_prefill(4, 1024, 32, 128, 128, 128)


def _prefill(params, cfg, tokens, lens, ps=16):
    b, s = tokens.shape
    pools = ds.init_paged_pools(cfg, 1 + b * (s // ps), ps)
    ptab = 1 + np.arange(b * (s // ps), dtype=np.int32).reshape(b, -1)
    return ds.prefill_paged(params, cfg, pools, jnp.asarray(tokens),
                            jnp.asarray(lens, jnp.int32), jnp.asarray(ptab))


class TestPrefillPaged:
    @pytest.mark.parametrize("dtype,tol", [("float32", TOL),
                                           ("bfloat16", 4e-3)])
    def test_kernel_forced_against_the_xla_path(self, wide, monkeypatch,
                                                dtype, tol):
        """Layer 0 writes its latents before any attention ran: its
        pages are the same bits on both paths.  The last true rows'
        logits differ as the attentions do — in float32 by the order of
        the softmax's sums (``test_deepseek_v3.py``'s TOL), in bf16 by
        roundings of each layer's attention output, which reach logits
        of deviation 0.16 at no more than what bf16 compute itself does
        to them (4e-3, ``test_bf16_compute_fails_the_tolerance``'s
        scale)."""
        params, cfg = wide
        cfg = dataclasses.replace(cfg, dtype=dtype)
        tokens = np.random.default_rng(2).integers(0, 512, (2, 64)).astype(
            np.int32)
        lens = [41, 64]
        want_logits, want_pools = _prefill(params, cfg, tokens, lens)
        monkeypatch.setattr(fp, "pallas_enabled", lambda: True)
        monkeypatch.setattr(fp, "MIN_SCORE_BYTES", 0)
        monkeypatch.setattr(fp, "BLOCKS", (32, 32))
        monkeypatch.setattr(fp, "_flash_prefill_tpu", functools.partial(
            fp._flash_prefill_tpu, interpret=True))
        before = metrics.counter(COUNTER).value
        got_logits, got_pools = _prefill(params, cfg, tokens, lens)
        assert metrics.counter(COUNTER).value == before + 2
        for got, want in zip(got_pools, want_pools):
            assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
        assert np.abs(np.asarray(got_logits)
                      - np.asarray(want_logits)).max() < tol

    def test_the_counter_rises_once_an_instance(self, wide, kernel_on):
        """A trace with the chip's branch taken holds the kernel twice —
        layer 0 and the scan's body — and counts two."""
        params, cfg = wide
        before = metrics.counter(COUNTER).value
        text = str(jax.make_jaxpr(lambda t: _prefill(
            params, cfg, t, [30, 32])[0])(jnp.zeros((2, 32), jnp.int32)))
        assert text.count("name=flash_prefill_fwd") == 2
        assert metrics.counter(COUNTER).value == before + 2

    def test_the_cpu_path_counts_nothing(self, wide):
        params, cfg = wide
        before = metrics.counter(COUNTER).value
        text = str(jax.make_jaxpr(lambda t: _prefill(
            params, cfg, t, [30, 32])[0])(jnp.zeros((2, 32), jnp.int32)))
        assert "flash_prefill_fwd" not in text
        assert metrics.counter(COUNTER).value == before

    def test_through_the_engine(self, wide, kernel_on):
        """Prefill waves with the kernel in them, decode steps behind:
        every generated position's logits against the plain reference,
        and the engine's ``stats()`` say the kernel engaged."""
        params, cfg = wide
        eng = PagedServingEngine(wide, slots=3, max_len=64, page_size=8,
                                 num_pages=25, seq_buckets=(16, 32),
                                 batch_buckets=(1, 2), capture_logits=True)
        assert eng.stats()["flash_prefill_kernel_calls"] == 0
        rng = np.random.default_rng(5)
        reqs = [eng.submit(rng.integers(0, 512, n).astype(np.int32), 4)
                for n in (7, 18, 30)]
        eng.run(max_steps=60)
        assert eng.stats()["flash_prefill_kernel_calls"] >= 2
        hp = dataclasses.asdict(cfg)
        for r in reqs:
            assert r.done and not r.failed
            history = np.concatenate([r.prompt,
                                      np.asarray(r.tokens, np.int32)])
            with jax.default_matmul_precision("highest"):
                want = np.asarray(ref.logits(params, jnp.asarray(history),
                                             hp))
            n = len(r.prompt)
            got = np.stack(r.logits)
            assert np.abs(got - want[n - 1:n - 1 + len(r.tokens)]).max() < TOL
