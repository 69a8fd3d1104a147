"""Causal flash forward for a prefill wave: ``flash_attn.py``'s online
softmax with the parameters inference wants — a value width of its own,
a second score product against a key every head shares, the rows'
lengths, no logsumexp — over operands as the projections leave them.

    score[h, i, j] = (q[h, i] . k[h, j] + q_rope[h, i] . k_rope[j]) * scale
    o[h, i]        = sum_{j <= i} softmax_j(score[h, i, :]) v[h, j]

It is the latent family's prefill attention (models/deepseek_v3.py:
``q`` / ``k`` the 128 ``nope`` columns, ``q_rope`` / ``k_rope`` the 64
rope columns in a whole 128-lane row, ``k_rope`` one row a position for
all 32 heads, ``v`` 128 wide), which XLA computed through float32
``[b, nh, s, s]`` scores in HBM: two products wrote them, five passes
re-read them (PERF.md section 6, PR 38).  Here a block of scores lives
in VMEM and dies there.

q, k and the rope operands are ``[b, s, heads * width]``, heads side by
side along the lanes as a projection's matmul writes them, and a grid
step takes one head's ``width`` lanes of a block of rows: nothing is
transposed on the way in.  That is why every per-head width must be
whole 128-lane rows (:func:`use_flash_prefill`).

**The scores are held transposed**, ``[keys, queries]``: a query's
maximum and sum are then reductions down the sublanes (elementwise
over a block's vregs, one 8-row fold at the end) and the running
maximum, sum and rescale are ROW vectors, a vreg for 128 queries.  With
queries down the sublanes every step paid two cross-lane reductions a
group of 8 queries and its column vectors filled a vreg for every 8:
at blocks of 512 that was 3.6 of a step's 3.8 us whatever the block's
width (my chip runs, PR 38).  So ``v`` comes transposed, ``[b, heads *
dv, s]`` (the expansion's matmul writes it so as readily), the
accumulator is ``[dv, queries]`` and the output leaves as ``[b, heads *
dv, s]``; the caller's output projection contracts it where it lies.

Grid (batch row, head, q block, k block), the last one carrying the
softmax's recurrence.  A k block wholly above the diagonal runs
nothing and fetches nothing (its index map repeats the last block
under the diagonal), and so does every block of a q block that lies
wholly past its row's length: a wave's pad rows (a third of the
serving cell's rows) cost a grid step each and their outputs are zeros,
which nobody reads.  Only a block the diagonal crosses pays for the
mask.  ``flash_attn.py`` is the trainer's and is not touched: nothing
here is anything the train step compiles.

Inference only: no logsumexp leaves the kernel and nothing
differentiates it.  Off the chip the callers keep their XLA attention,
which is also what the tests hold this kernel to.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attn import NEG_INF
from .utils import HAS_PALLAS, count_flash_prefill_kernel, pallas_enabled

if HAS_PALLAS:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ...framework.jax_compat import tpu_compiler_params

LANES = 128

# (block_q, block_k) rows a grid step takes.  On the chip at the kanana2
# serving cell's shapes (32 heads, bf16; tools/flash_prefill_sweep.py;
# PERF.md section 6, PR 38) the attention of a layer took, at 1 x 1024,
# 328 / 252 / 245 us at 256 x 256 / 512 x 512 / 1,024 x 1,024 and 284 -
# 300 at the uneven pairs; at 4 x 1024 with two of the four rows pad
# rows 1,178 / 883 / 966.  A step costs about 0.5 us whatever it holds
# and a block the diagonal crosses is half wasted, which pulls opposite
# ways; 512 x 512 is within 3% of the best everywhere.
BLOCKS = (512, 512)

# The float32 scores of a wave, b * heads * s * s * 4 bytes, from which
# the kernel is worth its call.  XLA keeps small temporaries on the chip
# (memory space 1 in the compiled program): at 1 x 512 (32 MiB of
# scores) its attention read 54 us a layer, the kernel 92; at 4 x 512
# and 1 x 1024 (128 MiB each) 841 and 1,022 us, the kernel 353 and 252
# (the same sweep).  The threshold lies between the two readings.
MIN_SCORE_BYTES = 64 << 20


def _fp_kernel(lens_ref, *refs, rope, scale, block_q, block_k):
    """One (batch row, head, q block, k block) step.  q_ref
    [block_q, dk], k_ref [block_k, dk], vt_ref [dv, block_k], with
    ``rope`` also qr_ref [block_q, R] and kr_ref [block_k, R]; o_ref
    [dv, block_q]; running max and sum [8, block_q] (the row vector on
    every sublane), accumulator [dv, block_q], float32."""
    if rope:
        q_ref, qr_ref, k_ref, kr_ref, vt_ref = refs[:5]
    else:
        (q_ref, k_ref, vt_ref), qr_ref, kr_ref = refs[:3], None, None
    o_ref, m_scr, l_scr, acc_scr = refs[-4:]
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def step(masked):
        contract_last = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(k_ref[:], q_ref[:], contract_last,
                                preferred_element_type=jnp.float32)
        if rope:
            s = s + jax.lax.dot_general(
                kr_ref[:], qr_ref[:], contract_last,
                preferred_element_type=jnp.float32)
        s = s * scale                                # [block_k, block_q]
        if masked:
            key = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            query = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(key <= query, s, NEG_INF)
        m_prev = m_scr[:1]                           # [1, block_q]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[:] = jnp.broadcast_to(
            alpha * l_scr[:1] + jnp.sum(p, axis=0, keepdims=True),
            l_scr.shape)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        vt = vt_ref[:]
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            vt, p.astype(vt.dtype), preferred_element_type=jnp.float32)

    # under or on the diagonal, and some row of the q block is a true
    # one.  (No mask for the keys' zero padding past s: causality hides
    # it from every true row, and block 0 ran first, so the running
    # maximum of a row a later block wholly masks is finite.)
    run = ((ki * block_k <= qi * block_q + block_q - 1)
           & (qi * block_q < lens_ref[b]))
    # every key at or under every query: no mask
    clear = (ki + 1) * block_k - 1 <= qi * block_q
    pl.when(run & clear)(functools.partial(step, False))
    pl.when(run & jnp.logical_not(clear))(functools.partial(step, True))

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        # a q block that never ran holds zeros over zero: zeros out
        o_ref[:] = (acc_scr[:] / jnp.maximum(l_scr[:1], 1e-30)).astype(
            o_ref.dtype)


def _flash_prefill_tpu(q, k, v_t, lens, *, heads, scale, q_rope=None,
                       k_rope=None, block_q=None, block_k=None,
                       interpret=False, group=1):
    """q [b, s, heads * dk]; k [b, s, heads / group * dk]; v_t [b, heads
    / group * dv, s]; -> the output TRANSPOSED, [b, heads * dv, s].
    Query head ``h`` meets key/value head ``h // group``."""
    b, s = q.shape[:2]
    dk, dv = q.shape[2] // heads, v_t.shape[1] * group // heads
    # the key/value head a query head's grid steps fetch
    kv = (lambda h: h) if group == 1 else (lambda h: h // group)
    rope = q_rope is not None
    block_q = min(block_q or BLOCKS[0], s)
    block_k = min(block_k or BLOCKS[1], s)
    # rows padded to whole blocks with zeros, so that no block holds
    # what memory held (a masked key still meets its v row)
    sq, sk = pl.cdiv(s, block_q) * block_q, pl.cdiv(s, block_k) * block_k

    def rows(x, n, axis=1):
        pad = [(0, 0)] * 3
        pad[axis] = (0, n - s)
        return x if n == s else jnp.pad(x, pad)

    def k_block(qi, ki, ln):
        """The k block a step takes: past the last one its q block
        needs it repeats that one, and a dead q block's steps all name
        block 0 — a repeated block is not fetched again."""
        last = jnp.where(qi * block_q < ln,
                         (qi * block_q + block_q - 1) // block_k, 0)
        return jnp.minimum(ki, last)

    def q_spec(width, shared=False):
        return pl.BlockSpec(
            (None, block_q, width),
            lambda b, h, qi, ki, ln: (b, qi, 0 if shared else h))

    def k_spec(width, shared=False):
        return pl.BlockSpec(
            (None, block_k, width),
            lambda b, h, qi, ki, ln: (b, k_block(qi, ki, ln[b]),
                                      0 if shared else kv(h)))

    operands = [(rows(q, sq), q_spec(dk))]
    if rope:
        operands.append((rows(q_rope, sq), q_spec(q_rope.shape[2] // heads)))
    operands.append((rows(k, sk), k_spec(dk)))
    if rope:
        operands.append((rows(k_rope, sk), k_spec(k_rope.shape[2], True)))
    operands.append((rows(v_t, sk, 2), pl.BlockSpec(
        (None, dv, block_k),
        lambda b, h, qi, ki, ln: (b, kv(h), k_block(qi, ki, ln[b])))))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, heads, sq // block_q, sk // block_k),
        in_specs=[spec for _, spec in operands],
        out_specs=pl.BlockSpec((None, dv, block_q),
                               lambda b, h, qi, ki, ln: (b, h, qi)),
        scratch_shapes=[pltpu.VMEM((8, block_q), jnp.float32),
                        pltpu.VMEM((8, block_q), jnp.float32),
                        pltpu.VMEM((dv, block_q), jnp.float32)])
    out = pl.pallas_call(
        functools.partial(_fp_kernel, rope=rope, scale=scale,
                          block_q=block_q, block_k=block_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, heads * dv, sq), q.dtype),
        compiler_params=tpu_compiler_params(pltpu, dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        name="flash_prefill_fwd",
        interpret=interpret,
    )(lens.astype(jnp.int32), *[x for x, _ in operands])
    return out[:, :, :s]


def use_flash_prefill(b, s, heads, qk_width, v_width, rope_width=0):
    """Shape gate of the compiled kernel, for a wave of ``b`` rows of
    ``s``: a head's q/k, v and rope widths are whole 128-lane rows (a
    grid step takes one head's lanes of operands that hold the heads
    side by side), and the float32 scores XLA's attention would write
    are at least ``MIN_SCORE_BYTES`` — under that they never leave the
    chip and XLA's attention is the faster."""
    if not pallas_enabled():
        return False
    return (4 * b * heads * s * s >= MIN_SCORE_BYTES
            and not any(w % LANES for w in (qk_width, v_width, rope_width)))


def flash_prefill_attention(q, k, v_t, lens, *, heads, scale, q_rope=None,
                            k_rope=None, group=1):
    """Causal attention of a prefill wave through the compiled kernel;
    the caller asked :func:`use_flash_prefill` first.  q, k
    [b, s, heads * dk]; v_t [b, heads * dv, s], the values TRANSPOSED;
    lens int32 [b]: rows at or past ``lens[b]`` are padding, and their
    outputs are not defined (whole blocks of them are zeros); q_rope
    [b, s, heads * R] and k_rope [b, s, R], or neither: the second
    score product, its key shared by the heads.  ``group`` query heads
    share a key/value head (grouped-query attention: k and v_t hold
    ``heads / group`` heads, which no copy repeats; each query head's
    grid steps fetch its group's blocks).  Returns the output
    transposed as the values came, [b, heads * dv, s], in q's dtype."""
    count_flash_prefill_kernel()
    return _flash_prefill_tpu(q, k, v_t, lens, heads=heads, scale=scale,
                              q_rope=q_rope, k_rope=k_rope, group=group)
