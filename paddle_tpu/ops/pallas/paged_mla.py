"""Paged decode attention over a LATENT pool (multi-head latent
attention, models/deepseek_v3.py): every head of a slot meets the same
cached row — ``c`` (``kv_lora_rank`` wide, key and value at once) and
``kr`` (the shared rope key) — so one page serves all heads in one
matmul, with no block-diagonal trick and no per-head reduction:

    score[h, p] = (q_abs[h] . c[p] + q_rope[h] . kr[p]) * scale
    o_lat[h]    = sum_p softmax(score)[h, p] * c[p]

The pool is two arrays, ``[L, P, ps, rank]`` and ``[L, P, ps, 128]``
(the rope half in a whole lane row, zero past its columns), taken whole
with the layer index, the page table and the lengths by scalar prefetch
exactly as ``paged_attn.py`` takes its K and V pools.

Grid: (slot, page group), as ``paged_attn_decode``'s since PR 29 (which
copies its pages itself; PERF.md section 6).  Here a step takes
``GROUP`` pages through one BlockSpec each, the same pool operand passed
``GROUP`` times, and a group past the slot's last live page re-names
that page, which the pipeline does not fetch again.  At 64 slots, 2,048
positions a slot and pages of 64 that is 64 x 4 = 256 steps a layer,
not 64 x 128.

The XLA fallback is the reference form: gather the slot's view, mask,
float32 softmax.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .paged_attn import NEG_INF, _dot_f32, _layer_pages
from .utils import HAS_PALLAS, count_paged_kernel, pallas_enabled

if HAS_PALLAS:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

GROUP = 8       # pages a grid step takes (the largest of 8, 4, 2, 1 that
                # divides the table's width)


def _ref_paged_mla(q_abs, q_rope, c_pages, r_pages, page_table, lens,
                   scale):
    """q_abs [S, nh, rank], q_rope [S, nh, rope <= 128]; c_pages /
    r_pages: ONE layer's pages [P, ps, rank] / [P, ps, 128]; the new
    token sits at position lens[s], already written.  -> [S, nh, rank]
    in q_abs's dtype."""
    S, maxP = page_table.shape
    view = maxP * c_pages.shape[1]
    f32 = jnp.float32
    cv = c_pages[page_table].reshape(S, view, -1)
    rv = r_pages[page_table].reshape(S, view, -1)[..., :q_rope.shape[-1]]
    scores = (jnp.einsum("shc,skc->shk", q_abs.astype(f32), cv.astype(f32))
              + jnp.einsum("shr,skr->shk", q_rope.astype(f32),
                           rv.astype(f32))) * scale
    mask = jnp.arange(view)[None, :] <= lens[:, None]
    scores = jnp.where(mask[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, -1)
    return jnp.einsum("shk,skc->shc", probs,
                      cv.astype(f32)).astype(q_abs.dtype)


def _mla_decode_kernel(pt_ref, lens_ref, layer_ref, qa_ref, qr_ref, *rest,
                       page_size, group, scale):
    """One grid step: ``group`` pages of one slot as ONE block of rows
    (two score products and one weighted sum a step, not ``group`` of
    each: page by page the same kernel took 733 us a call, so 406; my
    chip run, PR 28), online softmax across the slot's steps.  qa_ref
    [nh, rank], qr_ref [nh, 128]; then ``group`` c pages [ps, rank],
    ``group`` rope pages [ps, 128], the output [nh, rank] and the
    scratch (running max, running sum, accumulator)."""
    c_refs, r_refs = rest[:group], rest[group:2 * group]
    o_ref, m_scr, l_scr, acc_scr = rest[2 * group:]
    s = pl.program_id(0)
    j = pl.program_id(1)
    ln = lens_ref[s]
    first = j * group * page_size

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(first <= ln)
    def _pages():
        # a page past the last live one is a copy of it, masked below
        c = jnp.concatenate([ref[:] for ref in c_refs], 0)
        r = jnp.concatenate([ref[:] for ref in r_refs], 0)
        scr = (_dot_f32(qa_ref[:], c, 1) + _dot_f32(qr_ref[:], r, 1)) * scale
        pos = first + jax.lax.broadcasted_iota(jnp.int32, scr.shape, 1)
        scr = jnp.where(pos <= ln, scr, NEG_INF)        # [nh, group * ps]
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(scr, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scr - m_new)
        l_scr[:] = jnp.broadcast_to(
            alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        acc_scr[:] = acc_scr[:] * alpha + _dot_f32(p, c, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[:] = (acc_scr[:] / jnp.maximum(l_scr[:, :1], 1e-30)).astype(
            o_ref.dtype)


def _group_of(max_pages):
    return next(g for g in (GROUP, 4, 2, 1) if max_pages % g == 0)


def _paged_mla_tpu(q_abs, q_rope, c_pool, r_pool, page_table, lens, layer,
                   scale, interpret=False):
    S, nh, rank = q_abs.shape
    ps = c_pool.shape[2]
    lanes = r_pool.shape[3]
    maxP = page_table.shape[1]
    group = _group_of(maxP)
    pt_flat = page_table.reshape(-1).astype(jnp.int32)
    lens32 = lens.astype(jnp.int32)
    layer1 = jnp.reshape(layer, (1,)).astype(jnp.int32)
    q_rope = jnp.pad(q_rope, ((0, 0), (0, 0), (0, lanes - q_rope.shape[-1])))

    def rows(width):
        return pl.BlockSpec((None, nh, width),
                            lambda s, j, pt, ln, ly: (s, 0, 0))

    def page(width, g):
        def index(s, j, pt, ln, ly):
            # a page past the slot's last live one re-names that one:
            # same block as the step before, so nothing is fetched
            live = jnp.minimum(j * group + g, ln[s] // ps)
            return ly[0], pt[s * maxP + live], 0, 0
        return pl.BlockSpec((None, None, ps, width), index)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, maxP // group),
        in_specs=[rows(rank), rows(lanes),
                  *(page(rank, g) for g in range(group)),
                  *(page(lanes, g) for g in range(group))],
        out_specs=rows(rank),
        scratch_shapes=[
            pltpu.VMEM((nh, 128), jnp.float32),     # running max
            pltpu.VMEM((nh, 128), jnp.float32),     # running sum
            pltpu.VMEM((nh, rank), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_decode_kernel, page_size=ps, group=group,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, nh, rank), q_abs.dtype),
        name="paged_mla_decode",
        interpret=interpret,
    )(pt_flat, lens32, layer1, q_abs, q_rope,
      *([c_pool] * group), *([r_pool] * group))


def paged_mla_attention(q_abs, q_rope, c_pool, r_pool, page_table, lens,
                        layer, scale):
    """Absorbed MLA decode attention through a page table.  q_abs
    [S, nh, rank] and q_rope [S, nh, rope] (one new token per slot,
    already written into its page); c_pool / r_pool: the whole latent
    pool, [L, P, ps, rank] / [L, P, ps, 128]; page_table int32
    [S, maxP]; lens int32 [S]; layer: int32 scalar.  Returns o_lat
    [S, nh, rank].  Inference only."""
    if pallas_enabled():
        count_paged_kernel()
        return _paged_mla_tpu(q_abs, q_rope, c_pool, r_pool, page_table,
                              lens, layer, scale)
    return _ref_paged_mla(q_abs, q_rope, _layer_pages(c_pool, layer),
                          _layer_pages(r_pool, layer), page_table, lens,
                          scale)
