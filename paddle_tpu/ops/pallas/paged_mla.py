"""Paged decode attention over a LATENT pool (multi-head latent
attention, models/deepseek_v3.py): every head of a slot meets the same
cached row — ``c`` (``kv_lora_rank`` wide, key and value at once) and
``kr`` (the shared rope key) — so one page serves all heads in one
matmul, with no block-diagonal trick and no per-head reduction:

    score[h, p] = (q_abs[h] . c[p] + q_rope[h] . kr[p]) * scale
    o_lat[h]    = sum_p softmax(score)[h, p] * c[p]

The pool is ONE array ``[L, P, ps, rank + 128]``: ``c`` in lanes
``[:rank]``, the rope key in a whole lane row of its own after it, zero
past its columns — so a page is one contiguous block and one copy.  It
stays in HBM, handed over whole; the layer index, the page table and
the lengths come by scalar prefetch, exactly as ``paged_attn.py`` takes
its K and V pools.

Grid: (slot, page group), as ``paged_attn_decode``'s: a step works on
``G`` pages (:func:`group_pages`, a rule of shapes) as ONE block of
rows, which the kernel copies itself into a twice-buffered block
(:func:`page_mover`) — the pages a slot's length reaches and no others,
the next live step's under this step's arithmetic, one copy a page and
one wait a whole group.  A dead table entry is never dereferenced and a
group wholly past the length costs a grid step and nothing else.  At 64
slots, 2,048 positions a slot and pages of 64 that is 64 x 2 steps a
layer (G = 16).  What a copy costs is its issue on the scalar core, not
its bytes (PERF.md section 6): a live page takes between 1 + 1/G and 2
DMA descriptors (:func:`copy_descriptors`), where two arrays, a start
and a wait each, took 4.

The XLA fallback is the reference form: gather the slot's view, mask,
float32 softmax.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .paged_attn import (NEG_INF, _MAX_STEP_VMEM_BYTES, _dot_f32,
                         _layer_pages, _step_vmem_bytes)
from .utils import HAS_PALLAS, count_paged_kernel, pallas_enabled

if HAS_PALLAS:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu


def _ref_paged_mla(q_abs, q_rope, pages, page_table, lens, scale):
    """q_abs [S, nh, rank], q_rope [S, nh, rope <= 128]; pages: ONE
    layer's pages [P, ps, rank + 128], ``c`` in lanes ``[:rank]`` and
    the rope key from lane ``rank``; the new token sits at position
    lens[s], already written.  -> [S, nh, rank] in q_abs's dtype."""
    S, maxP = page_table.shape
    rank, rope = q_abs.shape[-1], q_rope.shape[-1]
    view = maxP * pages.shape[1]
    f32 = jnp.float32
    rows = pages[page_table].reshape(S, view, -1)
    cv, rv = rows[..., :rank], rows[..., rank:rank + rope]
    scores = (jnp.einsum("shc,skc->shk", q_abs.astype(f32), cv.astype(f32))
              + jnp.einsum("shr,skr->shk", q_rope.astype(f32),
                           rv.astype(f32))) * scale
    mask = jnp.arange(view)[None, :] <= lens[:, None]
    scores = jnp.where(mask[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, -1)
    return jnp.einsum("shk,skc->shc", probs,
                      cv.astype(f32)).astype(q_abs.dtype)


def copy_descriptors(lens, group, page_size):
    """(DMA descriptors, live pages) of one call of the kernel over the
    slots of lengths ``lens`` (host side, numpy): a slot reaches ``n =
    len // ps + 1`` pages; each is started once, and a step whose group
    is whole (``G`` live pages) waits once for all of them where a
    slot's last, partial group waits a page at a time
    (:func:`page_mover`)."""
    n = np.asarray(lens, np.int64) // page_size + 1
    waits = n // group + n % group
    return int((n + waits).sum()), int(n.sum())


def page_mover(pt_ref, lens_ref, layer_ref, pool, buf, sem, turn, *,
               group, page_size, table_width):
    """The page traffic of a (slot, page group) grid over a pool that
    stays in HBM (``paged_attn.py`` and ``paged_diff_attn.py`` still
    carry their own copy of the loop, ROADMAP.md D13).

    ``pool``: the whole pool ``[L, P, ps, width]`` (``pl.ANY``);
    ``buf``: a VMEM block ``[2, group * ps, width]``; ``sem``: DMA
    semaphores ``(2,)``, one a half; ``turn``: SMEM ``int32[1]``, which
    half holds the current step's rows.  Returns

    ``prime()``: the grid's first step calls it: zeroes the block (rows
    no copy ever reaches then hold zeros, not whatever VMEM held) and
    starts slot 0's first group into half 0;

    ``take(s, j) -> half``: a LIVE step (its first row at or under
    ``lens[s]``) calls it: starts the copies of the next live step (the
    slot's next group into the other half, or, when the slot has no
    more, the next slot's first group: every slot's first group is
    live), waits for its own, flips ``turn`` and returns the half whose
    ``buf[half]`` holds this step's rows.  Page ``g`` of the group
    lands at rows ``g * ps``: ONE copy a page, a row of the pool being
    everything the kernel reads of a position.  Only pages at or under
    the slot's length are copied, so a table entry past them is never
    dereferenced; rows past the length in the last live group are what
    the block held and must weigh zero with the caller.

    A DMA semaphore counts what arrived, so one wait the size of the
    whole half covers a whole group's ``G`` copies; the slot's last,
    partial group waits a page at a time.  A wait is never for more
    than was started into its half: the chip would wait for ever."""
    G, ps = group, page_size
    last_slot = pl.num_programs(0) - 1

    def live_pages(slot, group_j):
        """Pages of (slot, group_j) at or under the slot's length."""
        return jnp.clip(lens_ref[slot] // ps + 1 - group_j * G, 0, G)

    def start(slot, group_j, half, n):
        """Start the copies of the first ``n`` pages of (slot, group_j)
        into ``half``: one loop over the live pages (on a v5e a branch
        a table entry instead read 226 us a call where the loop read
        216)."""
        def page(g, carry):
            src = pt_ref[slot * table_width + group_j * G + g]
            at = pl.ds(pl.multiple_of(g * ps, ps), ps)
            pltpu.make_async_copy(pool.at[layer_ref[0], src],
                                  buf.at[half, at], sem.at[half]).start()
            return carry
        jax.lax.fori_loop(0, n, page, 0)

    def wait(half, n):
        """Wait for the ``n`` pages started into ``half`` (a wait needs
        the copy's size, not its source)."""
        @pl.when(n == G)
        def _whole():
            whole = buf.at[half]
            pltpu.make_async_copy(whole, whole, sem.at[half]).wait()

        @pl.when(n < G)
        def _pages():
            one = buf.at[half, pl.ds(0, ps)]

            def page(g, carry):
                pltpu.make_async_copy(one, one, sem.at[half]).wait()
                return carry
            jax.lax.fori_loop(0, n, page, 0)

    def prime():
        buf[:] = jnp.zeros_like(buf)
        turn[0] = 0
        start(0, 0, 0, live_pages(0, 0))

    def take(s, j):
        half = turn[0]
        more = (((j + 1) * G * ps <= lens_ref[s])
                & (j + 1 < pl.num_programs(1)))
        next_s = jnp.where(more, s, jnp.minimum(s + 1, last_slot))
        next_j = jnp.where(more, j + 1, 0)
        start(next_s, next_j, 1 - half,
              jnp.where(more | (s < last_slot),
                        live_pages(next_s, next_j), 0))
        wait(half, live_pages(s, j))
        turn[0] = 1 - half
        return half

    return prime, take


def _mla_decode_kernel(pt_ref, lens_ref, layer_ref, qa_ref, qr_ref, pool,
                       o_ref, buf, sem, turn, m_scr, l_scr, acc_scr, *,
                       page_size, group, table_width, scale):
    """One grid step: ``group`` pages of one slot as ONE block of rows
    (two score products and one weighted sum a step, not ``group`` of
    each: page by page the same kernel took 733 us a call, so 406; my
    chip run, PR 28), online softmax across the slot's steps.  qa_ref
    [nh, rank], qr_ref [nh, 128]; the pool whole, in HBM; the output
    [nh, rank]; the twice-buffered block of rows [2, group * ps, rank +
    128] that :func:`page_mover` fills, its semaphores and turn flag;
    the running max, running sum and accumulator.  The two score
    products stay two, on the lane-aligned views ``[:, :rank]`` and
    ``[:, rank:]`` (on a v5e one 640-wide product read 230.6 us a call
    against 225.6)."""
    s = pl.program_id(0)
    j = pl.program_id(1)
    ln = lens_ref[s]
    rank = qa_ref.shape[-1]
    first = j * group * page_size
    prime, take = page_mover(
        pt_ref, lens_ref, layer_ref, pool, buf, sem, turn, group=group,
        page_size=page_size, table_width=table_width)

    @pl.when((s == 0) & (j == 0))
    def _first():
        prime()

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # a group wholly past the length is a grid step and nothing else
    @pl.when(first <= ln)
    def _pages():
        half = take(s, j)
        c = buf[half, :, :rank]                         # [group * ps, .]
        r = buf[half, :, rank:]
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


# Rows of latents a grid step takes at most (GROUP_ROWS // page_size
# pages).  On the chip at the serving cell's shapes (64 slots x 32
# heads, rank 512 + 128, pages of 64, bf16; lengths 200..1,520) a call
# took 269 / 217 / 204 us at 256 / 512 / 1,024 rows over a table of 32
# (the parent, one BlockSpec a page: 396; least by bytes 87) and 192 /
# 151 / 139 over a table of 16 (232; 59); in the cell itself 1,024 rows
# read 14.62 ms a decode step against 14.72 at 512 (tools/
# paged_mla_sweep.py; PERF.md section 6, PR 36).  Unlike
# ``paged_attn.GROUP_ROWS`` (256) the step here is bound by what it
# does once whatever its rows — the softmax's chain of reductions and
# the copies' issue, a page at a time on the scalar core — not by its
# bytes, so the taller step wins until it sweeps dead rows.
GROUP_ROWS = 1024


def group_pages(table_width, page_size, width, itemsize, heads):
    """Pages a grid step takes: ``paged_attn.group_pages``' rule with
    the latent kernel's own ``GROUP_ROWS`` — the largest power of two
    that divides the page table's width, keeps the step's rows at or
    under ``GROUP_ROWS`` and its VMEM under the bound the other paged
    kernels keep (``paged_attn._MAX_STEP_VMEM_BYTES``); a page that is
    not whole packed tiles of its dtype (8 rows of float32, 16 of bf16)
    goes one a step.  ``width`` is a cached row's, ``c`` and the rope
    lane row side by side (rank + 128).  Shapes in, G out: nothing else
    selects it."""
    if page_size % (32 // itemsize):
        return 1
    g = 1
    while (table_width % (2 * g) == 0
           and 2 * g * page_size <= GROUP_ROWS
           and _step_vmem_bytes(2 * g, page_size, width, itemsize,
                                heads) <= _MAX_STEP_VMEM_BYTES):
        g *= 2
    return g


def _paged_mla_tpu(q_abs, q_rope, pool, page_table, lens, layer, scale,
                   interpret=False):
    S, nh, rank = q_abs.shape
    ps, width = pool.shape[2:]
    lanes = width - rank
    maxP = page_table.shape[1]
    group = group_pages(maxP, ps, width, pool.dtype.itemsize, nh)
    pt_flat = page_table.reshape(-1).astype(jnp.int32)
    lens32 = lens.astype(jnp.int32)
    layer1 = jnp.reshape(layer, (1,)).astype(jnp.int32)
    q_rope = jnp.pad(q_rope, ((0, 0), (0, 0), (0, lanes - q_rope.shape[-1])))

    def rows(w):
        return pl.BlockSpec((None, nh, w),
                            lambda s, j, pt, ln, ly: (s, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, maxP // group),
        in_specs=[rows(rank), rows(lanes),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=rows(rank),
        scratch_shapes=[
            # the group's rows, c and the rope lane row, twice buffered
            pltpu.VMEM((2, group * ps, width), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),            # which half is current
            pltpu.VMEM((nh, 128), jnp.float32),     # running max
            pltpu.VMEM((nh, 128), jnp.float32),     # running sum
            pltpu.VMEM((nh, rank), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_decode_kernel, page_size=ps, group=group,
                          table_width=maxP, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, nh, rank), q_abs.dtype),
        name="paged_mla_decode",
        interpret=interpret,
    )(pt_flat, lens32, layer1, q_abs, q_rope, pool)


def _use_pallas_mla(pool, rank, nh):
    """Shape gate of the compiled kernel, ``paged_attn``'s: the rope
    key's lanes start a whole 128-lane row into the pool's row (a
    ``kv_lora_rank`` that is not a multiple of 128 falls back), and the
    one-page step stays under the VMEM bound (:func:`group_pages` takes
    more only under the same bound)."""
    if not pallas_enabled():
        return False
    ps, width = pool.shape[2:]
    return rank % 128 == 0 and _step_vmem_bytes(
        1, ps, width, pool.dtype.itemsize, nh) <= _MAX_STEP_VMEM_BYTES


def paged_mla_attention(q_abs, q_rope, pool, page_table, lens, layer,
                        scale):
    """Absorbed MLA decode attention through a page table.  q_abs
    [S, nh, rank] and q_rope [S, nh, rope] (one new token per slot,
    already written into its page); pool: the whole latent pool
    [L, P, ps, rank + 128] (``c``, then the rope key in a lane row of
    its own, zero past its columns); page_table int32 [S, maxP]; lens
    int32 [S]; layer: int32 scalar.  Returns o_lat [S, nh, rank].
    Inference only."""
    if _use_pallas_mla(pool, q_abs.shape[-1], q_abs.shape[1]):
        count_paged_kernel()
        return _paged_mla_tpu(q_abs, q_rope, pool, page_table, lens, layer,
                              scale)
    return _ref_paged_mla(q_abs, q_rope, _layer_pages(pool, layer),
                          page_table, lens, scale)
