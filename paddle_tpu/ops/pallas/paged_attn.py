"""Paged-attention decode kernel (ISSUE 8): single-token queries gather
K/V through a block page table instead of a contiguous per-slot strip.

Extends flash_attn.py's blocked online-softmax scaffolding to the paged
KV layout the serving engine owns: K/V live in ONE pool per engine,
``[L, num_pages, page_size, nh * hd]`` (models/gpt.py::init_paged_pools:
a page is ``page_size`` rows of all heads side by side, so the minor
axis is a multiple of the 128 lanes and the device keeps the array
major-to-minor with no padding), and each decode lane's logical sequence
is the concatenation of the pages its table names.  The TPU kernel takes
the WHOLE pool and the layer index, which stay in HBM, and works on a
GROUP of physical pages per grid step as one block of rows — layer,
page table and lengths come through scalar prefetch, and the kernel
copies exactly the pages a lane's length reaches, the next step's under
this step's arithmetic: the pool is never sliced, transposed or copied
around the call, and a dead table entry is never read.

A pure-lax fallback (gather pages into the contiguous per-slot view,
then the exact `_slot_block` masked-attention math) serves
``JAX_PLATFORMS=cpu``.  The Pallas kernel is checked three ways: for
results in interpret mode (tests/test_paged_serving.py,
test_quant_serving.py), for the chip's compiler by AOT compiles at the
served widths (tests/test_chip_compile.py), and on the chip against the
float32 model by ``chip_smoke.py``, which also reads
``serving.paged_kernel_calls`` to see that it engaged.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .utils import (HAS_PALLAS, count_dequant_kernel, count_paged_kernel,
                    pallas_enabled)

if HAS_PALLAS:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ref_paged_attention(q, k_pages, v_pages, page_table, lens):
    """Lax fallback: gather each slot's pages into its contiguous view
    and run the slot-batched masked attention — the SAME math (shapes,
    mask constant, fp32 softmax) as models/gpt.py::_slot_block, so the
    paged engine's logits match the slot-contiguous engine bit-for-bit
    when the view width equals max_len.

    q: [S, 1, nh, hd]; k/v_pages: ONE layer's pages, [P, ps, nh * hd]
    as the pool stores them (or [P, ps, nh, hd]: the same bytes);
    page_table: int32 [S, maxP]; lens: int32 [S] (the new token sits at
    position lens[s], already scattered into its page).  Returns
    [S, 1, nh, hd].  Pages of fewer K/V heads than ``nh`` are read by
    groups of query heads (:func:`_ref_paged_gqa`)."""
    S, maxP = page_table.shape
    ps = k_pages.shape[1]
    nh, hd = q.shape[2:]
    cd = q.dtype
    view = maxP * ps
    nkv = math.prod(k_pages.shape[2:]) // hd
    if nkv != nh:
        return _ref_paged_gqa(q, k_pages, v_pages, page_table, lens, nkv)
    kc = k_pages[page_table].reshape(S, view, nh, hd)
    vc = v_pages[page_table].reshape(S, view, nh, hd)
    logits = jnp.einsum("sqhd,skhd->shqk", q.astype(jnp.float32),
                        kc.astype(jnp.float32)) / math.sqrt(hd)
    mask = jnp.arange(view)[None, :] <= lens[:, None]       # [S, view]
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, -1).astype(cd)
    return jnp.einsum("shqk,skhd->sqhd", probs, vc.astype(cd))


def _ref_paged_gqa(q, k_pages, v_pages, page_table, lens, nkv):
    """:func:`_ref_paged_attention` for grouped-query heads: query head
    ``i`` of ``nh`` reads K/V head ``i // (nh / nkv)``."""
    S, maxP = page_table.shape
    nh, hd = q.shape[2:]
    g = nh // nkv
    view = maxP * k_pages.shape[1]
    kc = k_pages[page_table].reshape(S, view, nkv, hd)
    vc = v_pages[page_table].reshape(S, view, nkv, hd)
    qg = q.reshape(S, nkv, g, hd).astype(jnp.float32)
    logits = jnp.einsum("sngd,sknd->sngk", qg,
                        kc.astype(jnp.float32)) / math.sqrt(hd)
    mask = jnp.arange(view)[None, :] <= lens[:, None]       # [S, view]
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, -1).astype(q.dtype)
    out = jnp.einsum("sngk,sknd->sngd", probs, vc.astype(q.dtype))
    return out.reshape(S, 1, nh, hd)


def _head_mask(nh, hd):
    """bool [nh, nh * hd]: row h is true over head h's ``hd`` columns of
    the merged page axis."""
    row = jax.lax.broadcasted_iota(jnp.int32, (nh, nh * hd), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (nh, nh * hd), 1)
    return (col >= row * hd) & (col < (row + 1) * hd)


def _dot_f32(a, b, contract_b):
    """``a [m, k] . b`` contracting b's axis ``contract_b``, float32
    products and accumulation on the MXU.  bf16 operands multiply
    exactly there (8 x 8 significant bits), so they go in as stored; a
    float32 ``a`` (the softmax weights) against a bf16 ``b`` is split
    into three bf16 terms that sum back to it to 2**-24, stacked on the
    row axis so one pass carries all three; anything else runs at
    ``HIGHEST``.  No product is ever rounded to bf16."""
    dims = (((1,), (contract_b,)), ((), ()))
    bf16 = jnp.bfloat16
    if a.dtype == bf16 and b.dtype == bf16:
        return jax.lax.dot_general(a, b, dims,
                                   preferred_element_type=jnp.float32)
    if b.dtype == bf16:
        m = a.shape[0]
        hi = a.astype(bf16)
        rest = a - hi.astype(jnp.float32)
        mid = rest.astype(bf16)
        lo = (rest - mid.astype(jnp.float32)).astype(bf16)
        out = jax.lax.dot_general(jnp.concatenate([hi, mid, lo], 0), b,
                                  dims, preferred_element_type=jnp.float32)
        return out[:m] + out[m:2 * m] + out[2 * m:]
    return jax.lax.dot_general(a.astype(jnp.float32),
                               b.astype(jnp.float32), dims,
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST)


def _paged_decode_kernel(pt_ref, lens_ref, layer_ref, q_ref, *rest,
                         page_size, head_dim, group, table_width, quant,
                         q_group=1):
    """Grid (slot, page group).  A step works on ``group`` physical
    pages of K and of V as ONE block of rows, [group * ps, C], online
    softmax across a lane's groups exactly like flash_attn's streamed
    K-blocks.  q_ref: [1, C] with C = nh * hd, every head's query side
    by side.  The K and V pools stay in HBM (``pl.ANY``) and the kernel
    copies the pages itself, each to its rows of a twice-buffered
    block: a live step first starts the copies of the NEXT live step
    (the slot's next group, or the next slot's first), then waits for
    its own, so the copies run under the arithmetic; only pages at or
    under the slot's length are copied, a dead table entry is never
    dereferenced, and a group wholly past the length costs a grid step
    and nothing else.  (One BlockSpec a page instead pays its index map
    and the pipeline's bookkeeping for every entry of the table, live
    or not: 0.41 ms of a 0.65 ms call; PERF.md section 6, PR 29.)  Rows
    past the length in the last live group are whatever the buffer held
    — pool rows, or the zeros it starts with — and weigh exactly zero.
    With ``quant`` the pages are int8 and their fp32 scale rows follow
    the pools as ``group`` [ps, nh] blocks of K's and ``group`` of V's:
    32 heads on the minor axis is no shape a copy out of HBM takes, so
    those come through BlockSpecs (a page past the last live one
    re-names that one).

    The per-head reduction is the block-diagonal trick: the slot's query
    row is spread into ``qbd [nh, C]`` (row h holds head h's query over
    its own hd columns and zeros elsewhere), so ``qbd . rows^T`` is
    flash attention's NT matmul and gives scores[h, p] with every
    off-head product an exact zero; ``p . rows_v`` gives [nh, C] of
    which row h's own hd columns are head h's output, and the rest is
    dropped at the end.  The MXU does nh times the needed products of a
    matrix-VECTOR problem it would otherwise idle through, and nothing
    is reshaped or moved between lanes and sublanes, whatever the head
    split.  Products and sums are float32 (:func:`_dot_f32`); int8 rows
    widen to bf16 exactly, and their scales are [rows, nh] like the
    transposed scores and probabilities, so they fold in there
    (``q . (k*s) == (q . k) * s``) and the pages are never dequantized
    elementwise.

    Grouped-query pages (``q_group`` > 1 query heads a K/V head; no int8
    form) take no block-diagonal query: q_ref is [nh, hd], and each K/V
    head's ``hd`` lanes of the rows meet its ``q_group`` query rows in
    a ``[q_group, hd] x [hd, rows]`` product, so the MXU does the
    needed products only; the accumulator is [nh, hd] and leaves as
    the output."""
    G, ps = group, page_size
    pools, rest = rest[:2], rest[2:]
    if quant:
        ks_refs, vs_refs, rest = rest[:G], rest[G:2 * G], rest[2 * G:]
    if q_group > 1:
        o_ref, k_buf, v_buf, sem, turn, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, k_buf, v_buf, sem, turn, qbd_scr, m_scr, l_scr, acc_scr = rest
    bufs = (k_buf, v_buf)
    s = pl.program_id(0)
    j = pl.program_id(1)
    nh, C = acc_scr.shape
    first = j * G * ps
    ln = lens_ref[s]

    def copies(slot_s, group_j, buf, start):
        """Start, or wait for, the copy of each live page of
        (slot_s, group_j) into half ``buf`` of the block buffers."""
        last = lens_ref[slot_s] // ps
        for g in range(G):
            entry = group_j * G + g

            @pl.when(entry <= last)
            def _page(g=g, entry=entry):
                # a wait needs the copy's size, not its source
                page = pt_ref[slot_s * table_width + entry] if start else 0
                for i, (pool, rows) in enumerate(zip(pools, bufs)):
                    copy = pltpu.make_async_copy(
                        pool.at[layer_ref[0], page],
                        rows.at[buf, pl.ds(g * ps, ps)], sem.at[buf, i])
                    copy.start() if start else copy.wait()

    @pl.when((s == 0) & (j == 0))
    def _first():
        for rows in bufs:
            rows[:] = jnp.zeros_like(rows)
        turn[0] = 0
        copies(0, 0, 0, start=True)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)
        if q_group == 1:
            q = jnp.broadcast_to(q_ref[:].astype(jnp.float32), (nh, C))
            qbd_scr[:] = jnp.where(_head_mask(nh, head_dim), q,
                                   0.0).astype(qbd_scr.dtype)

    def by_kv_head(a, rows, contract):
        """``_dot_f32`` of each K/V head's ``q_group`` rows of ``a``
        against its ``hd`` lanes of ``rows`` (a buffer half), stacked
        back in query-head order."""
        hd, g = head_dim, q_group
        return jnp.concatenate([
            _dot_f32(a[h * g:(h + 1) * g], rows[:, pl.ds(h * hd, hd)],
                     contract)
            for h in range(nh // g)], 0)

    # groups entirely past the fill bound contribute nothing; skipping
    # them is the paged analogue of the causal block skip
    @pl.when(first <= ln)
    def _body():
        buf = turn[0]
        more = (first + G * ps <= ln) & (j + 1 < pl.num_programs(1))

        @pl.when(more)
        def _next_group():
            copies(s, j + 1, 1 - buf, start=True)

        @pl.when(jnp.logical_not(more) & (s + 1 < pl.num_programs(0)))
        def _next_slot():
            copies(s + 1, 0, 1 - buf, start=True)

        copies(s, j, buf, start=False)
        turn[0] = 1 - buf

        if q_group > 1:
            # scores[i, p] = q[i, :] . k[p, i // q_group, :]
            scr = by_kv_head(q_ref[:], k_buf.at[buf], 1) / math.sqrt(
                head_dim)
        else:
            k, v = k_buf[buf], v_buf[buf]                # [G * ps, C]
            if quant:
                k = k.astype(jnp.bfloat16)
                v = v.astype(jnp.bfloat16)
            # scores[h, p] = q[h, :] . k[p, h, :]
            scr = _dot_f32(qbd_scr[:], k, 1) / math.sqrt(head_dim)
        if quant:
            scr = scr * jnp.concatenate([r[:] for r in ks_refs], 0).T
        pos = first + jax.lax.broadcasted_iota(jnp.int32, scr.shape, 1)
        scr = jnp.where(pos <= ln, scr, NEG_INF)

        m_prev = m_scr[:, :1]                            # [nh, 1]
        m_new = jnp.maximum(m_prev, jnp.max(scr, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scr - m_new)                         # [nh, G * ps]
        l_scr[:] = jnp.broadcast_to(
            alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        if q_group > 1:
            # acc[i, d] += sum_p p[i, p] * v[p, i // q_group, d]
            acc_scr[:] = acc_scr[:] * alpha + by_kv_head(p, v_buf.at[buf],
                                                         0)
            return
        if quant:
            p = p * jnp.concatenate([r[:] for r in vs_refs], 0).T
        # acc[h, h*hd + d] += sum_p p[h, p] * v[p, h*hd + d]
        acc_scr[:] = acc_scr[:] * alpha + _dot_f32(p, v, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        l = jnp.maximum(l_scr[:, :1], 1e-30)             # [nh, 1]
        if q_group > 1:
            o_ref[:] = (acc_scr[:] / l).astype(o_ref.dtype)
            return
        own = jnp.where(_head_mask(nh, head_dim), acc_scr[:] / l, 0.0)
        o_ref[:] = jnp.sum(own, axis=0, keepdims=True).astype(o_ref.dtype)


# The largest grid step the AOT compiles for a described v5e have been
# shown to fit in scoped VMEM, by :func:`_step_vmem_bytes`' count: 32
# heads x 256 at 64 positions of a float32 pool, one page a step.
# Nothing larger has been tried, so nothing larger is admitted.
_MAX_STEP_VMEM_BYTES = (4 * 32 * 4 + 4 * 64 * 4) * 32 * 256

# Rows of the merged axis a grid step takes at most (GROUP_ROWS //
# page_size pages).  On the chip at the benchmark cell's shapes a call
# took 424 / 304 / 280 / 285 us at 64 / 128 / 256 / 512 rows (1,405 one
# page a step; least by bytes 237), and 256 was first or within 4% of
# it at 16 x 128 heads, pages of 32, int8 and one rank of tp=4 (PERF.md
# section 6, PR 29).
GROUP_ROWS = 256


def _step_vmem_bytes(group, page_size, width, itemsize, heads):
    """What one grid step keeps in VMEM, as ``_use_pallas_paged`` counts
    it: the K and V blocks twice buffered (4 x rows at the pool's
    itemsize) and the kernel's float32 working rows of the merged axis
    (the accumulator and the three-term p . v product, 4 x heads)."""
    return (4 * heads * 4 + 4 * group * page_size * itemsize) * width


def group_pages(table_width, page_size, width, itemsize, heads):
    """Pages a grid step takes: the largest power of two that divides
    the page table's width, keeps the step's rows at or under
    ``GROUP_ROWS`` and its VMEM under ``_MAX_STEP_VMEM_BYTES``.  A page
    that is not whole packed tiles of its dtype (8 rows of float32, 16
    of bf16, 32 of int8) would land inside a tile of the block, and
    goes one a step.  ``width`` and ``heads`` are what one 'tp' rank
    holds.  Shapes in, G out: nothing else selects it."""
    if page_size % (32 // itemsize):
        return 1
    g = 1
    while (table_width % (2 * g) == 0
           and 2 * g * page_size <= GROUP_ROWS
           and _step_vmem_bytes(2 * g, page_size, width, itemsize,
                                heads) <= _MAX_STEP_VMEM_BYTES):
        g *= 2
    return g


def _paged_call(q, pools, scales, page_table, lens, layer,
                interpret=False):
    """The one ``pallas_call`` both pools go through.  ``pools`` is the
    engine's whole (k, v) pool, [L, P, ps, nh * hd] each; ``scales`` is
    () for the fp pool and the layer's (k_scale, v_scale), [P, ps, nh],
    for int8.  Page table, lengths and layer index ride the
    scalar-prefetch channel; the pools are handed over where they lie
    and the kernel copies G pages a grid step (:func:`group_pages`) out
    of them, so the pool is never sliced, transposed or copied around
    the call; an int8 page's scale rows come through G BlockSpecs over
    each scale array."""
    S, T, nh, hd = q.shape
    assert T == 1, "paged decode kernel is single-token"
    ps, C = pools[0].shape[2:]
    assert C % hd == 0 and nh % (C // hd) == 0, (pools[0].shape, q.shape)
    q_group = nh // (C // hd)
    maxP = page_table.shape[1]
    G = group_pages(maxP, ps, C, pools[0].dtype.itemsize, nh)
    pt_flat = page_table.reshape(-1).astype(jnp.int32)
    lens32 = lens.astype(jnp.int32)
    layer1 = jnp.reshape(layer, (1,)).astype(jnp.int32)
    quant = len(scales) > 0
    assert q_group == 1 or not quant, "grouped-query pages are bf16 or fp32"

    def scale_rows(g):
        def index(s, j, pt, ln, ly):
            return pt[s * maxP + jnp.minimum(j * G + g, ln[s] // ps)], 0, 0
        return pl.BlockSpec((None, ps, nh), index)

    # a slot's queries: one row of every head side by side, or (grouped
    # query heads) a row a head
    rows_q, width_q = (1, C) if q_group == 1 else (nh, hd)
    row = pl.BlockSpec((None, rows_q, width_q),
                       lambda s, j, pt, ln, ly: (s, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, maxP // G),
        in_specs=[row] + [pl.BlockSpec(memory_space=pl.ANY)] * 2
        + [scale_rows(g) for _ in scales for g in range(G)],
        out_specs=row,
        scratch_shapes=[
            # the group's rows of K and of V, twice buffered
            *[pltpu.VMEM((2, G * ps, C), pools[0].dtype)] * 2,
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),         # which half is current
            *([pltpu.VMEM((nh, C), q.dtype)]     # block-diagonal query
              if q_group == 1 else []),
            pltpu.VMEM((nh, 128), jnp.float32),  # running max, lanes equal
            pltpu.VMEM((nh, 128), jnp.float32),  # running sum, lanes equal
            pltpu.VMEM((nh, width_q), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, page_size=ps, head_dim=hd,
                          group=G, table_width=maxP, quant=quant,
                          q_group=q_group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, rows_q, width_q), q.dtype),
        name="paged_attn_decode",
        interpret=interpret,
    )(pt_flat, lens32, layer1, q.reshape(S, rows_q, width_q), *pools,
      *(x for x in scales for _ in range(G)))
    return out.reshape(S, 1, nh, hd)


def _over_heads(mesh, q, pools, scales, page_table, lens, layer,
                interpret=False):
    """:func:`_paged_call`, run per 'tp' shard of the head axis under a
    mesh.  The tensor-parallel engine is a GSPMD ``jit`` over
    head-sharded pools, and the partitioner cannot split a Mosaic call
    ("Mosaic kernels cannot be automatically partitioned"), so the
    kernel is wrapped in a ``shard_map``: each rank runs it on its own
    nh/tp heads of every page — a contiguous column range of the merged
    axis — with the page table, lengths and layer index replicated.
    Heads are axis 2 of q and of a layer's int8 scale rows, and the last
    axis (3) of the pools."""
    def fn(q, *rest):
        return _paged_call(q, rest[:2], rest[2:-3], *rest[-3:],
                           interpret=interpret)

    args = (q, *pools, *scales, page_table, lens, layer)
    if mesh is None:
        return fn(*args)
    from ...framework.jax_compat import partition_spec as P, shard_map
    heads = P(None, None, "tp")
    pool = P(None, None, None, "tp")
    return shard_map(fn, mesh=mesh,
                     in_specs=((heads,) + (pool,) * len(pools)
                               + (heads,) * len(scales)
                               + (P(),) * 3),
                     out_specs=heads, check_vma=False)(*args)


def _use_pallas_paged(k_pool, nh, mesh=None):
    """Shape gate of the compiled kernel, on what one rank holds.
    Mosaic takes every page the sweep tried (1..32 heads x 16..256,
    page_size 4..64, bf16, fp32 and int8 pools) whose merged axis is
    whole 128-lane rows — a narrower page is no shape a copy out of HBM
    takes — which leaves the VMEM bound at one page a step
    (:func:`group_pages` takes more only under the same bound)."""
    if not pallas_enabled():
        return False
    ps, C = k_pool.shape[2:]
    if mesh is not None:
        nh //= mesh.shape["tp"]
        C //= mesh.shape["tp"]
    return C % 128 == 0 and _step_vmem_bytes(
        1, ps, C, k_pool.dtype.itemsize, nh) <= _MAX_STEP_VMEM_BYTES


def _layer_pages(pool, layer):
    """One layer of a pool or of an int8 scale array, [P, ...]."""
    return jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)


def _ref_paged_attention_quant(q, k_pages, k_scale, v_pages, v_scale,
                               page_table, lens):
    """Lax fallback over ONE layer of the int8 pool: dequantize
    (``q_int8 * scale`` per position per head, staying fp32 like the fp
    path's score math) and delegate to :func:`_ref_paged_attention` —
    ONE copy of the gather/mask/softmax semantics to keep in sync.
    k/v_pages: [P, ps, nh * hd] int8; k/v_scale: [P, ps, nh] fp32."""
    nh = k_scale.shape[-1]

    def deq(pages, scale):
        heads = pages.reshape(*scale.shape, -1).astype(jnp.float32)
        return (heads * scale[..., None]).reshape(pages.shape)

    assert k_pages.shape[-1] % nh == 0, (k_pages.shape, k_scale.shape)
    return _ref_paged_attention(q, deq(k_pages, k_scale),
                                deq(v_pages, v_scale), page_table, lens)


def paged_attention(q, pools, page_table, lens, layer, mesh=None):
    """Decode attention through a page table.  q: [S, 1, nh, hd] (one
    new token per slot, already scattered into its page); ``pools``:
    the whole pool in the engine's operand order — (k, v), each
    [L, P, ps, nh * hd], or for the int8 pool (k, k_scale, v, v_scale)
    with per-position-per-head fp32 scales [L, P, ps, nh], which fold
    into the scores and the probabilities on read (in-kernel on TPU);
    page_table: int32 [S, maxP]; lens: int32 [S]; layer: int32 scalar,
    which layer's pages to read.  Returns [S, 1, nh, hd].
    Inference-only (no custom VJP): the decode step never
    differentiates.  ``mesh``: the serving mesh when the caller is a
    GSPMD program over head-sharded pools (:func:`_over_heads`);
    callers already inside a ``shard_map`` pass their local shards and
    no mesh.  The kernel takes the pages whole and the scales as the
    layer's slice: 32 heads on the minor axis is a layout the device
    stores page-minor, so that slice (4 / hd of a layer's page bytes)
    is the one thing still relaid out for the call (PERF.md section
    7)."""
    pages, scales = ((pools, ()) if len(pools) == 2
                     else (pools[::2], pools[1::2]))
    scales = tuple(_layer_pages(s, layer) for s in scales)
    if _use_pallas_paged(pages[0], q.shape[2], mesh):
        count_paged_kernel()
        if scales:
            count_dequant_kernel("paged_attn")
        return _over_heads(mesh, q, pages, scales, page_table, lens, layer)
    k, v = (_layer_pages(p, layer) for p in pages)
    if scales:
        return _ref_paged_attention_quant(q, k, scales[0], v, scales[1],
                                          page_table, lens)
    return _ref_paged_attention(q, k, v, page_table, lens)
