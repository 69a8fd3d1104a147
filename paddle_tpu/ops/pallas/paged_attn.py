"""Paged-attention decode kernel (ISSUE 8): single-token queries gather
K/V through a block page table instead of a contiguous per-slot strip.

Extends flash_attn.py's blocked online-softmax scaffolding to the paged
KV layout the serving engine owns: K/V live in ONE pool per engine,
``[L, num_pages, page_size, nh * hd]`` (models/gpt.py::init_paged_cache:
a page is ``page_size`` rows of all heads side by side, so the minor
axis is a multiple of the 128 lanes and the device keeps the array
major-to-minor with no padding), and each decode lane's logical sequence
is the concatenation of the pages its table names.  The TPU kernel takes
the WHOLE pool and the layer index and streams one *physical page* per
grid step — layer and page id come through scalar prefetch, so the
BlockSpec index map turns the logical ``(slot, page_j)`` coordinate into
the physical page's HBM block and Mosaic DMAs exactly the pages a lane
references: the pool is never sliced, transposed or copied around the
call.

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
    [S, 1, nh, hd]."""
    S, maxP = page_table.shape
    ps = k_pages.shape[1]
    nh, hd = q.shape[2:]
    cd = q.dtype
    view = maxP * ps
    kc = k_pages[page_table].reshape(S, view, nh, hd)
    vc = v_pages[page_table].reshape(S, view, nh, hd)
    logits = jnp.einsum("sqhd,skhd->shqk", q.astype(jnp.float32),
                        kc.astype(jnp.float32)) / math.sqrt(hd)
    mask = jnp.arange(view)[None, :] <= lens[:, None]       # [S, view]
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, -1).astype(cd)
    return jnp.einsum("shqk,skhd->sqhd", probs, vc.astype(cd))


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


def _paged_decode_kernel(pt_ref, lens_ref, layer_ref, q_ref, k_ref, *rest,
                         page_size, head_dim, quant):
    """Grid (slot, page_j).  One physical page of K/V per step, online
    softmax across a lane's pages exactly like flash_attn's streamed
    K-blocks.  q_ref: [1, C] with C = nh * hd, every head's query side
    by side; k_ref/v_ref: [ps, C] — the page the scalar-prefetched table
    names for this (slot, j) in the layer ``layer_ref`` names, as the
    pool stores it.  With ``quant`` the pages are int8 and each is
    followed by its [ps, nh] fp32 scale block (HBM traffic per page is
    1 byte/element plus the scale row).

    The per-head reduction is the block-diagonal trick: the slot's query
    row is spread into ``qbd [nh, C]`` (row h holds head h's query over
    its own hd columns and zeros elsewhere), so ``qbd . page^T`` is
    flash attention's NT matmul and gives scores[h, p] with every
    off-head product an exact zero; ``p . page_v`` gives [nh, C] of
    which row h's own hd columns are head h's output, and the rest is
    dropped at the end.  The MXU does nh times the needed products of a
    matrix-VECTOR problem it would otherwise idle through, and nothing
    is reshaped or moved between lanes and sublanes, whatever the head
    split.  Products and sums are float32 (:func:`_dot_f32`); int8 pages
    widen to bf16 exactly, and their scales are [ps, nh] like the
    transposed scores and probabilities, so they fold in there
    (``q . (k*s) == (q . k) * s``) and the pages are never dequantized
    elementwise."""
    if quant:
        ks_ref, v_ref, vs_ref, o_ref, qbd_scr, m_scr, l_scr, acc_scr = rest
    else:
        v_ref, o_ref, qbd_scr, m_scr, l_scr, acc_scr = rest
    s = pl.program_id(0)
    j = pl.program_id(1)
    nh, C = acc_scr.shape

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)
        q = jnp.broadcast_to(q_ref[:].astype(jnp.float32), (nh, C))
        qbd_scr[:] = jnp.where(_head_mask(nh, head_dim), q,
                               0.0).astype(qbd_scr.dtype)

    ln = lens_ref[s]
    # pages entirely past the fill bound contribute nothing; skipping
    # them is the paged analogue of the causal block skip
    @pl.when(j * page_size <= ln)
    def _body():
        k = k_ref[:]                                     # [ps, C]
        v = v_ref[:]
        if quant:
            k = k.astype(jnp.bfloat16)
            v = v.astype(jnp.bfloat16)
        # scores[h, p] = q[h, :] . k[p, h, :]
        scr = _dot_f32(qbd_scr[:], k, 1) / math.sqrt(head_dim)
        if quant:
            scr = scr * ks_ref[:].T
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, scr.shape, 1)
        scr = jnp.where(pos <= ln, scr, NEG_INF)

        m_prev = m_scr[:, :1]                            # [nh, 1]
        m_new = jnp.maximum(m_prev, jnp.max(scr, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scr - m_new)                         # [nh, ps]
        l_scr[:] = jnp.broadcast_to(
            alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        if quant:
            p = p * vs_ref[:].T
        # acc[h, h*hd + d] += sum_p p[h, p] * v[p, h*hd + d]
        acc_scr[:] = acc_scr[:] * alpha + _dot_f32(p, v, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        l = jnp.maximum(l_scr[:, :1], 1e-30)             # [nh, 1]
        own = jnp.where(_head_mask(nh, head_dim), acc_scr[:] / l, 0.0)
        o_ref[:] = jnp.sum(own, axis=0, keepdims=True).astype(o_ref.dtype)


def _paged_call(q, pools, scales, page_table, lens, layer, interpret):
    """The one ``pallas_call`` both pools go through.  ``pools`` is the
    engine's whole (k, v) pool, [L, P, ps, nh * hd] each; ``scales`` is
    () for the fp pool and the layer's (k_scale, v_scale), [P, ps, nh],
    for int8.  Page table, lengths and layer index ride the
    scalar-prefetch channel so BlockSpec index maps can translate
    logical page coordinates into physical pool blocks before the DMA is
    issued: each grid step DMAs exactly one page (and, for int8, its
    scale rows) out of the pool where it lies."""
    S, T, nh, hd = q.shape
    assert T == 1, "paged decode kernel is single-token"
    ps, C = pools[0].shape[2:]
    assert C == nh * hd, (pools[0].shape, q.shape)
    maxP = page_table.shape[1]
    pt_flat = page_table.reshape(-1).astype(jnp.int32)
    lens32 = lens.astype(jnp.int32)
    layer1 = jnp.reshape(layer, (1,)).astype(jnp.int32)
    quant = bool(scales)

    row = pl.BlockSpec((None, 1, C), lambda s, j, pt, ln, ly: (s, 0, 0))
    page = pl.BlockSpec(
        (None, None, ps, C),
        lambda s, j, pt, ln, ly: (ly[0], pt[s * maxP + j], 0, 0))
    scale = pl.BlockSpec(
        (None, ps, nh), lambda s, j, pt, ln, ly: (pt[s * maxP + j], 0, 0))
    if quant:
        operands = (pools[0], scales[0], pools[1], scales[1])
        specs = [page, scale, page, scale]
    else:
        operands, specs = pools, [page, page]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, maxP),
        in_specs=[row, *specs],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((nh, C), q.dtype),        # block-diagonal query
            pltpu.VMEM((nh, 128), jnp.float32),  # running max, lanes equal
            pltpu.VMEM((nh, 128), jnp.float32),  # running sum, lanes equal
            pltpu.VMEM((nh, C), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, page_size=ps, head_dim=hd,
                          quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, 1, C), q.dtype),
        name="paged_attn_decode",
        interpret=interpret,
    )(pt_flat, lens32, layer1, q.reshape(S, 1, C), *operands)
    return out.reshape(S, 1, nh, hd)


def _over_heads(fn, mesh, q, pools, scales, *table_lens_layer):
    """``fn(q, *pools, *scales, page_table, lens, layer)``, run per 'tp'
    shard of the head axis under a mesh.  The tensor-parallel engine is
    a GSPMD ``jit`` over head-sharded pools, and the partitioner cannot
    split a Mosaic call ("Mosaic kernels cannot be automatically
    partitioned"), so the kernel is wrapped in a ``shard_map``: each
    rank runs it on its own nh/tp heads of every page — a contiguous
    column range of the merged axis — with the page table, lengths and
    layer index replicated.  Heads are axis 2 of q and of a layer's
    int8 scale rows, and the last axis (3) of the pools."""
    args = (q, *pools, *scales, *table_lens_layer)
    if mesh is None:
        return fn(*args)
    from ...framework.jax_compat import partition_spec as P, shard_map
    heads = P(None, None, "tp")
    pool = P(None, None, None, "tp")
    return shard_map(fn, mesh=mesh,
                     in_specs=((heads,) + (pool,) * len(pools)
                               + (heads,) * len(scales)
                               + (P(),) * len(table_lens_layer)),
                     out_specs=heads, check_vma=False)(*args)


def _paged_attention_tpu(q, k_pool, v_pool, page_table, lens, layer,
                         interpret=False):
    """q: [S, 1, nh, hd] -> [S, 1, nh, hd] through the Pallas kernel."""
    return _paged_call(q, (k_pool, v_pool), (), page_table, lens, layer,
                       interpret)


# The largest page the AOT compiles for a described v5e have been shown
# to fit in scoped VMEM, counted as the kernel's float32 working rows of
# the merged axis (the accumulator and the three-term p . v product,
# 4 x nh, plus the two pages twice buffered, 4 x ps): 32 heads x 256 at
# 64 positions.  Nothing larger has been tried, so nothing larger is
# admitted.
_MAX_ROWS_F32_BYTES = (4 * 32 + 4 * 64) * 32 * 256 * 4


def _use_pallas_paged(k_pool, nh, mesh=None):
    """Shape gate of the compiled kernel.  Mosaic takes every page the
    sweep tried (head_dim 16..256, 1..32 heads, page_size 4..64, bf16,
    fp32 and int8 pools), which leaves only the VMEM bound above, on
    the heads one rank holds."""
    if not pallas_enabled():
        return False
    ps, C = k_pool.shape[2:]
    if mesh is not None:
        nh //= mesh.shape["tp"]
        C //= mesh.shape["tp"]
    return (4 * nh + 4 * ps) * C * 4 <= _MAX_ROWS_F32_BYTES


def _layer_pages(pool, layer):
    """One layer of a pool or of an int8 scale array, [P, ...]."""
    return jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)


def paged_attention(q, k_pool, v_pool, page_table, lens, layer, mesh=None):
    """Decode attention through a page table.  q: [S, 1, nh, hd] (one
    new token per slot, already scattered into its page); k/v_pool: the
    whole pool, [L, P, ps, nh * hd]; page_table: int32 [S, maxP]; lens:
    int32 [S]; layer: int32 scalar, which layer's pages to read.
    Returns [S, 1, nh, hd].  Inference-only (no custom VJP): the decode
    step never differentiates.  ``mesh``: the serving mesh when the
    caller is a GSPMD program over head-sharded pools
    (:func:`_over_heads`); callers already inside a ``shard_map`` pass
    their local shards and no mesh."""
    if _use_pallas_paged(k_pool, q.shape[2], mesh):
        count_paged_kernel()
        return _over_heads(_paged_attention_tpu, mesh, q, (k_pool, v_pool),
                           (), page_table, lens, layer)
    return _ref_paged_attention(q, _layer_pages(k_pool, layer),
                                _layer_pages(v_pool, layer), page_table,
                                lens)


# --------------------------------------------------------------------------
# quantized pages (ISSUE 9): int8 K/V + per-position-per-head scales
# --------------------------------------------------------------------------

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


def _paged_attention_quant_tpu(q, k_pool, v_pool, k_scale, v_scale,
                               page_table, lens, layer, interpret=False):
    """Quantized-pool Pallas path: the layer's scale rows ride their own
    page-indexed BlockSpecs next to the int8 pages."""
    return _paged_call(q, (k_pool, v_pool), (k_scale, v_scale), page_table,
                       lens, layer, interpret)


def paged_attention_quant(q, k_pool, k_scale, v_pool, v_scale,
                          page_table, lens, layer, mesh=None):
    """Decode attention through a page table over the INT8 pool:
    k/v_pool [L, P, ps, nh * hd] int8 with per-position-per-head fp32
    scales [L, P, ps, nh]; the scales fold into the scores and the
    probabilities on read (in-kernel on TPU).  Same contract, ``mesh``
    and kernel gate as :func:`paged_attention`.  The kernel takes the
    int8 pools whole and the scales as the layer's slice: 32 heads on
    the minor axis is a layout the device stores page-minor, so that
    slice (4 / hd of a layer's page bytes) is the one thing still relaid
    out for the call (PERF.md section 7)."""
    ks, vs = _layer_pages(k_scale, layer), _layer_pages(v_scale, layer)
    if _use_pallas_paged(k_pool, q.shape[2], mesh):
        count_paged_kernel()
        count_dequant_kernel("paged_attn")
        return _over_heads(_paged_attention_quant_tpu, mesh, q,
                           (k_pool, v_pool), (ks, vs), page_table, lens,
                           layer)
    return _ref_paged_attention_quant(
        q, _layer_pages(k_pool, layer), ks, _layer_pages(v_pool, layer),
        vs, page_table, lens)
