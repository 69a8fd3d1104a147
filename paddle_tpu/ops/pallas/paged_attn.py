"""Paged-attention decode kernel (ISSUE 8): single-token queries gather
K/V through a block page table instead of a contiguous per-slot strip.

Extends flash_attn.py's blocked online-softmax scaffolding to the paged
KV layout the serving engine owns: K/V live in a fixed pool
``[num_pages, page_size, nh, hd]`` and each decode lane's logical
sequence is the concatenation of the pages its table names.  The TPU
kernel streams one *physical page* per grid step — the page id comes
from the scalar-prefetched page table, so the BlockSpec index map turns
the logical ``(slot, page_j)`` coordinate into the physical page's HBM
block and Mosaic DMAs exactly the pages a lane references, never the
whole pool.

A pure-lax fallback (gather pages into the contiguous per-slot view,
then the exact `_slot_block` masked-attention math) serves
``JAX_PLATFORMS=cpu``.  The Pallas kernel is checked three ways: for
results in interpret mode (slow suite), for the chip's compiler by AOT
compiles at the served widths (tests/test_chip_compile.py), and on the
chip against the float32 model by ``chip_smoke.py``, which also reads
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

    q: [S, 1, nh, hd]; k/v_pages: [P, ps, nh, hd];
    page_table: int32 [S, maxP]; lens: int32 [S] (the new token sits at
    position lens[s], already scattered into its page).  Returns
    [S, 1, nh, hd]."""
    S, maxP = page_table.shape
    ps = k_pages.shape[1]
    hd = q.shape[-1]
    cd = q.dtype
    view = maxP * ps
    kc = k_pages[page_table].reshape(S, view, *k_pages.shape[2:])
    vc = v_pages[page_table].reshape(S, view, *v_pages.shape[2:])
    logits = jnp.einsum("sqhd,skhd->shqk", q.astype(jnp.float32),
                        kc.astype(jnp.float32)) / math.sqrt(hd)
    mask = jnp.arange(view)[None, :] <= lens[:, None]       # [S, view]
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, -1).astype(cd)
    return jnp.einsum("shqk,skhd->sqhd", probs, vc.astype(cd))


def _paged_decode_kernel(pt_ref, lens_ref, q_ref, k_ref, *rest, page_size,
                         quant):
    """Grid (slot, page_j).  One physical page of K/V per step, online
    softmax across a lane's pages exactly like flash_attn's streamed
    K-blocks.  q_ref: [nh, hd]; k_ref/v_ref: [ps, nh, hd] — the page the
    scalar-prefetched table names for this (slot, j).  With ``quant``
    the pages are int8 and each is followed by its [ps, nh] fp32 scale
    block (HBM traffic per page is 1 byte/element plus the scale row).

    Decode attention is one query row per head, a matrix-VECTOR product:
    Mosaic's matmul needs a non-contracting dimension on both sides and
    the head batch dimension leading, neither of which the page layout
    has (a refused ``dot_dimension_numbers`` on the chip), and with one
    query row the MXU would idle anyway.  So both contractions run on
    the vector unit in the page's own layout, heads on sublanes and
    head_dim on lanes: scores reduce over lanes, the output reduces over
    the page's leading position axis.  The int8 scales are [ps, nh] like
    the scores and the probabilities, so they fold in there
    (``q . (k*s) == (q . k) * s``) and the pages are never dequantized
    elementwise."""
    if quant:
        ks_ref, v_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        v_ref, o_ref, m_scr, l_scr, acc_scr = rest
    s = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    ln = lens_ref[s]
    # pages entirely past the fill bound contribute nothing; skipping
    # them is the paged analogue of the causal block skip
    @pl.when(j * page_size <= ln)
    def _body():
        q = q_ref[:].astype(jnp.float32)                 # [nh, hd]
        k = k_ref[:].astype(jnp.float32)                 # [ps, nh, hd]
        v = v_ref[:].astype(jnp.float32)
        hd = q.shape[-1]
        # scores[p, h] = q[h, :] . k[p, h, :]
        scr = jnp.sum(k * q[None], axis=-1) / math.sqrt(hd)
        if quant:
            scr = scr * ks_ref[:]
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, scr.shape, 0)
        scr = jnp.where(pos <= ln, scr, NEG_INF)

        m_prev = m_scr[:]                                # [1, nh]
        m_new = jnp.maximum(m_prev, jnp.max(scr, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scr - m_new)                         # [ps, nh]
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=0, keepdims=True)
        m_scr[:] = m_new
        if quant:
            p = p * vs_ref[:]
        # out[h, d] += sum_p p[p, h] * v[p, h, d]; [..., None] moves the
        # head axis from lanes to sublanes, where acc keeps it
        acc_scr[:] = (acc_scr[:] * alpha[..., None][0]
                      + jnp.sum(p[..., None] * v, axis=0))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        l = jnp.maximum(l_scr[:], 1e-30)[..., None][0]   # [nh, 1]
        o_ref[:] = (acc_scr[:] / l).astype(o_ref.dtype)


def _paged_call(q, pages, page_table, lens, interpret):
    """The one ``pallas_call`` both pools go through.  ``pages`` is
    (k, v) for the fp pool and (k, k_scale, v, v_scale) for int8.  The
    page table rides the scalar-prefetch channel so BlockSpec index maps
    can translate logical page coordinates into physical pool blocks
    before the DMA is issued: each grid step DMAs exactly one page (and,
    for int8, its scale rows)."""
    S, T, nh, hd = q.shape
    assert T == 1, "paged decode kernel is single-token"
    ps = pages[0].shape[1]
    maxP = page_table.shape[1]
    qs = q[:, 0]                                         # [S, nh, hd]
    pt_flat = page_table.reshape(-1).astype(jnp.int32)
    lens32 = lens.astype(jnp.int32)

    def page_spec(a):
        tail = a.shape[1:]
        return pl.BlockSpec(
            (None,) + tail,
            lambda s, j, pt, ln: (pt[s * maxP + j],) + (0,) * len(tail))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, maxP),
        in_specs=[pl.BlockSpec((None, nh, hd),
                               lambda s, j, pt, ln: (s, 0, 0)),
                  *[page_spec(a) for a in pages]],
        out_specs=pl.BlockSpec((None, nh, hd),
                               lambda s, j, pt, ln: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, nh), jnp.float32),
            pltpu.VMEM((1, nh), jnp.float32),
            pltpu.VMEM((nh, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, page_size=ps,
                          quant=len(pages) == 4),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, nh, hd), q.dtype),
        name="paged_attn_decode",
        interpret=interpret,
    )(pt_flat, lens32, qs, *pages)
    return out[:, None]


def _over_heads(fn, mesh, q, *pages_table_lens):
    """``fn(q, *pages, page_table, lens)``, run per 'tp' shard of the
    head axis under a mesh.  The tensor-parallel engine is a GSPMD
    ``jit`` over head-sharded pools, and the partitioner cannot split a
    Mosaic call ("Mosaic kernels cannot be automatically partitioned"),
    so the kernel is wrapped in a ``shard_map``: each rank runs it on
    its own nh/tp heads of every page, with the page table and lengths
    replicated.  Heads are axis 2 of q, of the pages and of the int8
    scale rows alike."""
    if mesh is None:
        return fn(q, *pages_table_lens)
    from ...framework.jax_compat import partition_spec as P, shard_map
    heads = P(None, None, "tp")
    n_sharded = len(pages_table_lens) - 1           # q + pages
    return shard_map(fn, mesh=mesh,
                     in_specs=(heads,) * n_sharded + (P(), P()),
                     out_specs=heads, check_vma=False)(q, *pages_table_lens)


def _paged_attention_tpu(q, k_pages, v_pages, page_table, lens,
                         interpret=False):
    """q: [S, 1, nh, hd] -> [S, 1, nh, hd] through the Pallas kernel."""
    return _paged_call(q, (k_pages, v_pages), page_table, lens, interpret)


# The largest page whose fp32 working copies (k, v and their products,
# [ps, nh, hd] each, head_dim padded to the 128 lanes) the AOT compiles
# for a described v5e have been shown to fit in scoped VMEM: 64 x 32 x
# 256.  Nothing larger has been tried, so nothing larger is admitted.
_MAX_PAGE_F32_BYTES = 64 * 32 * 256 * 4


def _use_pallas_paged(k_pages, mesh=None):
    """Shape gate of the compiled kernel.  Mosaic takes every page the
    sweep tried (head_dim 16..256, 1..32 heads, page_size 4..64, bf16,
    fp32 and int8 pools: the page's position axis is an untiled leading
    dimension, so int8 needs no 32-row pages), which leaves only the
    VMEM bound above, on the heads one rank holds."""
    if not pallas_enabled():
        return False
    _, ps, nh, hd = k_pages.shape
    if mesh is not None:
        nh //= mesh.shape["tp"]
    return ps * nh * max(hd, 128) * 4 <= _MAX_PAGE_F32_BYTES


def paged_attention(q, k_pages, v_pages, page_table, lens, mesh=None):
    """Decode attention through a page table.  q: [S, 1, nh, hd] (one
    new token per slot, already scattered into its page); k/v_pages:
    [P, ps, nh, hd]; page_table: int32 [S, maxP]; lens: int32 [S].
    Returns [S, 1, nh, hd].  Inference-only (no custom VJP): the decode
    step never differentiates.  ``mesh``: the serving mesh when the
    caller is a GSPMD program over head-sharded pools
    (:func:`_over_heads`); callers already inside a ``shard_map`` pass
    their local shards and no mesh."""
    if _use_pallas_paged(k_pages, mesh):
        count_paged_kernel()
        return _over_heads(_paged_attention_tpu, mesh,
                           q, k_pages, v_pages, page_table, lens)
    return _ref_paged_attention(q, k_pages, v_pages, page_table, lens)


# --------------------------------------------------------------------------
# quantized pages (ISSUE 9): int8 K/V + per-position-per-head scales
# --------------------------------------------------------------------------

def _ref_paged_attention_quant(q, k_pages, k_scale, v_pages, v_scale,
                               page_table, lens):
    """Lax fallback over the int8 pool: dequantize
    (``q_int8 * scale`` per position per head, staying fp32 like the fp
    path's score math) and delegate to :func:`_ref_paged_attention` —
    ONE copy of the gather/mask/softmax semantics to keep in sync.
    k/v_pages: [P, ps, nh, hd] int8; k/v_scale: [P, ps, nh] fp32."""
    return _ref_paged_attention(
        q, k_pages.astype(jnp.float32) * k_scale[..., None],
        v_pages.astype(jnp.float32) * v_scale[..., None],
        page_table, lens)


def _paged_attention_quant_tpu(q, k_pages, k_scale, v_pages, v_scale,
                               page_table, lens, interpret=False):
    """Quantized-pool Pallas path: the scale rows ride their own
    page-indexed BlockSpecs next to the int8 pages."""
    return _paged_call(q, (k_pages, k_scale, v_pages, v_scale), page_table,
                       lens, interpret)


def paged_attention_quant(q, k_pages, k_scale, v_pages, v_scale,
                          page_table, lens, mesh=None):
    """Decode attention through a page table over the INT8 pool:
    k/v_pages [P, ps, nh, hd] int8 with per-position-per-head fp32
    scales [P, ps, nh]; the scales fold into the scores and the
    probabilities on read (in-kernel on TPU).  Same shapes, contract,
    ``mesh`` and kernel gate as :func:`paged_attention`."""
    if _use_pallas_paged(k_pages, mesh):
        count_paged_kernel()
        count_dequant_kernel("paged_attn")
        return _over_heads(_paged_attention_quant_tpu, mesh,
                           q, k_pages, k_scale, v_pages, v_scale,
                           page_table, lens)
    return _ref_paged_attention_quant(q, k_pages, k_scale, v_pages,
                                      v_scale, page_table, lens)
