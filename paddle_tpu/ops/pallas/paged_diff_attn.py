"""Paged DIFFERENTIAL-attention decode kernel: single-token queries of
a grouped-query layer whose heads are read in pairs and whose two
softmaxes are subtracted (arXiv 2410.05258), gathered through a block
page table as ``paged_attn.py`` gathers plain multi-head attention.

The layer (``models/phi4flash.py``): ``nq`` query heads, ``nkv = nq / 2``
key and value heads, all ``hd`` wide.  Query pair ``p`` is heads ``(2p,
2p + 1)`` and reads K/V pair ``p' = p // 2``: K heads ``(2p', 2p' +
1)``, one for each softmax, and the two V heads side by side as ONE
value of ``2 * hd``::

    A1 = softmax(q[2p]   . k[2p']^T     / sqrt(hd))
    A2 = softmax(q[2p+1] . k[2p' + 1]^T / sqrt(hd))
    o[p] = (A1 - lam * A2) . [v[2p'], v[2p' + 1]]        -> 2 * hd wide

(the sub-layer norm over ``2 * hd`` and the ``1 - lambda_init`` scale
follow in the caller: they are a few rows of elementwise work).

``paged_attn.py`` needs K, V and q of one width and one head count.
Here a page row is ``nkv * hd`` values of K and as many of V, and the
query row is twice that.  The kernel keeps ``paged_attn``'s scaffolding
— the whole pool in HBM, page table, lengths and layer index by scalar
prefetch, a GROUP of pages a grid step copied into a twice-buffered
block under the step before's arithmetic (``group_pages``) — and its
block-diagonal trick, with the query heads laid out by the K head each
reads: the caller hands the queries as two rows ``[2, nkv * hd]`` (row
``r`` holds, at K head ``kh = 2p' + e``'s columns, query head ``4p' +
2r + e``), so ``qbd . rows^T`` gives the scores of all ``nq`` heads with
every off-head product an exact zero and nothing moves between lanes.
Each score row keeps its own running max, sum and accumulator over the
``nkv * hd`` merged V axis; at the end row ``(r, kh)`` keeps the
columns of ITS V pair (``kh // 2``), the odd-``kh`` rows are scaled by
``-lam`` and the two rows of a pair are added: two output rows ``[2,
nkv * hd]`` a slot, pair ``2p' + r`` at columns ``p' * 2 * hd``.

The same call reads the one pooled layer (a page table the pager owns)
and the window layers' rings (a fixed table over a strip each slot
owns): with no positional term, softmax over a ring is indifferent to
the order of its rows, so a ring written at ``t mod W`` and masked by
``min(t + 1, W)`` IS the window.

A pure-lax fallback serves ``JAX_PLATFORMS=cpu``; the kernel is checked
in interpret mode (tests/test_phi4flash.py), by an AOT compile at the
served widths (tests/test_chip_compile.py) and on the chip by the
benchmark cell's comparison with the float32 reference.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .paged_attn import (NEG_INF, _MAX_STEP_VMEM_BYTES, _dot_f32,
                         _layer_pages, _step_vmem_bytes, group_pages)
from .utils import HAS_PALLAS, count_paged_diff_kernel, pallas_enabled

if HAS_PALLAS:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

# score rows a query row of the kernel spreads into: the ``nkv`` K
# heads, rounded up to whole packed tiles of a bf16 block (16 rows)
_ROW_TILE = 16


def lambda_of(lam_q1, lam_k1, lam_q2, lam_k2, lambda_init):
    """The layer's scalar: exp(lq1 . lk1) - exp(lq2 . lk2) +
    lambda_init, float32."""
    f32 = jnp.float32
    return (jnp.exp(jnp.sum(lam_q1.astype(f32) * lam_k1.astype(f32)))
            - jnp.exp(jnp.sum(lam_q2.astype(f32) * lam_k2.astype(f32)))
            + lambda_init)


def diff_attention(q, k, v, mask, lam):
    """The layer as written, dense.  q: [B, T, nq, hd]; k, v: [B, K,
    nkv, hd]; ``mask`` bool, broadcastable to [B, T, K] (true where row
    t may see key k); ``lam`` float32 scalar.  Returns the pairs'
    outputs BEFORE the sub-layer norm, float32 [B, T, nq / 2, 2 * hd].
    Scores and softmax in float32, probabilities in q's dtype."""
    B, T, nq, hd = q.shape
    K, nkv = k.shape[1:3]
    cd = q.dtype
    qg = q.reshape(B, T, nkv // 2, 2, 2, hd)             # p', r, e
    kg = k.astype(cd).reshape(B, K, nkv // 2, 2, hd)     # p', e
    vg = v.astype(cd).reshape(B, K, nkv // 2, 2 * hd)    # p': the pair
    scores = jnp.einsum("btgred,bkged->bgretk", qg, kg,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(hd)
    m = jnp.broadcast_to(mask, (B, T, K))[:, None, None, None]
    probs = jax.nn.softmax(jnp.where(m, scores, NEG_INF), -1).astype(cd)
    av = jnp.einsum("bgretk,bkgd->btgred", probs, vg,
                    preferred_element_type=jnp.float32)
    out = av[..., 0, :] - lam * av[..., 1, :]            # [B, T, g, r, 2hd]
    return out.reshape(B, T, nq // 2, 2 * hd)


def _ref_paged_diff_attention(q, k_pages, v_pages, page_table, lens, lam):
    """Lax fallback: gather each slot's pages into its contiguous view
    and run :func:`diff_attention` under the paged mask (the new token
    sits at position ``lens[s]``, already written).  q: [S, nq, hd];
    k/v_pages: ONE layer's pages [P, ps, nkv * hd].  Returns float32
    [S, nq / 2, 2 * hd]."""
    S, maxP = page_table.shape
    ps = k_pages.shape[1]
    hd = q.shape[-1]
    view = maxP * ps
    kc = k_pages[page_table].reshape(S, view, -1, hd)
    vc = v_pages[page_table].reshape(S, view, -1, hd)
    mask = (jnp.arange(view)[None, :] <= lens[:, None])[:, None, :]
    return diff_attention(q[:, None], kc, vc, mask, lam)[:, 0]


def _rows_by_k_head(q):
    """[S, nq, hd] -> [S, 2, nkv * hd]: row r holds query head ``4p' +
    2r + e`` at the columns of K head ``2p' + e``."""
    S, nq, hd = q.shape
    return (q.reshape(S, nq // 4, 2, 2, hd).transpose(0, 2, 1, 3, 4)
            .reshape(S, 2, (nq // 2) * hd))


def _pairs_in_order(out, hd):
    """The kernel's [S, 2, nkv * hd] (pair ``2p' + r`` at row r, columns
    ``p' * 2hd``) -> [S, nq / 2, 2 * hd] in pair order."""
    S, _, C = out.shape
    return (out.reshape(S, 2, C // (2 * hd), 2 * hd).transpose(0, 2, 1, 3)
            .reshape(S, C // hd, 2 * hd))


def _paged_diff_kernel(pt_ref, lens_ref, layer_ref, q_ref, lam_ref, k_pool,
                       v_pool, o_ref, k_buf, v_buf, sem, turn, qbd_scr,
                       m_scr, l_scr, acc_scr, *, page_size, head_dim,
                       group, table_width, rows):
    """Grid (slot, page group); a step works on ``group`` physical pages
    of K and of V as one block of rows [group * ps, C], C = nkv * hd,
    online softmax across a slot's groups, the copies of the next live
    step started before this step's arithmetic (the copy loop is
    ``paged_attn._paged_decode_kernel``'s, for two pools).  Score rows:
    ``2 * rows`` of them, row ``r * rows + kh`` for query row r and K
    head kh (rows past ``nkv`` in each half are zeros and weigh
    nothing)."""
    G, ps, R = group, page_size, rows
    pools, bufs = (k_pool, v_pool), (k_buf, v_buf)
    s = pl.program_id(0)
    j = pl.program_id(1)
    C = acc_scr.shape[1]
    first = j * G * ps
    ln = lens_ref[s]

    def copies(slot_s, group_j, buf, start):
        last = lens_ref[slot_s] // ps
        for g in range(G):
            entry = group_j * G + g

            @pl.when(entry <= last)
            def _page(g=g, entry=entry):
                # a wait needs the copy's size, not its source
                page = pt_ref[slot_s * table_width + entry] if start else 0
                for i, (pool, blk) in enumerate(zip(pools, bufs)):
                    copy = pltpu.make_async_copy(
                        pool.at[layer_ref[0], page],
                        blk.at[buf, pl.ds(g * ps, ps)], sem.at[buf, i])
                    copy.start() if start else copy.wait()

    def row_col():
        row = jax.lax.broadcasted_iota(jnp.int32, (2 * R, C), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (2 * R, C), 1)
        return row, jnp.where(row < R, row, row - R), col

    @pl.when((s == 0) & (j == 0))
    def _first():
        for blk in bufs:
            blk[:] = jnp.zeros_like(blk)
        turn[0] = 0
        copies(0, 0, 0, start=True)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)
        row, kh, col = row_col()
        q0 = jnp.broadcast_to(q_ref[0:1, :].astype(jnp.float32), (2 * R, C))
        q1 = jnp.broadcast_to(q_ref[1:2, :].astype(jnp.float32), (2 * R, C))
        own = (col >= kh * head_dim) & (col < (kh + 1) * head_dim)
        qbd_scr[:] = jnp.where(own, jnp.where(row < R, q0, q1),
                               0.0).astype(qbd_scr.dtype)

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

        k, v = k_buf[buf], v_buf[buf]                    # [G * ps, C]
        scr = _dot_f32(qbd_scr[:], k, 1) / math.sqrt(head_dim)
        pos = first + jax.lax.broadcasted_iota(jnp.int32, scr.shape, 1)
        scr = jnp.where(pos <= ln, scr, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(scr, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scr - m_new)                         # [2R, G * ps]
        l_scr[:] = jnp.broadcast_to(
            alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        acc_scr[:] = acc_scr[:] * alpha + _dot_f32(p, v, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        row, kh, col = row_col()
        lam = lam_ref[0:1, 0:1]                          # [1, 1]
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        pair = (col >= (kh // 2) * 2 * head_dim) & (
            col < (kh // 2 + 1) * 2 * head_dim)
        sign = jnp.where(kh % 2 == 0, 1.0, -lam)
        own = jnp.where(pair, sign * (acc_scr[:] / l), 0.0)
        for r in (0, 1):
            half = (row >= r * R) & (row < (r + 1) * R)
            o_ref[r:r + 1, :] = jnp.sum(
                jnp.where(half, own, 0.0), axis=0,
                keepdims=True).astype(o_ref.dtype)


def _score_rows(nkv):
    return -(-nkv // _ROW_TILE) * _ROW_TILE


def _paged_diff_call(q, pools, page_table, lens, layer, lam,
                     interpret=False):
    """The ``pallas_call``.  q [S, nq, hd]; ``pools`` the whole (k, v)
    pool, [L, P, ps, nkv * hd] each; returns float32 [S, nq / 2, 2 *
    hd]."""
    S, nq, hd = q.shape
    ps, C = pools[0].shape[2:]
    nkv = nq // 2
    assert C == nkv * hd and nq % 4 == 0, (pools[0].shape, q.shape)
    maxP = page_table.shape[1]
    R = _score_rows(nkv)
    G = group_pages(maxP, ps, C, pools[0].dtype.itemsize, 2 * R)
    row2 = pl.BlockSpec((None, 2, C), lambda s, j, pt, ln, ly: (s, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, maxP // G),
        in_specs=[row2, pl.BlockSpec((1, 128),
                                     lambda s, j, pt, ln, ly: (0, 0))]
        + [pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=row2,
        scratch_shapes=[
            *[pltpu.VMEM((2, G * ps, C), pools[0].dtype)] * 2,
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),             # which half is current
            pltpu.VMEM((2 * R, C), q.dtype),         # block-diagonal query
            pltpu.VMEM((2 * R, 128), jnp.float32),   # running max
            pltpu.VMEM((2 * R, 128), jnp.float32),   # running sum
            pltpu.VMEM((2 * R, C), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_diff_kernel, page_size=ps, head_dim=hd,
                          group=G, table_width=maxP, rows=R),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, 2, C), jnp.float32),
        name="paged_diff_attn_decode",
        interpret=interpret,
    )(page_table.reshape(-1).astype(jnp.int32), lens.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), _rows_by_k_head(q),
      jnp.full((1, 128), lam, jnp.float32), *pools)
    return _pairs_in_order(out, hd)


def _use_pallas_diff(k_pool, nkv):
    """Shape gate of the compiled kernel: ``paged_attn``'s (whole
    128-lane rows, the one-page step under the VMEM bound)."""
    if not pallas_enabled():
        return False
    ps, C = k_pool.shape[2:]
    return C % 128 == 0 and _step_vmem_bytes(
        1, ps, C, k_pool.dtype.itemsize,
        2 * _score_rows(nkv)) <= _MAX_STEP_VMEM_BYTES


def paged_diff_attention(q, pools, page_table, lens, layer, lam):
    """Decode differential attention through a page table.  q: [S, nq,
    hd] (one new token a slot, its K/V already written); ``pools``: the
    whole (k, v) pool, [L, P, ps, nkv * hd] each; page_table: int32 [S,
    maxP]; lens: int32 [S], the new token's position in its view;
    layer: int32 scalar; lam: float32 scalar.  Returns the pairs'
    outputs before the sub-layer norm, float32 [S, nq / 2, 2 * hd]."""
    if _use_pallas_diff(pools[0], q.shape[1] // 2):
        count_paged_diff_kernel()
        return _paged_diff_call(q, pools, page_table, lens, layer, lam)
    k, v = (_layer_pages(p, layer) for p in pools)
    return _ref_paged_diff_attention(q, k, v, page_table, lens, lam)
