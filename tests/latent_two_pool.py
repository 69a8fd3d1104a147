"""A frozen copy of the latent families' TWO-ARRAY pool — ``c`` in
``[L, P, ps, rank]`` and the rope key in ``[L, P, ps, 128]`` — and of the
decode kernel that read it, a start and a wait on each array for every
live page: what ``ops/pallas/paged_mla.py`` and the pool helpers of
``models/deepseek_v3.py`` were before the pool became one array of rows
``rank + 128`` wide.  The tests hold the one-array programs to these,
bit for bit, on the same contents.

:func:`install` puts the two-array writers and pools in place of the
families' own (``monkeypatch``), for both ``deepseek_v3`` and
``longcat_flash``; everything else the families run is theirs."""
import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.models import deepseek_v3 as ds
from paddle_tpu.models import gpt
from paddle_tpu.ops.pallas import paged_mla
from paddle_tpu.ops.pallas.paged_attn import (
    NEG_INF, _dot_f32, _layer_pages)


def _ref_paged_mla(q_abs, q_rope, c_pages, r_pages, page_table, lens,
                   scale):
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


def page_mover(pt_ref, lens_ref, layer_ref, pools, bufs, sem, turn, *,
               group, page_size, table_width):
    G, ps = group, page_size
    last_slot = pl.num_programs(0) - 1

    def live_pages(slot, group_j):
        return jnp.clip(lens_ref[slot] // ps + 1 - group_j * G, 0, G)

    def copies(slot, group_j, half, n, start):
        def page(g, carry):
            src = (pt_ref[slot * table_width + group_j * G + g] if start
                   else 0)
            at = pl.ds(pl.multiple_of(g * ps, ps), ps)
            for i, (pool, rows) in enumerate(zip(pools, bufs)):
                copy = pltpu.make_async_copy(
                    pool.at[layer_ref[0], src], rows.at[half, at],
                    sem.at[half, i])
                copy.start() if start else copy.wait()
            return carry
        jax.lax.fori_loop(0, n, page, 0)

    def prime():
        for rows in bufs:
            rows[:] = jnp.zeros_like(rows)
        turn[0] = 0
        copies(0, 0, 0, live_pages(0, 0), start=True)

    def take(s, j):
        half = turn[0]
        more = (((j + 1) * G * ps <= lens_ref[s])
                & (j + 1 < pl.num_programs(1)))
        next_s = jnp.where(more, s, jnp.minimum(s + 1, last_slot))
        next_j = jnp.where(more, j + 1, 0)
        copies(next_s, next_j, 1 - half,
               jnp.where(more | (s < last_slot),
                         live_pages(next_s, next_j), 0), start=True)
        copies(s, j, half, live_pages(s, j), start=False)
        turn[0] = 1 - half
        return half

    return prime, take


def _mla_decode_kernel(pt_ref, lens_ref, layer_ref, qa_ref, qr_ref, c_pool,
                       r_pool, o_ref, c_buf, r_buf, sem, turn, m_scr, l_scr,
                       acc_scr, *, page_size, group, table_width, scale):
    s = pl.program_id(0)
    j = pl.program_id(1)
    ln = lens_ref[s]
    first = j * group * page_size
    prime, take = page_mover(
        pt_ref, lens_ref, layer_ref, (c_pool, r_pool), (c_buf, r_buf), sem,
        turn, group=group, page_size=page_size, table_width=table_width)

    @pl.when((s == 0) & (j == 0))
    def _first():
        prime()

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(first <= ln)
    def _pages():
        half = take(s, j)
        c, r = c_buf[half], r_buf[half]
        scr = (_dot_f32(qa_ref[:], c, 1) + _dot_f32(qr_ref[:], r, 1)) * scale
        pos = first + jax.lax.broadcasted_iota(jnp.int32, scr.shape, 1)
        scr = jnp.where(pos <= ln, scr, NEG_INF)
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


def _paged_mla_tpu(q_abs, q_rope, c_pool, r_pool, page_table, lens, layer,
                   scale, interpret=False):
    S, nh, rank = q_abs.shape
    ps = c_pool.shape[2]
    lanes = r_pool.shape[3]
    maxP = page_table.shape[1]
    # the live rule (and its GROUP_ROWS), as a test may set it
    group = paged_mla.group_pages(maxP, ps, rank + lanes,
                                  c_pool.dtype.itemsize, nh)
    pt_flat = page_table.reshape(-1).astype(jnp.int32)
    lens32 = lens.astype(jnp.int32)
    layer1 = jnp.reshape(layer, (1,)).astype(jnp.int32)
    q_rope = jnp.pad(q_rope, ((0, 0), (0, 0), (0, lanes - q_rope.shape[-1])))

    def rows(width):
        return pl.BlockSpec((None, nh, width),
                            lambda s, j, pt, ln, ly: (s, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, maxP // group),
        in_specs=[rows(rank), rows(lanes)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=rows(rank),
        scratch_shapes=[
            pltpu.VMEM((2, group * ps, rank), c_pool.dtype),
            pltpu.VMEM((2, group * ps, lanes), r_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((nh, 128), jnp.float32),
            pltpu.VMEM((nh, 128), jnp.float32),
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
    )(pt_flat, lens32, layer1, q_abs, q_rope, c_pool, r_pool)


def _use_pallas_mla(c_pool, r_pool, nh):
    return False


def paged_mla_attention(q_abs, q_rope, c_pool, r_pool, page_table, lens,
                        layer, scale):
    if _use_pallas_mla(c_pool, r_pool, q_abs.shape[1]):
        return _paged_mla_tpu(q_abs, q_rope, c_pool, r_pool, page_table,
                              lens, layer, scale)
    return _ref_paged_mla(q_abs, q_rope, _layer_pages(c_pool, layer),
                          _layer_pages(r_pool, layer), page_table, lens,
                          scale)


# --------------------------------------------------------------------------
# the two-array writers of both latent families
# --------------------------------------------------------------------------

def _shapes(layers, cfg, num_pages, page_size):
    lead = (layers, num_pages, page_size)
    return lead + (cfg.kv_lora_rank,), lead + (ds.ROPE_LANES,)


def decode_group_pages(cfg, pools, table_width, tp=1):
    c_pool, r_pool = pools[:2]
    return paged_mla.group_pages(table_width, c_pool.shape[2],
                                 c_pool.shape[3] + r_pool.shape[3],
                                 c_pool.dtype.itemsize, cfg.num_heads)


def prefill_attend(cfg, blk, x, pos, pools, layer, flat, lens, flash):
    b, s = x.shape[:2]
    ps = pools[0].shape[2]
    q_nope, q_rope, c, kr = ds._mla_project(cfg, x, blk, pos)
    with jax.named_scope("kv_scatter"):
        pc, pr = pools
        pc = pc.at[layer, flat].set(
            c.astype(pc.dtype).reshape(b * (s // ps), ps, -1))
        pr = pr.at[layer, flat].set(
            ds._pad_rope(kr, pr.dtype).reshape(b * (s // ps), ps, -1))
    with jax.named_scope("mla_attn"):
        if flash:
            a = ds._mla_attend_flash(cfg, blk, q_nope, q_rope, c, kr, lens)
        else:
            a = ds._mla_attend(cfg, blk, q_nope, q_rope, c, kr,
                               ds._causal(s, s))
    return a, (pc, pr)


def chunk_attend(cfg, blk, x, pos, pools, layer, pt_row, offset):
    C = x.shape[1]
    maxP = pt_row.shape[0]
    ps = pools[0].shape[2]
    q_nope, q_rope, c, kr = ds._mla_project(cfg, x, blk, pos)
    views = []
    for pool, new in zip(pools, (c, ds._pad_rope(kr, pools[1].dtype))):
        view = pool[layer, pt_row].reshape(1, maxP * ps, -1)
        views.append(jax.lax.dynamic_update_slice(
            view, new.astype(pool.dtype), (0, offset, 0)))
    pools = tuple(pool.at[layer, pt_row].set(v[0].reshape(maxP, ps, -1))
                  for pool, v in zip(pools, views))
    with jax.named_scope("mla_attn"):
        a = ds._mla_attend(cfg, blk, q_nope, q_rope, views[0],
                           views[1][..., :cfg.qk_rope_head_dim],
                           ds._causal(C, maxP * ps, offset))
    return a, pools


def decode_attend(cfg, blk, x, lens, pools, layer, page_table, write_pages,
                  write_offs, absorbed=True):
    S = x.shape[0]
    q_nope, q_rope, c, kr = ds._mla_project(cfg, x, blk, lens)
    with jax.named_scope("kv_write"):
        pc, pr = pools
        pc = pc.at[layer, write_pages, write_offs].set(c.astype(pc.dtype))
        pr = pr.at[layer, write_pages, write_offs].set(
            ds._pad_rope(kr, pr.dtype))
    with jax.named_scope("mla_attn"):
        if absorbed:
            wuk, wuv = ds._wukv(cfg, blk)
            q_abs = jnp.einsum("shd,chd->shc", q_nope, wuk)
            o_lat = paged_mla_attention(
                q_abs, q_rope, pc, pr, page_table, lens, layer,
                1.0 / math.sqrt(cfg.qk_head_dim))
            a = jnp.einsum("shc,chd->shd", o_lat, wuv).reshape(S, -1)
        else:
            view = page_table.shape[1] * pc.shape[2]
            cv = pc[layer][page_table].reshape(S, view, -1)
            rv = pr[layer][page_table].reshape(S, view, -1)
            mask = (jnp.arange(view)[None, :] <= lens[:, None])[:, None]
            a = ds._mla_attend(cfg, blk, q_nope[:, None], q_rope[:, None],
                               cv, rv[..., :cfg.qk_rope_head_dim],
                               mask)[:, 0]
    return a, (pc, pr)


def install(monkeypatch):
    """The two-array pool and writers in place of both families' own."""
    from paddle_tpu.models import longcat_flash as lc

    def pools_of(layers_of):
        def shapes(cfg, num_pages, page_size):
            return _shapes(layers_of(cfg), cfg, num_pages, page_size)

        def init(cfg, num_pages, page_size, dtype=None, mesh=None,
                 kv_quant=False):
            cd = jnp.dtype(dtype or cfg.dtype)
            return tuple(gpt._pool_zeros(s, cd)
                         for s in shapes(cfg, num_pages, page_size))
        return shapes, init

    for module, layers_of in ((ds, lambda c: c.num_hidden_layers),
                              (lc, lambda c: c.attention_blocks)):
        shapes, init = pools_of(layers_of)
        monkeypatch.setattr(module, "paged_pool_shapes", shapes)
        monkeypatch.setattr(module, "init_paged_pools", init)
        monkeypatch.setattr(module, "decode_group_pages", decode_group_pages)
    for name in ("prefill_attend", "chunk_attend", "decode_attend"):
        monkeypatch.setattr(ds, name, globals()[name])


def programs(module, params, cfg, seed=0, ps=8):
    """A family's three programs, as the engine runs them, over one
    pool: a bucketed wave of two prompts (13 and 16 positions), a third
    prompt of 16 in two chunks of 8, then four decode steps of all
    three.  Returns (every logits array in order, the final pools)."""
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, (3, 20)).astype(np.int32)
    table = np.asarray([[1, 2, 9, 11], [3, 4, 10, 12], [5, 6, 7, 8]],
                       np.int32)
    pools = module.init_paged_pools(cfg, 13, ps)
    wave = jax.jit(lambda pp, *a: module.prefill_paged(params, cfg, pp, *a))
    chunk = jax.jit(lambda pp, *a: module.chunk_paged(params, cfg, pp, *a))
    step = jax.jit(lambda pp, *a: module.decode_paged(params, cfg, pp, *a))
    out = []
    lens = np.asarray([13, 16], np.int32)
    got, pools = wave(pools, toks[:2, :16], lens, table[:2, :2])
    out.append(got)
    for off in (0, 8):
        got, pools = chunk(pools, toks[2:, off:off + 8], table[2],
                           jnp.int32(off))
        out.append(got)
    pos = np.asarray([13, 16, 16], np.int32)
    tok = toks[np.arange(3), pos]
    for _ in range(4):
        got, pools, _ = step(pools, table, table[np.arange(3), pos // ps],
                             pos % ps, pos, tok)
        out.append(got)
        tok = np.asarray(jnp.argmax(got, -1), np.int32)
        pos = pos + 1
    return [np.asarray(x) for x in out], pools


def kernels_on(monkeypatch):
    """Both decode kernels, the one-array and the two-array, in
    interpret mode wherever a family's decode attends."""
    this = sys.modules[__name__]
    monkeypatch.setattr(paged_mla, "_use_pallas_mla", lambda *a: True)
    monkeypatch.setattr(paged_mla, "_paged_mla_tpu", functools.partial(
        paged_mla._paged_mla_tpu, interpret=True))
    monkeypatch.setattr(this, "_use_pallas_mla", lambda *a: True)
    monkeypatch.setattr(this, "_paged_mla_tpu", functools.partial(
        _paged_mla_tpu, interpret=True))


def assert_held_to_two_arrays(module, params, cfg, monkeypatch):
    """:func:`programs` through the family's one-array pool and then
    through the two-array writers: every logit equal, bit for bit, and
    the one array's rows ``c`` and the rope lane row exactly the two
    arrays' contents."""
    got, (rows,) = programs(module, params, cfg)
    install(monkeypatch)
    want, (c, r) = programs(module, params, cfg)
    assert len(got) == len(want) == 7
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and np.array_equal(g, w)
    rank = cfg.kv_lora_rank
    assert rows.shape == c.shape[:3] + (rank + ds.ROPE_LANES,)
    assert np.array_equal(np.asarray(rows[..., :rank]), np.asarray(c))
    assert np.array_equal(np.asarray(rows[..., rank:]), np.asarray(r))
    assert np.asarray(c).any() and np.asarray(r).any()
