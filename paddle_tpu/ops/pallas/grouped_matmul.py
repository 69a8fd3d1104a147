"""Grouped matmul for an expert layer's routed products
(models/deepseek_v3.py::moe_ffn): rows sorted by expert meet each
expert's weights, the group sizes are data —

    out[r] = xs[r] @ experts[g(r)]        g(r): the group row r lies in

shaped for a decode step, where a group has a handful of rows and the
whole cost is streaming each touched expert's weights out of HBM once.

The rows are cut into windows of ``window_rows`` and the grid walks a
work list made from the sizes, not a tile of rows a group: one
step for every (window, group) pair that share rows, in row order.  A
step's weight block is one whole expert (``[K, N]``, or ``[K, tn]``
columns of it where VMEM asks); a group that crosses a window's edge
takes the next window over the same resident block, and a group no row
chose has no step at all, so nothing of it is fetched.  The list's
length is data but the grid is not: the steps past the last re-name the
last step's blocks and compute nothing.  Rows of the window that belong
to a neighbouring group are masked out, and the output window stays in
VMEM until every group that shares it has written its rows.

The experts come as one layer ``[E, K, N]`` or as the whole stack
``[layers, E, K, N]`` with the layer index, taken by scalar prefetch as
the page pools take ``layer``: the index map names the layer, nothing
is sliced or copied.

Two forms, one kernel body: :func:`grouped_matmul` (float32 out) and
:func:`grouped_gate_up`, which reads the rows once for the gate and the
up stacks and writes ``silu(g) * u``, formed in float32 and rounded to
the rows' dtype once.  Products are the operands' own (bf16 x bf16
exact), sums float32.

Off the chip both are ``jax.lax.ragged_dot`` over the stack as
``layers * E`` groups of which one layer's have rows (a layer sliced
out for a custom call would be copied).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .utils import HAS_PALLAS, count_grouped_matmul_kernel, pallas_enabled

if HAS_PALLAS:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ...framework.jax_compat import tpu_compiler_params as _compiler_params

# What a grid step may hold in VMEM by :func:`_step_vmem_bytes`' count
# (blocks twice-buffered, the float32 products); the call states it,
# with room for the compiler's own scratch, as its ``vmem_limit_bytes``
# (the default scoped limit is 16 MiB of the core's 128).
_STEP_VMEM_BYTES = 48 << 20
# Rows a grid step takes.  Picked on the chip (PERF.md section 6, PR 34;
# tools/grouped_matmul_sweep.py): with a whole expert a block the step's
# time is its weights' fetch whatever the rows, so a taller window only
# saves the steps of groups that cross an edge — 16 / 32 / 64 / 128 rows
# read 521 / 512 / 507 / 502 us a call at 384 rows — until its own
# matmul outlasts the fetch (256 rows lose at 3,072 rows and up).
WINDOW_ROWS = 128


def window_rows(m, itemsize):
    """``WINDOW_ROWS``, or all ``m`` rows rounded up to whole packed
    tiles of their dtype (16 rows of bf16) where that is fewer."""
    tile = 32 // itemsize
    return min(WINDOW_ROWS, -(-m // tile) * tile)


def _step_vmem_bytes(tm, k, tn, itemsize, stacks, out_itemsize):
    blocks = tm * k * itemsize + stacks * k * tn * itemsize \
        + tm * tn * out_itemsize
    return 2 * blocks + (stacks + 1) * tm * tn * 4


def column_tile(tm, k, n, itemsize, stacks, out_itemsize):
    """Columns of an expert a step takes: all of them where the step
    fits ``_STEP_VMEM_BYTES``, else the widest multiple of 128 lanes
    that divides N and fits."""
    for tn in [n] + [t for t in range(n - n % 128, 0, -128) if n % t == 0]:
        if _step_vmem_bytes(tm, k, tn, itemsize, stacks,
                            out_itemsize) <= _STEP_VMEM_BYTES:
            return tn
    raise ValueError(f"grouped matmul: no column tile of [{k}, {n}] fits "
                     f"{_STEP_VMEM_BYTES} bytes of VMEM")


def _work_list(sizes, tm, steps):
    """The grid's steps from the group sizes: (group int32 [steps],
    window int32 [steps], offsets int32 [E + 1], live steps int32 [1]).
    Group ``g`` owns rows ``offsets[g] : offsets[g + 1]`` and one step
    for every window those rows touch; steps past the live ones repeat
    the last.  (``lax`` primitives, not ``jnp``: every program of an
    engine traces and lowers this anew, and a ``jnp`` call is a jitted
    wrapper to trace and lower besides.)"""
    lax = jax.lax
    e = sizes.shape[0]
    one, rows = jnp.int32(1), jnp.int32(tm)
    sizes = lax.convert_element_type(sizes, jnp.int32)
    ends = lax.cumsum(sizes)
    starts = lax.sub(ends, sizes)
    first = lax.div(starts, rows)
    last = lax.div(lax.sub(ends, one), rows)
    spans = lax.select(lax.gt(sizes, jnp.int32(0)),
                       lax.add(lax.sub(last, first), one),
                       lax.full_like(sizes, 0))
    step_end = lax.cumsum(spans)
    step_start = lax.sub(step_end, spans)
    live = lax.slice(step_end, (e - 1,), (e,))
    s = lax.min(lax.iota(jnp.int32, steps),
                lax.broadcast(lax.max(lax.sub(live[0], one), jnp.int32(0)),
                              (steps,)))

    def per_step(row):
        """[E] -> [steps, E]: the same row at every step."""
        return lax.broadcast_in_dim(row, (steps, e), (1,))

    # which group a step belongs to, as 0 / 1 over the groups; what the
    # step needs of its group is then a masked sum (no gather)
    s_col = lax.broadcast_in_dim(s, (steps, e), (0,))
    mine = lax.bitwise_and(lax.le(per_step(step_start), s_col),
                           lax.lt(s_col, per_step(step_end)))

    def of_group(row):
        return lax.reduce_sum(
            lax.select(mine, per_step(row), lax.full_like(s_col, 0)), (1,))

    group = of_group(lax.iota(jnp.int32, e))
    window = lax.add(of_group(lax.sub(first, step_start)), s)
    offsets = lax.concatenate([starts, lax.slice(ends, (e - 1,), (e,))], 0)
    return group, window, offsets, live


def _grouped_kernel(group_ref, window_ref, off_ref, live_ref, layer_ref,
                    x_ref, *rest, tm, gate_up):
    """One (window, group) step.  x_ref [tm, K]; the group's expert
    [K, tn] (two of them, gate and up, when ``gate_up``); o_ref
    [tm, tn], resident while the steps stay on this window."""
    *w_refs, o_ref = rest
    s = pl.program_id(1)

    @pl.when(s < live_ref[0])
    def _step():
        g = group_ref[s]
        win = window_ref[s]
        x = x_ref[:]
        dims = (((1,), (0,)), ((), ()))
        acc = jax.lax.dot_general(x, w_refs[0][:], dims,
                                  preferred_element_type=jnp.float32)
        if gate_up:
            acc = jax.nn.silu(acc) * jax.lax.dot_general(
                x, w_refs[1][:], dims, preferred_element_type=jnp.float32)
        row = win * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = (row >= off_ref[g]) & (row < off_ref[g + 1])
        # the window's first step owes nothing to what the buffer held
        fresh = (s == 0) | (window_ref[jnp.maximum(s - 1, 0)] != win)
        kept = jnp.where(fresh, 0, o_ref[:])
        o_ref[:] = jnp.where(mine, acc.astype(o_ref.dtype), kept)


def _grouped_tpu(xs, sizes, stacks, layer, gate_up=False, tm=None, tn=None,
                 interpret=False):
    """xs [M, K]; sizes int32 [E] summing to M; ``stacks``: one or two
    arrays [layers, E, K, N]; layer: int32 scalar.  -> [M, N], float32,
    or xs's dtype for ``gate_up``."""
    m, k = xs.shape
    _, e, _, n = stacks[0].shape
    out_dtype = xs.dtype if gate_up else jnp.float32
    itemsize = xs.dtype.itemsize
    tm = tm or window_rows(m, itemsize)
    tn = tn or column_tile(tm, k, n, itemsize, len(stacks),
                           jnp.dtype(out_dtype).itemsize)
    windows = -(-m // tm)
    if windows * tm != m:
        xs = jnp.pad(xs, ((0, windows * tm - m), (0, 0)))
    steps = windows + e          # every window once, and every crossing
    group, window, offsets, live = _work_list(sizes, tm, steps)
    layer1 = jax.lax.reshape(jax.lax.convert_element_type(layer, jnp.int32),
                             (1,))

    def rows(j, s, gr, wi, of, lv, ly):
        return wi[s], 0

    def out_rows(j, s, gr, wi, of, lv, ly):
        return wi[s], j

    def expert(j, s, gr, wi, of, lv, ly):
        return ly[0], gr[s], 0, j

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n // tn, steps),
        in_specs=[pl.BlockSpec((tm, k), rows),
                  *[pl.BlockSpec((None, None, k, tn), expert)] * len(stacks)],
        out_specs=pl.BlockSpec((tm, tn), out_rows),
    )
    out = pl.pallas_call(
        functools.partial(_grouped_kernel, tm=tm, gate_up=gate_up),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((windows * tm, n), out_dtype),
        compiler_params=_compiler_params(
            pltpu, dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_STEP_VMEM_BYTES + (8 << 20)),
        name="moe_grouped_matmul" + ("_gate_up" if gate_up else ""),
        interpret=interpret,
    )(group, window, offsets, live, layer1, xs, *stacks)
    return out if windows * tm == m else out[:m]


def _ref_grouped(xs, sizes, stack, layer):
    """``jax.lax.ragged_dot`` over the whole stack: layers * E groups of
    which only ``layer``'s have rows."""
    layers, e = stack.shape[:2]
    sizes = jax.lax.dynamic_update_slice(
        jnp.zeros((layers * e,), jnp.int32), sizes.astype(jnp.int32),
        (layer * e,))
    return jax.lax.ragged_dot(
        xs, stack.reshape((layers * e,) + stack.shape[2:]), sizes,
        preferred_element_type=jnp.float32)


def _stack(experts):
    return experts[None] if experts.ndim == 3 else experts


def _layer(layer):
    return jnp.asarray(0 if layer is None else layer, jnp.int32)


def grouped_matmul(xs, sizes, experts, layer=None):
    """xs [M, K], sorted by group; sizes int32 [E], summing to M;
    experts [E, K, N], or the stack [layers, E, K, N] with ``layer``
    (int32 scalar, traced or not).  Returns [M, N] float32."""
    if pallas_enabled():
        count_grouped_matmul_kernel()
        return _grouped_tpu(xs, sizes, (_stack(experts),), _layer(layer))
    return _ref_grouped(xs, sizes, _stack(experts), _layer(layer))


def grouped_gate_up(xs, sizes, gate, up, layer=None):
    """``silu(xs @ gate[g]) * (xs @ up[g])`` in float32, rounded to
    xs's dtype once.  Operands as :func:`grouped_matmul`'s; ``gate``
    and ``up`` alike in shape.  Returns [M, N] in xs's dtype."""
    gate, up, layer = _stack(gate), _stack(up), _layer(layer)
    if pallas_enabled():
        count_grouped_matmul_kernel()
        return _grouped_tpu(xs, sizes, (gate, up), layer, gate_up=True)
    g = _ref_grouped(xs, sizes, gate, layer)
    u = _ref_grouped(xs, sizes, up, layer)
    return (jax.nn.silu(g) * u).astype(xs.dtype)
