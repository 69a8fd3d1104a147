"""Fused dequant matmul Pallas kernel (ISSUE 9): ``(x @ w_q) * scale``
with the int8->float weight dequant happening inside the matmul tile
loop, next to fused_ffn.py's discipline.

Weight-only quantized serving stores each matmul weight as int8 plus a
per-OUTPUT-channel fp32 scale (models/gpt.py::quantize_params).  Because
the scale is constant along the contraction axis it factors out of the
GEMM — ``x @ (w_q * s) == (x @ w_q) * s`` — so the kernel never
materializes a dequantized weight: each [K, block_n] int8 tile is cast
to the compute dtype in VMEM, contracted on the MXU with fp32
accumulation, and the scale lands once on the accumulator.  HBM only
ever carries 1-byte weights — the 4x weight-bandwidth cut is the entire
point on the decode path, whose matmuls are memory-bound at batch ~=
slots.

A pure-lax fallback with identical math (same cast, same factored
scale) serves CPU/tier-1; the kernel itself is validated against it in
interpret mode by the slow suite.  fp8 weights (e4m3 via
framework/jax_compat.py::fp8_dtype) always take the lax fallback — XLA
fuses the upcast into the matmul well enough, and Mosaic's fp8 story is
not worth pinning here.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .utils import HAS_PALLAS, count_dequant_kernel, pallas_enabled

if HAS_PALLAS:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ...framework.jax_compat import tpu_compiler_params as _compiler_params


def _ref_dequant_matmul(x2d, w_q, scale):
    """Lax fallback — the per-output-channel scale factors out of the
    contraction, so this is the same math the kernel runs tile-wise."""
    y = x2d @ w_q.astype(x2d.dtype)
    return y * scale.reshape(1, -1).astype(x2d.dtype)


def _dqmm_kernel(x_ref, w_ref, s_ref, o_ref):
    x = x_ref[:]                                     # [bm, K]
    # the dequant IS the tile loop's first op: the int8 tile becomes
    # compute dtype in VMEM, HBM never saw a float weight
    w = w_ref[:].astype(x.dtype)                     # [K, bn]
    acc = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[:] = (acc * s_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _dqmm_tpu(x2d, w_q, s, block_m, block_n, interpret):
    M, K = x2d.shape
    N = w_q.shape[1]
    grid = (pl.cdiv(M, block_m), pl.cdiv(N, block_n))
    # scale rides as [1, N] — Mosaic rejects rank-1 blocks
    return pl.pallas_call(
        _dqmm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, K), lambda m, n: (m, 0)),
            pl.BlockSpec((K, block_n), lambda m, n: (0, n)),
            pl.BlockSpec((1, block_n), lambda m, n: (0, n)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda m, n: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), x2d.dtype),
        # every (m, n) tile is independent: no cross-step accumulator
        compiler_params=_compiler_params(
            pltpu, dimension_semantics=("parallel", "parallel")),
        name="dequant_matmul",
        interpret=interpret,
    )(x2d, w_q, s.reshape(1, N))


def _pick_blocks(M, K, N, itemsize):
    """(block_m, block_n) fitting VMEM, or None if untileable.  K rides
    whole (serving K = hidden/ffn width, at most a few thousand)."""
    if K % 128 or N % 128:
        return None
    min_rows = {4: 8, 2: 16, 1: 32}.get(itemsize, 8)
    block_m = 128 if M % 128 == 0 else (M if M % min_rows == 0 and M <= 512
                                        else None)
    if block_m is None:
        return None
    for block_n in (512, 256, 128):
        if N % block_n:
            continue
        vmem = (K * block_n                          # int8 w tile
                + block_m * K * itemsize             # x tile
                + block_m * block_n * (itemsize + 4))  # out + fp32 acc
        if vmem < 12 * 2 ** 20:
            return block_m, block_n
    return None


def dequant_matmul(x, w_q, scale, interpret=False):
    """x: [..., K] @ weight-only-quantized w -> [..., N] in x.dtype.

    ``w_q``: [K, N] int8 (or fp8); ``scale``: per-output-channel fp32,
    any [N]-broadcastable shape.  Fused Pallas kernel on TPU for int8,
    lax fallback (identical math) elsewhere.

    Decode dispatches have M = slots (a handful of rows) — far below
    the sublane minimum — so M pads up with zero rows before the kernel
    and slices back after; zero rows cost one wasted sublane tile, not
    a silent fall back to float weights in HBM on exactly the
    memory-bound path this kernel exists for."""
    K = x.shape[-1]
    N = w_q.shape[1]
    M = math.prod(x.shape[:-1]) if x.ndim > 1 else 1
    x2 = x.reshape(M, K)
    itemsize = jnp.dtype(x.dtype).itemsize
    Mp = M
    blocks = None
    if w_q.dtype == jnp.int8:
        min_rows = {4: 8, 2: 16, 1: 32}.get(itemsize, 8)
        Mp = -(-M // min_rows) * min_rows
        blocks = _pick_blocks(Mp, K, N, itemsize)
    use = (HAS_PALLAS and (interpret or pallas_enabled())
           and blocks is not None)
    if use:
        count_dequant_kernel("matmul")
        if Mp != M:
            x2 = jnp.concatenate(
                [x2, jnp.zeros((Mp - M, K), x2.dtype)], axis=0)
        out = _dqmm_tpu(x2, w_q, scale, *blocks, interpret=interpret)[:M]
    else:
        out = _ref_dequant_matmul(x2, w_q, scale)
    return out.reshape(*x.shape[:-1], N)
