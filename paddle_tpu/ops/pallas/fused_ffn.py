"""Fused transformer FFN Pallas kernel: gelu(x@w1+b1)@w2 + b2 in one pass.

Replaces the reference's fused feed-forward CUDA op (ref: paddle/fluid/
operators/fused/fused_feedforward_op.cu).  The HBM win: the [M, F]
intermediate (F = 4H) never materializes — each F-tile of the first matmul
is activated in VMEM and immediately contracted into a [block_m, H] fp32
accumulator, so HBM traffic is x + w1 + w2 + y instead of + 2·[M,F].

Grid (m_blocks, f_blocks), F innermost; both matmuls hit the MXU via
``dot_general`` with fp32 accumulation.  Backward goes through XLA autodiff
of the reference composition (XLA refuses nothing here — the bwd is three
matmuls it schedules well).  Fallback to the XLA composition off-TPU or for
shapes that don't tile.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .utils import (HAS_PALLAS as _HAS_PALLAS, on_tpu as _on_tpu,
                    pallas_enabled as _pallas_enabled)

if _HAS_PALLAS:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ...framework.jax_compat import tpu_compiler_params as _compiler_params


def _ref_ffn(x, w1, b1, w2, b2):
    h = jax.nn.gelu(x @ w1.astype(x.dtype) + b1.astype(x.dtype),
                    approximate=True)
    return h @ w2.astype(x.dtype) + b2.astype(x.dtype)


def _ffn_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref, acc_ref):
    fi = pl.program_id(1)

    @pl.when(fi == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x = x_ref[:]                                     # [bm, H]
    h = jax.lax.dot_general(x, w1_ref[:], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    h = h + b1_ref[:].astype(jnp.float32)            # [bm, bf]
    h = jax.nn.gelu(h, approximate=True).astype(x.dtype)
    acc_ref[:] += jax.lax.dot_general(
        h, w2_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(fi == pl.num_programs(1) - 1)
    def _finish():
        o_ref[:] = (acc_ref[:]
                    + b2_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _fused_ffn_tpu(x2d, w1, b1, w2, b2, block_m, block_f, interpret):
    M, H = x2d.shape
    F = w1.shape[1]
    grid = (pl.cdiv(M, block_m), pl.cdiv(F, block_f))
    # biases ride as [1, F] / [1, H] — Mosaic rejects 1-D (rank<2) blocks
    return pl.pallas_call(
        _ffn_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, H), lambda m, f: (m, 0)),
            pl.BlockSpec((H, block_f), lambda m, f: (0, f)),
            pl.BlockSpec((1, block_f), lambda m, f: (0, f)),
            pl.BlockSpec((block_f, H), lambda m, f: (f, 0)),
            pl.BlockSpec((1, H), lambda m, f: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, H), lambda m, f: (m, 0)),
        out_shape=jax.ShapeDtypeStruct((M, H), x2d.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, H), jnp.float32)],
        # row blocks are independent; only the f (accumulator) axis carries
        compiler_params=_compiler_params(pltpu, 
            dimension_semantics=("parallel", "arbitrary")),
        name="fused_ffn",
        interpret=interpret,
    )(x2d, w1, b1.reshape(1, F), w2, b2.reshape(1, H))


# sweep-installed tiling override (tools/tpu_kernel_check.py measures the
# candidates on-chip at the flagship shape; bench.py installs the winner
# so the gate only approves the configuration that actually executes)
_BLOCK_OVERRIDE = None


def set_default_blocks(blocks=None):
    """Install an explicit (block_m, block_f) tiling; None reverts to the
    automatic _pick_blocks choice."""
    global _BLOCK_OVERRIDE
    _BLOCK_OVERRIDE = tuple(blocks) if blocks else None


def _pick_blocks(M, H, F, itemsize):
    """(block_m, block_f) fitting ~12MB VMEM, or None if untileable."""
    if H % 128 or F % 128:
        return None
    # sublane minimum scales inversely with itemsize: (8,128) f32, (16,128)
    # bf16, (32,128) int8 — same guard as norms._rows_block
    min_rows = {4: 8, 2: 16, 1: 32}.get(itemsize, 8)
    block_m = 128 if M % 128 == 0 else (M if M % min_rows == 0 and M <= 512
                                        else None)
    if block_m is None:
        return None
    for block_f in (512, 256, 128):
        if F % block_f:
            continue
        # w1/w2 tiles + x/out tiles in input dtype, fp32 acc + gelu tile
        vmem = (2 * H * block_f * itemsize           # w1 + w2 tiles
                + 2 * block_m * H * itemsize         # x + out tiles
                + block_m * H * 4                    # fp32 accumulator
                + block_m * block_f * 4)             # fp32 h tile
        if vmem < 12 * 2 ** 20:
            return block_m, block_f
    return None


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def fused_ffn(x, w1, b1, w2, b2, interpret=False):
    """x: [..., H]; w1: [H, F]; b1: [F]; w2: [F, H]; b2: [H] -> [..., H]."""
    H = x.shape[-1]
    M = math.prod(x.shape[:-1]) if x.ndim > 1 else 1
    F = w1.shape[1]
    blocks = None
    if _BLOCK_OVERRIDE is not None:
        bm, bf = _BLOCK_OVERRIDE
        # the kernel has no tail masking: the override only applies when
        # it divides this shape exactly; otherwise the automatic choice
        if M % bm == 0 and F % bf == 0 and H % 128 == 0:
            blocks = (bm, bf)
    if blocks is None:
        blocks = _pick_blocks(M, H, F, jnp.dtype(x.dtype).itemsize)
    use = (_HAS_PALLAS and (interpret or _pallas_enabled())
           and blocks is not None)
    if not use:
        return _ref_ffn(x, w1, b1, w2, b2)
    out = _fused_ffn_tpu(x.reshape(M, H), w1, b1, w2, b2, *blocks,
                         interpret=interpret)
    return out.reshape(x.shape)


def _ffn_fwd(x, w1, b1, w2, b2, interpret):
    return fused_ffn(x, w1, b1, w2, b2, interpret), (x, w1, b1, w2, b2)


def _ffn_bwd(interpret, res, g):
    x, w1, b1, w2, b2 = res
    _, vjp = jax.vjp(_ref_ffn, x, w1, b1, w2, b2)
    return vjp(g)


fused_ffn.defvjp(_ffn_fwd, _ffn_bwd)
