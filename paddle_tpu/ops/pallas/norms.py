"""Fused LayerNorm / RMSNorm Pallas kernels.

Replaces the reference's fused norm CUDA kernels (ref: paddle/fluid/
operators/layer_norm_op.cu, fused/fused_layernorm_residual_dropout_bias.h).
One pass over rows resident in VMEM: moments in fp32 on the VPU, scale/shift
applied in place — the [.., H] activation never round-trips to HBM between
the moment computation and the affine.  Backward runs through XLA autodiff
of the reference formula (already a single fused HLO); the Pallas win is the
forward eval/serving path and keeping the residual stream in bf16.

Rows are tiled ``block_rows`` at a time; H stays whole in VMEM (hidden sizes
up to ~32k fit comfortably).  Fallback to the XLA formula off-TPU or for
ragged shapes.
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


def _ref_layer_norm(x, g, b, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def _ref_rms_norm(x, g, eps):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, -1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps)
            * g.astype(jnp.float32)).astype(x.dtype)


def _ln_kernel(x_ref, g_ref, b_ref, o_ref, *, eps):
    xf = x_ref[:].astype(jnp.float32)                 # [block_rows, H]
    mu = jnp.mean(xf, axis=1, keepdims=True)
    xc = xf - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    o_ref[:] = (y * g_ref[:].astype(jnp.float32)
                + b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _rms_kernel(x_ref, g_ref, o_ref, *, eps):
    xf = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=1, keepdims=True)
    o_ref[:] = (xf * jax.lax.rsqrt(ms + eps)
                * g_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _rows_block(n_rows, dtype):
    """Row tile honoring the dtype's sublane minimum, or None when the
    row count doesn't split into legal tiles (caller falls back to XLA)."""
    min_rows = 16 if dtype == jnp.bfloat16 else 8
    block = 128
    while block > min_rows and n_rows % block:
        block //= 2
    return block if n_rows % block == 0 else None


def _tileable(rows, H, dtype):
    return H % 128 == 0 and _rows_block(rows, dtype) is not None


def _pallas_norm(kernel, out_dtype, x2d, *scale_args, interpret, name=None):
    rows, H = x2d.shape
    br = _rows_block(rows, x2d.dtype)
    grid = (pl.cdiv(rows, br),)
    in_specs = [pl.BlockSpec((br, H), lambda i: (i, 0))]
    in_specs += [pl.BlockSpec((H,), lambda i: (0,))
                 for _ in scale_args]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((br, H), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, H), out_dtype),
        # every row block is independent — let Mosaic pipeline them
        compiler_params=_compiler_params(pltpu, 
            dimension_semantics=("parallel",)),
        name=name,
        interpret=interpret,
    )(x2d, *scale_args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def layer_norm(x, g, b, eps=1e-5, interpret=False):
    """Fused LayerNorm over the last axis.  x: [..., H]; g,b: [H]."""
    rows = math.prod(x.shape[:-1]) if x.ndim > 1 else 1
    H = x.shape[-1]
    use = (_HAS_PALLAS and (interpret or _pallas_enabled())
           and _tileable(rows, H, x.dtype))
    if not use:
        return _ref_layer_norm(x, g, b, eps)
    out = _pallas_norm(functools.partial(_ln_kernel, eps=eps), x.dtype,
                       x.reshape(rows, H), g, b, interpret=interpret,
                       name="layer_norm")
    return out.reshape(x.shape)


def _ln_fwd(x, g, b, eps, interpret):
    return layer_norm(x, g, b, eps, interpret), (x, g, b)


def _ln_bwd(eps, interpret, res, dy):
    x, g, b = res
    _, vjp = jax.vjp(lambda a, gg, bb: _ref_layer_norm(a, gg, bb, eps),
                     x, g, b)
    return vjp(dy)


layer_norm.defvjp(_ln_fwd, _ln_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def rms_norm(x, g, eps=1e-6, interpret=False):
    """Fused RMSNorm over the last axis.  x: [..., H]; g: [H]."""
    rows = math.prod(x.shape[:-1]) if x.ndim > 1 else 1
    H = x.shape[-1]
    use = (_HAS_PALLAS and (interpret or _pallas_enabled())
           and _tileable(rows, H, x.dtype))
    if not use:
        return _ref_rms_norm(x, g, eps)
    out = _pallas_norm(functools.partial(_rms_kernel, eps=eps), x.dtype,
                       x.reshape(rows, H), g, interpret=interpret,
                       name="rms_norm")
    return out.reshape(x.shape)


def _rms_fwd(x, g, eps, interpret):
    return rms_norm(x, g, eps, interpret), (x, g)


def _rms_bwd(eps, interpret, res, dy):
    x, g = res
    _, vjp = jax.vjp(lambda a, gg: _ref_rms_norm(a, gg, eps), x, g)
    return vjp(dy)


rms_norm.defvjp(_rms_fwd, _rms_bwd)
