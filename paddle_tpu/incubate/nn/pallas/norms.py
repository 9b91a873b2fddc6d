"""Pallas TPU fused normalization kernels: rms_norm, layer_norm.

TPU-native analog of the reference fused norm CUDA kernels
(reference: paddle/phi/kernels/fusion/gpu/fused_layernorm_kernel.cu,
fused_rms_norm via incubate/nn/functional/fused_rms_norm.py). One pass
over rows resident in VMEM; mean/var in f32 regardless of input dtype.

Forward is a Pallas kernel; backward is the standard XLA composition via
``jax.custom_vjp`` (XLA fuses norm backwards well — the win here is the
single-pass forward in the serving/decode path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

_DEF_BLOCK_ROWS = 256


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    xf = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    o_ref[...] = (xf * inv * w_ref[...].astype(jnp.float32)) \
        .astype(x_ref.dtype)


def _rms_kernel_bias(x_ref, w_ref, b_ref, o_ref, *, eps):
    xf = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    out = xf * inv * w_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    o_ref[...] = out.astype(x_ref.dtype)


def _ln_kernel(x_ref, w_ref, b_ref, o_ref, *, eps):
    xf = x_ref[...].astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    out = xc * inv * w_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    o_ref[...] = out.astype(x_ref.dtype)


def _rowwise_call(name, kernel, x2d, params, interpret,
                  block_rows=_DEF_BLOCK_ROWS):
    n, d = x2d.shape
    # rows are independent: a cdiv grid lets Pallas pad the trailing block
    # (padded rows compute garbage that is clipped on write) and keeps the
    # block row count 8-aligned regardless of n
    block_rows = n if n < block_rows else block_rows
    grid = (pl.cdiv(n, block_rows),)
    in_specs = [pl.BlockSpec((block_rows, d), lambda i: (i, 0))]
    for p in params:
        in_specs.append(pl.BlockSpec((d,), lambda i: (0,)))
    return pl.pallas_call(
        kernel,
        name=name,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x2d.dtype),
        interpret=interpret,
    )(x2d, *params)


# --------------------------------------------------------------------- rms
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rms_norm(x2d, w, b, eps):
    interpret = _interpret_default()
    if b is None:
        return _rowwise_call(
            "rms_norm", functools.partial(_rms_kernel, eps=eps), x2d, [w],
            interpret)
    return _rowwise_call(
        "rms_norm", functools.partial(_rms_kernel_bias, eps=eps), x2d,
        [w, b], interpret)


def _rms_ref(x2d, w, b, eps):
    xf = x2d.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    out = xf * inv * w.astype(jnp.float32)
    if b is not None:
        out = out + b.astype(jnp.float32)
    return out.astype(x2d.dtype)


def _rms_fwd(x2d, w, b, eps):
    return _rms_norm(x2d, w, b, eps), (x2d, w, b)


def _rms_bwd(eps, res, g):
    x2d, w, b = res
    dx, dw, db = jax.vjp(
        lambda x, w_, b_: _rms_ref(x, w_, b_, eps), x2d, w,
        b if b is not None else jnp.zeros_like(w))[1](g)
    return dx, dw, (db if b is not None else None)


_rms_norm.defvjp(_rms_fwd, _rms_bwd)


# --------------------------------------------------------------------- ln
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _layer_norm(x2d, w, b, eps):
    interpret = _interpret_default()
    return _rowwise_call(
        "layer_norm", functools.partial(_ln_kernel, eps=eps), x2d, [w, b],
        interpret)


def _ln_ref(x2d, w, b, eps):
    xf = x2d.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mu
    inv = jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    return (xc * inv * w.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x2d.dtype)


def _ln_fwd(x2d, w, b, eps):
    return _layer_norm(x2d, w, b, eps), (x2d, w, b)


def _ln_bwd(eps, res, g):
    x2d, w, b = res
    return jax.vjp(lambda x, w_, b_: _ln_ref(x, w_, b_, eps), x2d, w, b)[1](g)


_layer_norm.defvjp(_ln_fwd, _ln_bwd)


# ------------------------------------------------------------------ public
def rms_norm(x, weight, bias=None, eps=1e-6):
    """Fused RMSNorm over the last axis. x: [..., d]."""
    d = x.shape[-1]
    out = _rms_norm(x.reshape(-1, d), weight, bias, float(eps))
    return out.reshape(x.shape)


def layer_norm(x, weight, bias, eps=1e-5):
    """Fused LayerNorm over the last axis. x: [..., d]."""
    d = x.shape[-1]
    out = _layer_norm(x.reshape(-1, d), weight, bias, float(eps))
    return out.reshape(x.shape)


__all__ = ["rms_norm", "layer_norm"]
