"""Fused transformer ops (reference: python/paddle/incubate/nn/functional/ —
fused_rotary_position_embedding.py, fused_rms_norm.py, swiglu.py,
fused_dropout_add.py, fused_bias_dropout_residual_layer_norm; CUDA kernels
under paddle/phi/kernels/fusion/gpu/).

On TPU the "fusion" is XLA's job — these compositions compile to fused
kernels; rms_norm/rope additionally have Pallas fast paths.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ....core import random as _rng
from ....nn.functional.norm import layer_norm as _layer_norm
from ....nn.functional.norm import rms_norm as _rms_norm
from ....nn.functional.activation import swiglu  # noqa: F401  re-export
from ....ops._helpers import as_tensor, run_op

__all__ = ["fused_rotary_position_embedding", "fused_rms_norm",
           "fused_layer_norm", "fused_dropout_add",
           "fused_bias_dropout_residual_layer_norm", "fused_linear",
           "fused_linear_activation", "swiglu"]


def _rope_rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _rope_rotate_pairwise(x):
    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    out = jnp.stack([-x2, x1], axis=-1)
    return out.reshape(x.shape)


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None,
                                    use_neox_rotary_style=True,
                                    time_major=False, rotary_emb_base=10000.0,
                                    name=None):
    """RoPE over [batch, seq, heads, head_dim]
    (reference: fused_rotary_position_embedding.py; kernel
    phi/kernels/fusion/gpu/fused_rope_kernel.cu)."""
    tensors = [t for t in (q, k, v) if t is not None]
    shapes = as_tensor(tensors[0]).shape
    seq_len, head_dim = shapes[1], shapes[-1]

    if sin is None or cos is None:
        inv = 1.0 / (rotary_emb_base ** (
            jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
        if position_ids is not None:
            from ....ops._helpers import unwrap

            # frequencies straight from the (possibly offset) positions —
            # no table, so decode positions beyond seq_len stay exact
            pid = unwrap(as_tensor(position_ids)).astype(jnp.float32)
            freqs = pid[..., None] * inv  # [batch, seq, head_dim/2]
            if use_neox_rotary_style:
                emb = jnp.concatenate([freqs, freqs], axis=-1)
            else:
                emb = jnp.repeat(freqs, 2, axis=-1)
            cos_arr = jnp.cos(emb)[:, :, None, :]
            sin_arr = jnp.sin(emb)[:, :, None, :]
        else:
            t = jnp.arange(seq_len, dtype=jnp.float32)
            freqs = jnp.outer(t, inv)  # [seq, head_dim/2]
            if use_neox_rotary_style:
                emb = jnp.concatenate([freqs, freqs], axis=-1)
            else:
                emb = jnp.repeat(freqs, 2, axis=-1)
            cos_arr = jnp.cos(emb)[None, :, None, :]
            sin_arr = jnp.sin(emb)[None, :, None, :]
    else:
        from ....ops._helpers import unwrap

        cos_arr = unwrap(as_tensor(cos))
        sin_arr = unwrap(as_tensor(sin))
        if cos_arr.ndim == 2:
            cos_arr = cos_arr[None, :, None, :]
            sin_arr = sin_arr[None, :, None, :]
        if position_ids is not None:
            pid = unwrap(as_tensor(position_ids))  # [batch, seq]
            cos_arr = jnp.squeeze(cos_arr, (0, 2))[pid][:, :, None, :]
            sin_arr = jnp.squeeze(sin_arr, (0, 2))[pid][:, :, None, :]

    rotate = _rope_rotate_half if use_neox_rotary_style \
        else _rope_rotate_pairwise

    def apply_one(t):
        def fn(a):
            af = a.astype(jnp.float32)
            out = af * cos_arr + rotate(af) * sin_arr
            return out.astype(a.dtype)

        return run_op(fn, [as_tensor(t)], name="fused_rope")

    outs = tuple(apply_one(t) if t is not None else None for t in (q, k, v))
    return outs


def _last_axis_norm(begin_norm_axis, x):
    return begin_norm_axis in (-1, x.ndim - 1)


# test hook: force the Pallas dispatch branch on non-TPU backends (the
# kernels run under the interpreter there)
_FORCE_PALLAS = False


def _pallas_norm_ok(x):
    """Gate like flash_attention.attention_impl: TPU backend +
    non-degenerate shape; otherwise the XLA composition path."""
    if jax.default_backend() != "tpu" and not _FORCE_PALLAS:
        return False
    return x.size > 0


def fused_rms_norm(x, norm_weight, norm_bias=None, epsilon=1e-6,
                   begin_norm_axis=-1, bias=None, residual=None,
                   quant_scale=-1, name=None):
    """reference: incubate/nn/functional/fused_rms_norm.py.

    Last-axis case dispatches to the Pallas fused kernel
    (:mod:`paddle_tpu.incubate.nn.pallas.norms`)."""
    if bias is not None:
        x = as_tensor(x) + as_tensor(bias)
    xt = as_tensor(x)
    if residual is not None:
        xt = xt + as_tensor(residual)
    if norm_weight is not None and _last_axis_norm(begin_norm_axis, xt) \
            and _pallas_norm_ok(xt):
        from ..pallas.norms import rms_norm as pallas_rms

        w = as_tensor(norm_weight)
        ts = [xt, w]
        if norm_bias is not None:
            ts.append(as_tensor(norm_bias))
            fn = lambda a, wa, ba: pallas_rms(a, wa, ba, eps=epsilon)
        else:
            fn = lambda a, wa: pallas_rms(a, wa, eps=epsilon)
        out = run_op(fn, ts, name="fused_rms_norm")
    else:
        out = _rms_norm(xt, norm_weight, norm_bias, epsilon, begin_norm_axis)
    if residual is not None:
        return out, xt
    return out


def fused_layer_norm(x, norm_weight, norm_bias=None, epsilon=1e-5,
                     begin_norm_axis=-1, bias=None, residual=None,
                     quant_scale=-1, name=None):
    if bias is not None:
        x = as_tensor(x) + as_tensor(bias)
    if residual is not None:
        x = as_tensor(x) + as_tensor(residual)
        nshape = as_tensor(x).shape[begin_norm_axis:] \
            if begin_norm_axis >= 0 else as_tensor(x).shape[-1:]
        out = _layer_norm(x, nshape, norm_weight, norm_bias, epsilon)
        return out, x
    xt = as_tensor(x)
    nshape = xt.shape[begin_norm_axis:] if begin_norm_axis >= 0 \
        else xt.shape[-1:]
    return _layer_norm(xt, nshape, norm_weight, norm_bias, epsilon)


def fused_dropout_add(x, y, p=0.5, training=True, mode="upscale_in_train",
                      name=None):
    """reference: incubate/nn/functional/fused_dropout_add.py."""
    if not training or p == 0.0:
        return as_tensor(x) + as_tensor(y)
    key = _rng.next_key()

    def fn(a, b):
        keep = jax.random.bernoulli(key, 1.0 - p, a.shape)
        if mode == "upscale_in_train":
            d = jnp.where(keep, a / (1.0 - p), 0.0)
        else:
            d = jnp.where(keep, a, 0.0)
        return (d + b).astype(a.dtype)

    return run_op(fn, [as_tensor(x), as_tensor(y)], name="fused_dropout_add")


def fused_bias_dropout_residual_layer_norm(x, residual, bias=None,
                                           ln_scale=None, ln_bias=None,
                                           dropout_rate=0.5, ln_epsilon=1e-5,
                                           training=True, mode="upscale_in_train",
                                           name=None):
    """reference: fused_bias_dropout_residual_layer_norm kernel
    (phi/kernels/fusion/gpu/fused_bias_dropout_residual_layer_norm_kernel.cu)."""
    h = as_tensor(x)
    if bias is not None:
        h = h + as_tensor(bias)
    h = fused_dropout_add(h, residual, p=dropout_rate, training=training,
                          mode=mode)
    nshape = h.shape[-1:]
    return _layer_norm(h, nshape, ln_scale, ln_bias, ln_epsilon)


def fused_linear(x, weight, bias=None, transpose_weight=False, name=None):
    def fn(*arrs):
        a, w = arrs[0], arrs[1]
        if transpose_weight:
            w = w.T
        out = jnp.matmul(a, w)
        if len(arrs) > 2:
            out = out + arrs[2]
        return out

    ts = [as_tensor(x), as_tensor(weight)]
    if bias is not None:
        ts.append(as_tensor(bias))
    return run_op(fn, ts, name="fused_linear")


def fused_linear_activation(x, y, bias, trans_x=False, trans_y=False,
                            activation="gelu", name=None):
    """fused_gemm_epilogue analog (reference:
    phi/kernels/fusion/gpu/fused_gemm_epilogue_kernel.cu)."""
    act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
           "none": lambda v: v}[activation]

    def fn(a, w, b):
        if trans_x:
            a = jnp.swapaxes(a, -1, -2)
        if trans_y:
            w = jnp.swapaxes(w, -1, -2)
        return act(jnp.matmul(a, w) + b)

    return run_op(fn, [as_tensor(x), as_tensor(y), as_tensor(bias)],
                  name="fused_linear_activation")
