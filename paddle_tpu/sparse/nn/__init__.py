"""paddle.sparse.nn (reference: python/paddle/sparse/nn/__init__.py —
ReLU/ReLU6/LeakyReLU/Softmax/BatchNorm/SyncBatchNorm/Conv2D/Conv3D/
SubmConv2D/SubmConv3D/MaxPool3D over phi/kernels/sparse/).

TPU-native sparse conv: the classic rulebook formulation
(gather -> GEMM -> scatter-add). The rulebook (which input nnz pairs with
which output site under each kernel offset) is integer bookkeeping built
host-side per step — the FLOPs all live in one [pairs, Cin] x [Cin, Cout]
matmul per kernel offset, which is exactly MXU-shaped work. Submanifold
conv fixes the output sites to the input sites (SubmConv*), standard conv
enumerates the dilated neighborhood.
"""
from __future__ import annotations

import math
from typing import Sequence

import jax.numpy as jnp
import numpy as np
from jax.experimental import sparse as jsparse

from ...core.tensor import Tensor
from ...nn.layer.layers import Layer
from .. import SparseCooTensor, SparseCsrTensor, _as_bcoo

__all__ = ["ReLU", "ReLU6", "LeakyReLU", "Softmax", "BatchNorm",
           "SyncBatchNorm", "Conv2D", "Conv3D", "SubmConv2D", "SubmConv3D",
           "MaxPool3D"]


# ----------------------------------------------------------- activations
class _ValueAct(Layer):
    def forward(self, x):
        bc = _as_bcoo(x)
        out = SparseCooTensor(jsparse.BCOO((self._fn(bc.data), bc.indices),
                                           shape=bc.shape))
        return (out.to_sparse_csr() if isinstance(x, SparseCsrTensor)
                else out)


class ReLU(_ValueAct):
    def _fn(self, d):
        return jnp.maximum(d, 0)


class ReLU6(_ValueAct):
    def _fn(self, d):
        return jnp.clip(d, 0, 6)


class LeakyReLU(_ValueAct):
    def __init__(self, negative_slope=0.01):
        super().__init__()
        self.negative_slope = float(negative_slope)

    def _fn(self, d):
        return jnp.where(d >= 0, d, self.negative_slope * d)


class Softmax(Layer):
    """Softmax over the non-zero entries of each row (reference:
    sparse/nn/layer/activation.py Softmax — CSR, axis=-1 only)."""

    def __init__(self, axis=-1):
        super().__init__()
        if axis != -1:
            raise ValueError("sparse Softmax only supports axis=-1")

    def forward(self, x):
        csr = x if isinstance(x, SparseCsrTensor) else x.to_sparse_csr()
        crows = np.asarray(csr._crows)
        vals = csr._values
        out_vals = jnp.zeros_like(vals)
        # per-row softmax over the stored values; rows are ragged so this
        # builds a segment id vector and uses segment ops (one pass)
        seg = np.repeat(np.arange(len(crows) - 1), np.diff(crows))
        seg_j = jnp.asarray(seg, dtype=jnp.int32)
        n_rows = len(crows) - 1
        import jax

        mx = jax.ops.segment_max(vals, seg_j, num_segments=n_rows)
        ex = jnp.exp(vals - mx[seg_j])
        den = jax.ops.segment_sum(ex, seg_j, num_segments=n_rows)
        out_vals = ex / den[seg_j]
        out = SparseCsrTensor(csr._crows, csr._cols, out_vals, csr.shape)
        return out if isinstance(x, SparseCsrTensor) else out.to_sparse_coo()


# ----------------------------------------------------------- batch norm
class BatchNorm(Layer):
    """BatchNorm over the channel (last) axis of a COO tensor's values
    (reference: sparse/nn/layer/norm.py BatchNorm — NDHWC)."""

    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NDHWC",
                 use_global_stats=None, name=None):
        super().__init__()
        self._num_features = num_features
        self._momentum = float(momentum)
        self._epsilon = float(epsilon)
        self.weight = self.create_parameter(
            [num_features], default_initializer=lambda s, dt=None: jnp.ones(s))
        self.bias = self.create_parameter(
            [num_features], default_initializer=lambda s, dt=None: jnp.zeros(s))
        self._mean = jnp.zeros((num_features,))
        self._variance = jnp.ones((num_features,))
        self._use_global_stats = use_global_stats

    def forward(self, x):
        bc = _as_bcoo(x)
        vals = bc.data  # [nnz, C]
        if self.training and not self._use_global_stats:
            mean = jnp.mean(vals, axis=0)
            var = jnp.var(vals, axis=0)
            self._mean = (self._momentum * self._mean
                          + (1 - self._momentum) * mean)
            self._variance = (self._momentum * self._variance
                              + (1 - self._momentum) * var)
        else:
            mean, var = self._mean, self._variance
        normed = (vals - mean) / jnp.sqrt(var + self._epsilon)
        out_vals = normed * self.weight._data + self.bias._data
        return SparseCooTensor(jsparse.BCOO((out_vals, bc.indices),
                                            shape=bc.shape))


class SyncBatchNorm(BatchNorm):
    """Cross-replica BatchNorm: under pmap/shard_map the mean/var reduce
    with a psum (reference: sparse/nn/layer/norm.py SyncBatchNorm); on a
    single device it equals BatchNorm."""

    def forward(self, x):
        import jax

        bc = _as_bcoo(x)
        vals = bc.data
        if self.training:
            mean = jnp.mean(vals, axis=0)
            var = jnp.var(vals, axis=0)
            # inside a collective context, all-reduce the statistics
            # single-device fallback: NameError ("unbound axis name") is
            # raised at TRACE time on every rank identically when there
            # is no sync_bn axis, so ranks cannot diverge here
            try:
                mean = jax.lax.pmean(mean, axis_name="sync_bn")  # ptlint: disable=collective-consistency
                var = jax.lax.pmean(var, axis_name="sync_bn")  # ptlint: disable=collective-consistency
            except NameError:
                pass
        else:
            mean, var = self._mean, self._variance
        normed = (vals - mean) / jnp.sqrt(var + self._epsilon)
        out_vals = normed * self.weight._data + self.bias._data
        return SparseCooTensor(jsparse.BCOO((out_vals, bc.indices),
                                            shape=bc.shape))


# ----------------------------------------------------------- convolution
def _tupled(v, n):
    if isinstance(v, (list, tuple)):
        return tuple(int(s) for s in v)
    return (int(v),) * n


def _build_rulebook(coords, spatial, ksize, stride, padding, dilation,
                    subm):
    """Rulebook for an ndim sparse conv.

    coords: [nnz, 1+ndim] int array (batch + spatial), already unique.
    Returns (out_coords [m,1+ndim], rules: list over kernel offsets of
    (in_idx, out_idx) integer arrays).
    """
    ndim = len(spatial)
    offsets = np.stack(np.meshgrid(*[np.arange(k) for k in ksize],
                                   indexing="ij"), -1).reshape(-1, ndim)
    in_map = {tuple(c): i for i, c in enumerate(coords.tolist())}
    if subm:
        out_coords = coords
        out_map = in_map
        out_spatial = list(spatial)
    else:
        out_spatial = [(spatial[d] + 2 * padding[d]
                        - dilation[d] * (ksize[d] - 1) - 1) // stride[d] + 1
                       for d in range(ndim)]
        out_map = {}
        out_list = []
        for c in coords.tolist():
            b = c[0]
            for off in offsets:
                oc = []
                ok = True
                for d in range(ndim):
                    num = c[1 + d] + padding[d] - off[d] * dilation[d]
                    if num % stride[d]:
                        ok = False
                        break
                    o = num // stride[d]
                    if o < 0 or o >= out_spatial[d]:
                        ok = False
                        break
                    oc.append(o)
                if ok:
                    key = (b, *oc)
                    if key not in out_map:
                        out_map[key] = len(out_list)
                        out_list.append(key)
        out_coords = np.asarray(sorted(out_list), dtype=coords.dtype) \
            if out_list else np.zeros((0, 1 + ndim), coords.dtype)
        out_map = {tuple(c): i for i, c in enumerate(out_coords.tolist())}
    rules = []
    for off in offsets:
        ins, outs = [], []
        if subm:
            # center-aligned: out site o pulls in site o + (off - center)*dil
            for key, oi in out_map.items():
                ic = [key[0]]
                ok = True
                for d in range(ndim):
                    center = (ksize[d] - 1) // 2
                    i = key[1 + d] + (off[d] - center) * dilation[d]
                    if i < 0 or i >= spatial[d]:
                        ok = False
                        break
                    ic.append(i)
                if ok:
                    ii = in_map.get(tuple(ic))
                    if ii is not None:
                        ins.append(ii)
                        outs.append(oi)
        else:
            for key, ii in in_map.items():
                b = key[0]
                oc = [b]
                ok = True
                for d in range(ndim):
                    num = key[1 + d] + padding[d] - off[d] * dilation[d]
                    if num % stride[d]:
                        ok = False
                        break
                    o = num // stride[d]
                    if o < 0 or o >= out_spatial[d]:
                        ok = False
                        break
                    oc.append(o)
                if ok:
                    oi = out_map.get(tuple(oc))
                    if oi is not None:
                        ins.append(ii)
                        outs.append(oi)
        rules.append((np.asarray(ins, np.int32), np.asarray(outs, np.int32)))
    return out_coords, out_spatial, rules


class _SparseConv(Layer):
    _ndim = 3
    _subm = False

    def __init__(self, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, dilation=1, groups=1, padding_mode="zeros",
                 weight_attr=None, bias_attr=None, data_format=None):
        super().__init__()
        n = self._ndim
        self._in = in_channels
        self._out = out_channels
        self._ksize = _tupled(kernel_size, n)
        self._stride = _tupled(stride, n)
        self._padding = _tupled(padding, n)
        self._dilation = _tupled(dilation, n)
        k = 1.0 / math.sqrt(in_channels * int(np.prod(self._ksize)))
        wshape = self._ksize + (in_channels, out_channels)
        import jax

        from ...core import random as _rng

        self.weight = self.create_parameter(
            list(wshape),
            default_initializer=lambda s, dt=None: jax.random.uniform(
                _rng.next_key(), s, minval=-k, maxval=k))
        if bias_attr is not False:
            self.bias = self.create_parameter(
                [out_channels],
                default_initializer=lambda s, dt=None: jnp.zeros(s))
        else:
            self.bias = None

    def forward(self, x):
        bc = jsparse.bcoo_sum_duplicates(_as_bcoo(x))
        coords = np.asarray(bc.indices)  # [nnz, 1+ndim] (+channel dense)
        spatial = bc.shape[1:1 + self._ndim]
        out_coords, out_spatial, rules = _build_rulebook(
            coords, spatial, self._ksize, self._stride, self._padding,
            self._dilation, self._subm)
        n_out = len(out_coords)
        vals = bc.data  # [nnz, Cin]
        w = self.weight._data.reshape(-1, self._in, self._out)
        out_vals = jnp.zeros((n_out, self._out), vals.dtype)
        for ki, (ins, outs) in enumerate(rules):
            if not len(ins):
                continue
            gathered = vals[jnp.asarray(ins)]          # [pairs, Cin]
            prod = gathered @ w[ki]                    # MXU GEMM
            out_vals = out_vals.at[jnp.asarray(outs)].add(prod)
        if self.bias is not None:
            out_vals = out_vals + self.bias._data
        out_shape = ((bc.shape[0],) + tuple(out_spatial) + (self._out,))
        return SparseCooTensor(jsparse.BCOO(
            (out_vals, jnp.asarray(out_coords.astype(np.int32))),
            shape=out_shape))


class Conv3D(_SparseConv):
    """Sparse 3D conv, NDHWC (reference: sparse/nn/layer/conv.py
    Conv3D)."""

    _ndim = 3
    _subm = False


class SubmConv3D(_SparseConv):
    """Submanifold sparse 3D conv — output sites == input sites
    (reference: sparse/nn/layer/conv.py SubmConv3D)."""

    _ndim = 3
    _subm = True


class Conv2D(_SparseConv):
    _ndim = 2
    _subm = False


class SubmConv2D(_SparseConv):
    _ndim = 2
    _subm = True


class MaxPool3D(Layer):
    """Sparse max pooling over NDHWC COO input (reference:
    sparse/nn/layer/pooling.py MaxPool3D)."""

    def __init__(self, kernel_size, stride=None, padding=0,
                 data_format="NDHWC", name=None):
        super().__init__()
        self._ksize = _tupled(kernel_size, 3)
        self._stride = _tupled(stride if stride is not None
                               else kernel_size, 3)
        self._padding = _tupled(padding, 3)

    def forward(self, x):
        bc = jsparse.bcoo_sum_duplicates(_as_bcoo(x))
        coords = np.asarray(bc.indices)
        spatial = bc.shape[1:4]
        out_coords, out_spatial, rules = _build_rulebook(
            coords, spatial, self._ksize, self._stride, self._padding,
            (1, 1, 1), False)
        n_out = len(out_coords)
        c = bc.shape[-1]
        vals = bc.data
        out_vals = jnp.full((n_out, c), -jnp.inf, vals.dtype)
        for ins, outs in rules:
            if not len(ins):
                continue
            out_vals = out_vals.at[jnp.asarray(outs)].max(
                vals[jnp.asarray(ins)])
        out_vals = jnp.where(jnp.isfinite(out_vals), out_vals, 0.0)
        out_shape = ((bc.shape[0],) + tuple(out_spatial) + (c,))
        return SparseCooTensor(jsparse.BCOO(
            (out_vals, jnp.asarray(out_coords.astype(np.int32))),
            shape=out_shape))
