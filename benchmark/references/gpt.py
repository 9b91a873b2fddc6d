"""Plain reference for the GPT-3 configurations: the decoder of Brown et
al. 2020 (GPT-2's pre-LayerNorm block, learned positions, tanh GELU, tied
output embedding) in straightforward float32 ``jax.numpy``. No kernels,
no cache, no batching tricks, and nothing imported from the program: it
reads the run's own parameters by the names ``named_parameters()`` gives
them, one layer at a time, and casts each to float32 as it goes.

Departures from the paper, all stated in the configuration files'
``assumed``: dense attention in every layer (the paper alternates dense
and locally banded sparse layers), vocabulary padded to 50304, biases on
every linear layer, LayerNorm epsilon 1e-5.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _ln(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w.astype(F32) + b.astype(F32)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def _block(x, p, heads, eps):
    """One decoder block over ``x`` [b, s, h]; one sequence at a time, so
    the [heads, s, s] scores of a single sequence are all that is live."""
    def one(xs):
        s, h = xs.shape
        d = h // heads
        y = _ln(xs, p["ln_1.weight"], p["ln_1.bias"], eps)
        qkv = y @ p["attn.qkv_proj.weight"].astype(F32) \
            + p["attn.qkv_proj.bias"].astype(F32)
        q, k, v = (qkv.reshape(s, 3, heads, d)[:, i] for i in range(3))
        sc = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
        mask = jnp.tril(jnp.ones((s, s), bool))
        sc = jnp.where(mask[None], sc, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
        xs = xs + a.reshape(s, h) @ p["attn.out_proj.weight"].astype(F32) \
            + p["attn.out_proj.bias"].astype(F32)
        y = _ln(xs, p["ln_2.weight"], p["ln_2.bias"], eps)
        y = _gelu(y @ p["mlp.fc1.weight"].astype(F32)
                  + p["mlp.fc1.bias"].astype(F32))
        return xs + y @ p["mlp.fc2.weight"].astype(F32) \
            + p["mlp.fc2.bias"].astype(F32)

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, x)


@jax.jit
def _embed(ids, wte, wpe):
    pos = jnp.arange(ids.shape[1])
    return wte.astype(F32)[ids] + wpe.astype(F32)[pos][None]


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits(x, lnw, lnb, wte, eps):
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda xs: _ln(xs, lnw, lnb, eps) @ wte.astype(F32).T, x)


@functools.partial(jax.jit, static_argnames=("eps",))
def _loss(x, labels, lnw, lnb, wte, eps):
    """Mean next-token cross entropy; labels arrive already shifted."""
    def one(args):
        xs, ls = args
        lg = _ln(xs, lnw, lnb, eps) @ wte.astype(F32).T
        lse = jax.scipy.special.logsumexp(lg, -1)
        return (lse - jnp.take_along_axis(lg, ls[:, None], -1)[:, 0]).sum()

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, (x, labels)).sum() / labels.size


def _hidden(params, ids, config):
    eps = float(config.get("layer_norm_eps", 1e-5))
    x = _embed(jnp.asarray(ids, jnp.int32), params["gpt.wte.weight"],
               params["gpt.wpe.weight"])
    for i in range(int(config["num_layers"])):
        pre = "gpt.h.%d." % i
        p = {k[len(pre):]: v for k, v in params.items()
             if k.startswith(pre)}
        x = _block(x, p, heads=int(config["num_heads"]), eps=eps)
    return x, eps


def loss(params, ids, labels, config) -> float:
    x, eps = _hidden(params, ids, config)
    return float(_loss(x, jnp.asarray(labels, jnp.int32),
                       params["gpt.ln_f.weight"], params["gpt.ln_f.bias"],
                       params["gpt.wte.weight"], eps=eps))


def logits(params, ids, config):
    """float32 logits [b, s, vocab] of a full forward pass."""
    x, eps = _hidden(params, ids, config)
    return _logits(x, params["gpt.ln_f.weight"], params["gpt.ln_f.bias"],
                   params["gpt.wte.weight"], eps=eps)
