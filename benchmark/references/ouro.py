"""Plain reference for the Ouro LoopLM configurations (ByteDance Ouro
1.4B / 2.6B, arXiv:2510.25741, and the modelling code published with the
weights): one stack of pre- and post-normed ("sandwich") Llama-style
layers, applied ``total_ut_steps`` times on shared weights, the final
norm after every pass, an exit gate on each pass's hidden state. In
straightforward float32 ``jax.numpy``: no kernels, no cache, no batching
tricks, and nothing imported from the program: it reads the run's own
parameters by the names ``named_parameters()`` gives them, one layer at
a time, and casts each to float32 as it goes. Without a cache, "the keys
and values that pass r produced at layer l" are simply that pass's own.

``config`` is the configuration file's object, read by the published
``config.json``'s keys: ``num_hidden_layers``, ``num_attention_heads``,
``num_key_value_heads``, ``rms_norm_eps``, ``rope_theta``,
``total_ut_steps``, ``early_exit_threshold``.

Departures from the published code, all stated in the configuration
file's ``assumed``: none in the arithmetic. What ``config.json`` does not
say is taken from the modelling code: no bias on the attention and MLP
projections; four RMSNorms a block (the sublayer's output is normed
before the residual add); the final norm closes every pass and feeds the
next; rotate-half RoPE over the whole head; the gate is ``Linear(hidden,
1)`` with a bias, exit mass ``p_r = lambda_r * prod_{j<r}(1 -
lambda_j)`` with the remainder on the last pass, a token's logits read
from the first pass whose cumulative mass reaches
``early_exit_threshold`` (the last pass at the published threshold of
1); every pass runs for every token.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, theta):
    """Rotate-half RoPE of ``x`` [s, heads, d] at positions 0..s-1."""
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None]        # [s, d/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps",
                                             "theta"))
def _block(x, p, heads, kv_heads, eps, theta):
    """One sandwich block over ``x`` [b, s, h]; one sequence at a time,
    so the [heads, s, s] scores of a single sequence are all that is
    live."""
    def one(xs):
        s, h = xs.shape
        d = h // heads
        u = _rms(xs, p["input_layernorm.weight"], eps)
        q = (u @ p["self_attn.q_proj.weight"].astype(F32)) \
            .reshape(s, heads, d)
        k = (u @ p["self_attn.k_proj.weight"].astype(F32)) \
            .reshape(s, kv_heads, d)
        v = (u @ p["self_attn.v_proj.weight"].astype(F32)) \
            .reshape(s, kv_heads, d)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
        mask = jnp.tril(jnp.ones((s, s), bool))
        sc = jnp.where(mask[None], sc, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
        a = a.reshape(s, h) @ p["self_attn.o_proj.weight"].astype(F32)
        xs = xs + _rms(a, p["input_layernorm_2.weight"], eps)
        u = _rms(xs, p["post_attention_layernorm.weight"], eps)
        m = jax.nn.silu(u @ p["mlp.gate_proj.weight"].astype(F32)) \
            * (u @ p["mlp.up_proj.weight"].astype(F32))
        m = m @ p["mlp.down_proj.weight"].astype(F32)
        return xs + _rms(m, p["post_attention_layernorm_2.weight"], eps)

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("eps",))
def _close_pass(x, norm_w, gate_w, gate_b, eps):
    """The final norm that ends a pass, and the exit gate on its output:
    -> (h_r [b, s, h], lambda_r [b, s])."""
    with jax.default_matmul_precision("highest"):
        h = _rms(x, norm_w, eps)
        lam = jax.nn.sigmoid(h @ gate_w.astype(F32) + gate_b.astype(F32))
    return h, lam[..., 0]


@jax.jit
def _head(h, w):
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(lambda hs: hs @ w.astype(F32), h)


def _passes(params, ids, config):
    """-> the hidden state h_r and the gate value lambda_r of every
    pass."""
    eps = float(config["rms_norm_eps"])
    ids = jnp.asarray(ids, jnp.int32)
    x = params["ouro.embed_tokens.weight"].astype(F32)[ids]
    hs, lambdas = [], []
    for _ in range(int(config["total_ut_steps"])):
        for i in range(int(config["num_hidden_layers"])):
            pre = "ouro.layers.%d." % i
            p = {k[len(pre):]: v for k, v in params.items()
                 if k.startswith(pre)}
            x = _block(x, p, heads=int(config["num_attention_heads"]),
                       kv_heads=int(config["num_key_value_heads"]),
                       eps=eps, theta=float(config["rope_theta"]))
        x, lam = _close_pass(x, params["ouro.norm.weight"],
                             params["ouro.early_exit_gate.weight"],
                             params["ouro.early_exit_gate.bias"], eps=eps)
        hs.append(x)
        lambdas.append(lam)
    return hs, lambdas


def exit_passes(params, ids, config):
    """The pass (1-based) at which each token leaves, [b, s]: the first
    whose cumulative exit mass reaches ``early_exit_threshold``, the last
    where none does."""
    _, lambdas = _passes(params, ids, config)
    return _exit_of(lambdas, float(config["early_exit_threshold"]))


def _exit_of(lambdas, threshold):
    n = len(lambdas)
    remaining, cum = jnp.ones_like(lambdas[0]), jnp.zeros_like(lambdas[0])
    out = jnp.full(lambdas[0].shape, n, jnp.int32)
    for r in range(n - 1):
        cum = cum + lambdas[r] * remaining
        remaining = remaining * (1.0 - lambdas[r])
        out = jnp.where((cum >= threshold) & (out == n), r + 1, out)
    return out


def logits(params, ids, config):
    """float32 logits [b, s, vocab] of a full forward pass."""
    hs, lambdas = _passes(params, ids, config)
    ex = _exit_of(lambdas, float(config["early_exit_threshold"]))
    h = hs[-1]
    for r in range(len(hs) - 1):
        h = jnp.where((ex == r + 1)[..., None], hs[r], h)
    w = params["lm_head.weight"] if "lm_head.weight" in params \
        else params["ouro.embed_tokens.weight"].T
    return _head(h, w)
