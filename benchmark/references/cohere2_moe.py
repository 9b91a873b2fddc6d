"""Plain reference for the Command A+ configurations (CohereLabs
command-a-plus-05-2026, ``model_type`` ``cohere2_moe``): parallel blocks of
sliding-window and global attention beside sigmoid-routed SwiGLU experts
and averaged shared experts, of which the model may hold a SHARE of the
routed ones (one chip of an expert-parallel deployment). In
straightforward float32 ``jax.numpy`` at the highest matmul precision:
attention as a plain causal softmax with the window as a mask, the experts
one at a time on the tokens routed to them; no cache, no kernel, no
grouped matmul, and nothing imported from the program: it reads the run's
own parameters by the names ``named_parameters()`` gives them and casts
each to float32 as it goes.

``config`` is the configuration file's object (or the program config's
``published()``), read by the published ``config.json``'s keys. The
equations, per token, ``C`` = hidden size, one residual stream x, LN(z) =
(z - mean z) / sqrt(var z + ``layer_norm_eps``) times the norm's weight
(no bias):

  h = LN(x);  x <- x + Attn_i(h) + MoE_i(h)     for layer i;
  after the last layer, logits = LN(x) E^T x ``logit_scale`` (E the
  embedding: the head is tied).

Attn (H = ``num_attention_heads`` heads of d = ``head_dim``, K =
  ``num_key_value_heads``, each shared by H / K query heads, no bias):
  q = h W_q, k = h W_k, v = h W_v; a ``sliding_attention`` layer (by
  ``layer_types``) rotates q and k at position t by GPT-J's interleaved
  RoPE over the whole head, for i < d / 2 with
  f_i = ``rope_theta`` ^ (-2 i / d):
    (y[2i], y[2i+1]) = (x[2i] cos(t f_i) - x[2i+1] sin(t f_i),
                        x[2i+1] cos(t f_i) + x[2i] sin(t f_i)),
  and query t sees key j iff t - W < j <= t (W = ``sliding_window``); a
  ``full_attention`` layer has no position encoding and query t sees
  every j <= t. Softmax of q.k d^-0.5 over the keys seen; o =
  concat(heads) W_o.
MoE (E = the router's width, k = ``num_experts_per_tok``): s =
  sigmoid(h W_r); the k largest of s are chosen (no bias, no group
  limit); weights s[chosen] / sum(s[chosen]) (``norm_topk_prob``); an
  expert is SwiGLU, (SiLU(h G) * (h U)) D, G and U the two halves of its
  ``experts_gate_up`` [C, 2 I]; routed = sum over the chosen e that are
  HELD of w_e expert_e(h); shared = (1 / S) sum_j shared_j(h), the S =
  ``num_shared_experts`` shared experts stored side by side (column block
  j of the gate and up projections, row block j of the down projection);
  MoE(h) = routed + shared. No token is dropped.

Departures from the published description:

* **The share.** The model holds the experts ``[expert_first,
  expert_first + num_experts_held)`` of the E routed ones (the stacks have
  that many entries; E is the router weight's width). The choice and the
  weights are over all E; the chosen experts that are absent add nothing
  here (on the deployment their terms come from the other chips), and
  that partial result goes on to the next layer. With every expert held
  this is the published layer.
* What ``config.json`` leaves open (the shared experts' average, the
  LayerNorm's form, no position encoding in the full layers, no router
  bias) is listed in the configuration file's ``assumed``; the vision
  tower is not served.

To fit beside a serving engine's arrays on one chip, each expert's
weights are cast alone and run on the tokens routed to it (their indices
found on the host, padded to one bucket size so that one program serves
all experts), the shared experts go one at a time and a block of rows at
a time, attention goes a block of queries at a time (a windowed layer's
block reading only the keys its window can reach), and the head a block
of rows at a time, straight into host memory.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 32           # queries scored at a time
ROW_BLOCK = 1024       # rows of a shared expert or of the head at a time


def _highest(fn):
    @functools.wraps(fn)
    def run(*a, **k):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **k)
    return run


def _ln(x, w, eps):
    c = x - x.mean(-1, keepdims=True)
    return c / jnp.sqrt((c * c).mean(-1, keepdims=True) + eps) \
        * w.astype(F32)


def _rotate(x, pos, theta):
    """GPT-J's interleaved RoPE of x [s, heads, d] at positions pos [s]."""
    d = x.shape[-1]
    f = theta ** (-2.0 * jnp.arange(d // 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None, None] * f
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads",
                                             "head_dim", "window", "theta"))
@_highest
def _attention(h, p, heads, kv_heads, head_dim, window, theta):
    """Causal grouped-query attention of one sequence, h [s, C] its normed
    input; ``window`` None: every earlier key, no rotation."""
    s = h.shape[0]
    rep = heads // kv_heads
    wq = p["self_attn.q_proj.weight"].astype(F32)
    wo = p["self_attn.o_proj.weight"].astype(F32)
    k = (h @ p["self_attn.k_proj.weight"].astype(F32)).reshape(
        s, kv_heads, head_dim)
    v = (h @ p["self_attn.v_proj.weight"].astype(F32)).reshape(
        s, kv_heads, head_dim)
    if window is not None:
        k = _rotate(k, jnp.arange(s), theta)
    n_keys = s if window is None else min(s, window + Q_BLOCK)
    pad = -s % Q_BLOCK
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, Q_BLOCK, h.shape[1])

    def block(args):
        hq, t0 = args
        q = (hq @ wq).reshape(Q_BLOCK, heads, head_dim)
        tq = t0 + jnp.arange(Q_BLOCK)
        if window is not None:
            q = _rotate(q, tq, theta)
        start = jnp.clip(t0 - (window or s) + 1, 0, s - n_keys)
        kb = jax.lax.dynamic_slice_in_dim(k, start, n_keys)
        vb = jax.lax.dynamic_slice_in_dim(v, start, n_keys)
        kpos = start + jnp.arange(n_keys)
        seen = kpos[None, :] <= tq[:, None]
        if window is not None:
            seen = seen & (kpos[None, :] > tq[:, None] - window)
        qg = q.reshape(Q_BLOCK, kv_heads, rep, head_dim)
        sc = jnp.einsum("qgrd,kgd->grqk", qg, kb) * head_dim ** -0.5
        sc = jnp.where(seen[None, None], sc, -jnp.inf)
        o = jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(sc, -1), vb)
        return o.reshape(Q_BLOCK, heads * head_dim) @ wo

    o = jax.lax.map(block, (hb, jnp.arange(hb.shape[0]) * Q_BLOCK))
    return o.reshape(-1, o.shape[-1])[:s]


@functools.partial(jax.jit, static_argnames=("eps",))
@_highest
def _norm(z, w, eps):
    return _ln(z, w, eps)


@_highest
def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))) \
        @ down.astype(F32)


@jax.jit
@_highest
def _shared_one(h, gate, up, down):
    """One shared expert over every row, a block of rows at a time."""
    s = h.shape[0]
    pad = -s % ROW_BLOCK
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, ROW_BLOCK, h.shape[1])
    y = jax.lax.map(lambda b: _swiglu(b, gate, up, down), hb)
    return y.reshape(-1, y.shape[-1])[:s]


@jax.jit
@_highest
def _scores(h, gate_w):
    return jax.nn.sigmoid(h @ gate_w.astype(F32))


@jax.jit
@_highest
def _expert_on(h, y, idx, wt, gate_up, down):
    """y += wt x expert_e(h[idx]) at rows idx (weight 0: padding)."""
    i = down.shape[0]
    out = _swiglu(h[idx], gate_up[:, :i], gate_up[:, i:], down)
    return y.at[idx].add(out * wt[:, None])


def route(h, p, config):
    """-> (chosen experts [s, k] of the router's E, their weights [s, k])
    on the host."""
    k = int(config["num_experts_per_tok"])
    s = np.asarray(_scores(h, p["mlp.gate_weight"]), np.float64)
    top = np.argsort(-s, axis=1, kind="stable")[:, :k]
    wt = np.take_along_axis(s, top, axis=1)
    if config.get("norm_topk_prob", True):
        wt = wt / wt.sum(1, keepdims=True)
    return top, wt


def held_range(p, config):
    """-> (first, held): the routed experts this model holds."""
    held = p["mlp.experts_gate_up"].shape[0]
    first = int(config.get("expert_first", 0))
    assert held == int(config.get("num_experts_held", held))
    assert first + held <= p["mlp.gate_weight"].shape[1]
    return first, held


def routed(h, p, config):
    """The held experts' part of the routed sum: float32 [s, C]."""
    top, wt = route(h, p, config)
    first, held = held_range(p, config)
    counts = np.bincount(top.reshape(-1),
                         minlength=first + held)[first:first + held]
    bucket = 1
    while bucket < max(counts.max(), 1):
        bucket *= 2
    y = jnp.zeros(h.shape, F32)
    for e in range(held):
        tok, slot = np.nonzero(top == first + e)
        idx = np.zeros(bucket, np.int32)
        w = np.zeros(bucket, np.float32)
        idx[:len(tok)], w[:len(tok)] = tok, wt[tok, slot]
        y = _expert_on(h, y, jnp.asarray(idx), jnp.asarray(w),
                       p["mlp.experts_gate_up"][e], p["mlp.experts_down"][e])
    return y


@jax.jit
def _add(x, y, scale=1.0):
    return x + y * scale


def shared(h, p, config):
    """The average of the shared experts: float32 [s, C]."""
    n = int(config["num_shared_experts"])
    i = p["mlp.shared_experts.down_proj.weight"].shape[0] // n
    y = jnp.zeros(h.shape, F32)
    for j in range(n):
        cols = slice(j * i, (j + 1) * i)
        y = _add(y, _shared_one(
            h, p["mlp.shared_experts.gate_proj.weight"][:, cols],
            p["mlp.shared_experts.up_proj.weight"][:, cols],
            p["mlp.shared_experts.down_proj.weight"][cols]), 1.0 / n)
    return y


def experts(h, p, config):
    """An expert layer on its normed input: the held experts' part and
    the shared experts' average."""
    return _add(routed(h, p, config), shared(h, p, config))


def hidden(params, ids, config):
    """The residual stream of one sequence ``ids`` [s] after the last
    layer, before the final norm: float32 [s, C]."""
    eps = float(config["layer_norm_eps"])
    attn = dict(heads=int(config["num_attention_heads"]),
                kv_heads=int(config["num_key_value_heads"]),
                head_dim=int(config["head_dim"]),
                theta=float(config["rope_theta"]))
    x = params["model.embed_tokens.weight"][jnp.asarray(ids, jnp.int32)] \
        .astype(F32)
    types = config["layer_types"]
    assert len(types) == int(config["num_hidden_layers"])
    for i, kind in enumerate(types):
        pre = "model.layers.%d." % i
        p = {k[len(pre):]: v for k, v in params.items()
             if k.startswith(pre)}
        h = _norm(x, p["input_layernorm.weight"], eps=eps)
        window = int(config["sliding_window"]) \
            if kind == "sliding_attention" else None
        x = _add(_add(x, _attention(h, p, window=window, **attn)),
                 experts(h, p, config))
    return x


@functools.partial(jax.jit, static_argnames=("eps", "scale"))
@_highest
def _head(x, norm_w, embed, eps, scale):
    return _ln(x, norm_w, eps) @ embed.astype(F32).T * scale


def logits(params, ids, config):
    """float32 logits [b, s, vocab] of a full forward pass, a sequence
    and a block of rows at a time, gathered in host memory."""
    ids = np.asarray(ids)
    embed = params["model.embed_tokens.weight"]
    out = np.empty(ids.shape + (embed.shape[0],), np.float32)
    for b in range(ids.shape[0]):
        x = hidden(params, ids[b], config)
        for r0 in range(0, x.shape[0], ROW_BLOCK):
            out[b, r0:r0 + ROW_BLOCK] = np.asarray(_head(
                x[r0:r0 + ROW_BLOCK], params["model.norm.weight"], embed,
                eps=float(config["layer_norm_eps"]),
                scale=float(config["logit_scale"])))
    return out
