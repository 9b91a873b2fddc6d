"""Plain reference for the Nemotron-H configurations (nvidia
NVIDIA-Nemotron-3-Nano-30B-A3B, ``model_type`` ``nemotron_h``): a hybrid
stack of Mamba-2 mixers, grouped-query attention and sigmoid-routed relu²
experts beside a shared one, of which the model may hold a SHARE (one
chip of an expert-parallel deployment). In straightforward float32
``jax.numpy`` at the highest matmul precision: the state-space layer as
its SEQUENTIAL recurrence (a ``lax.scan`` over positions, no chunked
algorithm and no kernel), the conv over the whole sequence, attention as
a plain causal softmax, the experts one at a time on the tokens routed to
them; no cache, no grouped matmul, and nothing imported from the
program: it reads the run's own parameters by the names
``named_parameters()`` gives them and casts each to float32 as it goes.

``config`` is the configuration file's object (or the program config's
``published()``), read by the published ``config.json``'s keys. The
equations, per token, ``C`` = hidden size, one residual stream x:

  x <- x + f_i(RMSNorm(x)) for block i, f_i by the i-th letter of
  ``hybrid_override_pattern``; after the last block the final RMSNorm and
  the untied head. eps = ``layer_norm_epsilon`` everywhere.

``M`` (Mamba-2, H = ``mamba_num_heads`` heads of P = ``mamba_head_dim``,
  d_inner = H P, G = ``n_groups`` groups of N = ``ssm_state_size``, K =
  ``conv_kernel``), h the normed input:
  [z | xBC | dt] = h W_in  (d_inner, d_inner + 2 G N, H);
  xBC_t <- SiLU(sum_k w_k xBC_{t-K+1+k} + b), inputs before 0 being 0;
  [x | B | C] = xBC (x: H heads of P; B, C: G groups of N, head h reading
  group h / (H / G));  dt <- softplus(dt + dt_bias);  A = -exp(A_log);
  S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T (S_{-1} = 0, [P, N] a
  head);  y_t = S_t C_t + D_h x_t;  y <- y * SiLU(z), then an RMSNorm over
  each of G groups of d_inner / G values, times the norm's weight;  out =
  y W_out.
``*`` (attention, no position encoding, no bias): q = h W_q (heads of
  ``head_dim``), k = h W_k, v = h W_v (``num_key_value_heads`` heads, each
  shared by its group of query heads); causal softmax at head_dim^-0.5;
  o = concat(heads) W_o.
``E`` (experts), E = the router's width: s = sigmoid(h W_g); the
  ``num_experts_per_tok`` largest of s + e_score_correction_bias are
  chosen (no group limit); weights s[chosen] / sum(s[chosen]) x
  ``routed_scaling_factor``;  y = sum over the chosen e that are HELD of
  w_e relu(h U_e)² D_e + relu(h U_sh)² D_sh. No token is dropped.
``-`` (a dense MLP): relu(h U)² D.

Departures from the published description:

* **The share.** The model holds the experts ``[expert_first,
  expert_first + num_experts_held)`` of the E routed ones (the stacks have
  that many entries; E is the router weight's width). The choice and the
  weights are over all E; the chosen experts that are absent add nothing
  here (on the deployment their terms come from the other chips), and
  that partial result goes on to the next block. With every expert held
  this is the published layer.
* What ``config.json`` leaves open (no position encoding in attention,
  d_inner = heads x head size, no dt clamp, the gated norm's order and
  groups) is listed in the configuration file's ``assumed``.

To fit beside a serving engine's arrays on one chip, each expert's
weights are cast alone and run on the tokens routed to it (their indices
found on the host, padded to one bucket size so that one program serves
all experts), attention goes a block of queries at a time, and the head a
block of rows at a time, straight into host memory.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 128          # queries scored at a time
ROW_BLOCK = 1024       # rows of the head at a time


def _highest(fn):
    @functools.wraps(fn)
    def run(*a, **k):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **k)
    return run


def _rms(x, w, eps):
    y = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)
    return y if w is None else y * w.astype(F32)


@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "groups",
                                             "state", "eps"))
@_highest
def _mamba(h, p, heads, head_dim, groups, state, eps):
    """The Mamba-2 mixer of one sequence, h [s, C] its normed input."""
    s = h.shape[0]
    di = heads * head_dim
    zxd = h @ p["mixer.in_proj.weight"].astype(F32)
    conv_dim = di + 2 * groups * state
    z, xbc, dt = zxd[:, :di], zxd[:, di:di + conv_dim], \
        zxd[:, di + conv_dim:]
    w = p["mixer.conv_weight"].astype(F32)                  # [K, conv_dim]
    K = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((K - 1, conv_dim), F32), xbc], 0)
    xbc = jax.nn.silu(sum(xp[k:k + s] * w[k] for k in range(K))
                      + p["mixer.conv_bias"].astype(F32))
    x = xbc[:, :di].reshape(s, heads, head_dim)
    B = xbc[:, di:di + groups * state].reshape(s, groups, state)
    C = xbc[:, di + groups * state:].reshape(s, groups, state)
    dt = jax.nn.softplus(dt + p["mixer.dt_bias"].astype(F32))
    A = -jnp.exp(p["mixer.A_log"].astype(F32))
    D = p["mixer.D"].astype(F32)
    group_of = jnp.arange(heads) // (heads // groups)

    def token(S, inp):
        x_t, dt_t, B_t, C_t = inp
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[group_of][:, None, :]
        return S, (S * C_t[group_of][:, None, :]).sum(-1) + D[:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((heads, head_dim, state), F32),
                        (x, dt, B, C))
    y = y.reshape(s, di) * jax.nn.silu(z)
    y = _rms(y.reshape(s, groups, di // groups), None, eps).reshape(s, di) \
        * p["mixer.norm.weight"].astype(F32)
    return y @ p["mixer.out_proj.weight"].astype(F32)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads",
                                             "head_dim"))
@_highest
def _attention(h, p, heads, kv_heads, head_dim):
    """Causal grouped-query attention of one sequence, no position
    encoding, h [s, C] its normed input."""
    s = h.shape[0]
    q = (h @ p["mixer.q_proj.weight"].astype(F32)).reshape(s, heads,
                                                           head_dim)
    rep = heads // kv_heads
    k = jnp.repeat((h @ p["mixer.k_proj.weight"].astype(F32))
                   .reshape(s, kv_heads, head_dim), rep, axis=1)
    v = jnp.repeat((h @ p["mixer.v_proj.weight"].astype(F32))
                   .reshape(s, kv_heads, head_dim), rep, axis=1)
    pad = -s % Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))) \
        .reshape(-1, Q_BLOCK, heads, head_dim)

    def block(args):
        qs, t0 = args
        sc = jnp.einsum("qhd,khd->hqk", qs, k) * head_dim ** -0.5
        seen = jnp.arange(s)[None, :] <= (t0 + jnp.arange(Q_BLOCK))[:, None]
        sc = jnp.where(seen[None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    o = jax.lax.map(block, (qb, jnp.arange(qb.shape[0]) * Q_BLOCK))
    return o.reshape(-1, heads * head_dim)[:s] \
        @ p["mixer.o_proj.weight"].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
@_highest
def _norm(z, w, eps):
    return _rms(z, w, eps)


@jax.jit
@_highest
def _relu2(h, up, down):
    return jnp.square(jax.nn.relu(h @ up.astype(F32))) @ down.astype(F32)


@jax.jit
@_highest
def _scores(h, gate_w):
    return jax.nn.sigmoid(h @ gate_w.astype(F32))


@jax.jit
@_highest
def _expert_on(h, y, idx, wt, up, down):
    """y += wt x relu(h[idx] U_e)² D_e at rows idx (weight 0: padding);
    ``up`` is U_e stored out x in, [I, C]."""
    out = jnp.square(jax.nn.relu(h[idx] @ up.astype(F32).T)) \
        @ down.astype(F32)
    return y.at[idx].add(out * wt[:, None])


def route(h, p, config):
    """-> (chosen experts [s, k] of the router's E, their weights [s, k])
    on the host."""
    k = int(config["num_experts_per_tok"])
    s = np.asarray(_scores(h, p["mixer.gate_weight"]), np.float64)
    sel = s + np.asarray(p["mixer.e_score_correction_bias"], np.float64)
    top = np.argsort(-sel, axis=1, kind="stable")[:, :k]
    wt = np.take_along_axis(s, top, axis=1)
    if config.get("norm_topk_prob", True):
        wt = wt / wt.sum(1, keepdims=True)
    return top, wt * float(config["routed_scaling_factor"])


def held_range(p, config):
    """-> (first, held): the routed experts this model holds."""
    held = p["mixer.experts_up"].shape[0]
    first = int(config.get("expert_first", 0))
    assert held == int(config.get("num_experts_held", held))
    assert first + held <= p["mixer.gate_weight"].shape[1]
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
                       p["mixer.experts_up"][e], p["mixer.experts_down"][e])
    return y


def experts(h, p, config):
    """An expert layer on its normed input: the held experts' part and
    the shared expert."""
    return routed(h, p, config) + _relu2(
        h, p["mixer.shared_experts.up_proj.weight"],
        p["mixer.shared_experts.down_proj.weight"])


@jax.jit
def _add(x, y):
    return x + y


def hidden(params, ids, config):
    """The residual stream of one sequence ``ids`` [s] after the last
    block, before the final norm: float32 [s, C]."""
    eps = float(config["layer_norm_epsilon"])
    mamba = dict(heads=int(config["mamba_num_heads"]),
                 head_dim=int(config["mamba_head_dim"]),
                 groups=int(config["n_groups"]),
                 state=int(config["ssm_state_size"]), eps=eps)
    attn = dict(heads=int(config["num_attention_heads"]),
                kv_heads=int(config["num_key_value_heads"]),
                head_dim=int(config["head_dim"]))
    x = params["model.embed_tokens.weight"][jnp.asarray(ids, jnp.int32)] \
        .astype(F32)
    pattern = config["hybrid_override_pattern"]
    assert len(pattern) == int(config["num_hidden_layers"])
    for i, kind in enumerate(pattern):
        pre = "model.layers.%d." % i
        p = {k[len(pre):]: v for k, v in params.items()
             if k.startswith(pre)}
        h = _norm(x, p["norm.weight"], eps=eps)
        if kind == "M":
            y = _mamba(h, p, **mamba)
        elif kind == "*":
            y = _attention(h, p, **attn)
        elif kind == "E":
            y = experts(h, p, config)
        else:
            y = _relu2(h, p["mixer.up_proj.weight"],
                       p["mixer.down_proj.weight"])
        x = _add(x, y)
    return x


@functools.partial(jax.jit, static_argnames=("eps",))
@_highest
def _head(x, norm_w, w, eps):
    return _rms(x, norm_w, eps) @ w.astype(F32)


def logits(params, ids, config):
    """float32 logits [b, s, vocab] of a full forward pass, a sequence
    and a block of rows at a time, gathered in host memory."""
    ids = np.asarray(ids)
    w = params["lm_head.weight"] if "lm_head.weight" in params \
        else params["model.embed_tokens.weight"].T
    out = np.empty(ids.shape + (w.shape[1],), np.float32)
    for b in range(ids.shape[0]):
        x = hidden(params, ids[b], config)
        for r0 in range(0, x.shape[0], ROW_BLOCK):
            out[b, r0:r0 + ROW_BLOCK] = np.asarray(_head(
                x[r0:r0 + ROW_BLOCK], params["model.norm.weight"], w,
                eps=float(config["layer_norm_epsilon"])))
    return out
