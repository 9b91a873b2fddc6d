"""Plain reference for the sarvam-105b configurations (sarvamai
sarvam-105b, ``model_type`` ``sarvam_mla``): latent attention (MLA) with
a full-rank, per-head normed query in its EXPANDED form, and
sigmoid-routed experts beside a shared one, of which the model may hold
a SHARE (one chip of an expert-parallel deployment). In straightforward
float32 ``jax.numpy`` at the highest matmul precision: no kernel, no
cache, no absorbed projections, no grouped matmul, and nothing imported
from the program: it reads the run's own parameters by the names
``named_parameters()`` gives them and casts each to float32 as it goes.

``config`` is the configuration file's object, read by the published
``config.json``'s keys. The equations, per token, ``C`` = hidden size,
one residual stream x (pre-norm):

  x <- x + MLA(RMSNorm(x));  x <- x + FFN(RMSNorm(x));  after the last
  layer the final RMSNorm and the untied head.

MLA, h the normed input: q_i = [q_nope_i | q_rope_i] = RMSNorm_q(h W_q)_i,
  the norm with one gain of ``q_head_dim`` values over EACH head's
  ``q_head_dim`` values (``use_qk_norm``);  [c_kv | k_rope] = h W_kva,
  c_kv <- RMSNorm(c_kv);  rotate-half RoPE with YaRN's frequencies on
  q_rope_i and the one shared k_rope;  [k_nope_i | v_i] = c_kv W_kvb;
  score_i(t, s) = (q_nope_i(t) . k_nope_i(s) + q_rope_i(t) . k_rope(s))
  q_head_dim^-0.5 m^2, m = 0.1 mscale_all_dim ln(factor) + 1;  causal
  softmax;  o = concat_i(sum_s p_i v_i(s)) W_o.

Expert layer, E = the router's width: s = sigmoid(h W_g); the
  ``num_experts_per_tok`` largest of s + e_score_correction_bias are
  chosen (no group limit); weights s[chosen] / sum(s[chosen]) x
  routed_scaling_factor;  y = sum over the chosen e that are HELD of w_e
  SwiGLU_e(h) + SwiGLU_shared(h). No token is dropped. A leading layer
  (index < first_k_dense_replace) has one dense SwiGLU.

Departures from the published description:

* **The share.** The model holds the experts ``[expert_first,
  expert_first + num_experts_held)`` of the E routed ones (the expert
  stacks have ``num_experts_held`` entries; E is the router weight's
  width). The choice and the weights are over all E; the chosen experts
  that are absent add nothing here (on the deployment their terms come
  from the other chips), and that partial result is what goes on to the
  next layer. With every expert held this is the published layer.
* The vocabulary may be a slice: the embedding and the head have the
  rows they have.
* What ``config.json`` does not fix (the score function, the query and
  key norms' places, RoPE's layout) is listed in the configuration
  file's ``assumed``.

To fit beside a serving engine's arrays on one chip, each expert's
weights are cast alone and run on the tokens routed to it (their indices
found on the host, padded to one bucket size so that one program serves
all experts), the three matrices of the dense SwiGLU are cast one at a
time, attention goes a block of queries at a time, and the head a block
of rows at a time, straight into host memory.
"""
from __future__ import annotations

import functools
import math

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


def yarn_inv_freq(config):
    """YaRN's inverse frequencies over the rope dims (DeepSeek-V3's
    rotary embedding), float32 [qk_rope_head_dim / 2]."""
    sc = config["rope_scaling"]
    d, base = int(config["qk_rope_head_dim"]), float(config["rope_theta"])
    orig = float(sc["original_max_position_embeddings"])

    def correction_dim(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(sc["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(sc["beta_slow"]))), d - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    inter = extra / float(sc["factor"])
    keep = 1.0 - np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    return jnp.asarray(inter * (1 - keep) + extra * keep, F32)


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rope(x, inv_freq, cos_scale):
    """Rotate-half RoPE of ``x`` [s, heads, d] at positions 0..s-1."""
    s, _, d = x.shape
    ang = jnp.arange(s, dtype=F32)[:, None] * inv_freq[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None] * cos_scale
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None] * cos_scale
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("heads", "dn", "dr", "dv",
                                             "eps", "scale", "cos_scale"))
@_highest
def _attention(h, p, inv_freq, heads, dn, dr, dv, eps, scale, cos_scale):
    """Expanded latent attention of one sequence, h [s, C] its normed
    input."""
    s = h.shape[0]
    q = (h @ p["self_attn.q_proj.weight"].astype(F32)) \
        .reshape(s, heads, dn + dr)
    if "self_attn.q_norm.weight" in p:
        q = _rms(q, p["self_attn.q_norm.weight"], eps)
    kv = h @ p["self_attn.kv_a_proj_with_mqa.weight"].astype(F32)
    rank = kv.shape[-1] - dr
    ckv = _rms(kv[:, :rank], p["self_attn.kv_a_layernorm.weight"], eps)
    k_rope = _rope(kv[:, None, rank:], inv_freq, cos_scale)     # [s, 1, dr]
    q_rope = _rope(q[..., dn:], inv_freq, cos_scale)
    kvb = (ckv @ p["self_attn.kv_b_proj.weight"].astype(F32)) \
        .reshape(s, heads, dn + dv)
    k = jnp.concatenate([kvb[..., :dn],
                         jnp.broadcast_to(k_rope, (s, heads, dr))], -1)
    v = kvb[..., dn:]
    q = jnp.concatenate([q[..., :dn], q_rope], -1)
    pad = -s % Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))) \
        .reshape(-1, Q_BLOCK, heads, dn + dr)

    def block(args):
        qs, t0 = args
        sc = jnp.einsum("qhd,khd->hqk", qs, k) * scale
        seen = jnp.arange(s)[None, :] <= (t0 + jnp.arange(Q_BLOCK))[:, None]
        sc = jnp.where(seen[None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    o = jax.lax.map(block, (qb, jnp.arange(qb.shape[0]) * Q_BLOCK))
    o = o.reshape(-1, heads * dv)[:s]
    return o @ p["self_attn.o_proj.weight"].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
@_highest
def _norm(z, w, eps):
    return _rms(z, w, eps)


@jax.jit
@_highest
def _gated(h, gate, up):
    return jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))


@jax.jit
@_highest
def _matmul(a, w):
    return a @ w.astype(F32)


def _swiglu(h, p, name):
    return _matmul(_gated(h, p[name + "gate_proj.weight"],
                          p[name + "up_proj.weight"]),
                   p[name + "down_proj.weight"])


@jax.jit
@_highest
def _scores(h, gate_w):
    return jax.nn.sigmoid(h @ gate_w.astype(F32))


@jax.jit
@_highest
def _expert_on(h, y, idx, wt, gate_up, down):
    """y += wt x SwiGLU_e(h[idx]) at rows idx (weight 0: padding)."""
    gu = h[idx] @ gate_up.astype(F32)
    g, u = jnp.split(gu, 2, axis=-1)
    out = (jax.nn.silu(g) * u) @ down.astype(F32)
    return y.at[idx].add(out * wt[:, None])


def route(h, p, config):
    """-> (chosen experts [s, k] of the router's E, their weights [s, k])
    on the host."""
    k = int(config["num_experts_per_tok"])
    s = np.asarray(_scores(h, p["mlp.gate_weight"]), np.float64)
    sel = s + np.asarray(p["mlp.e_score_correction_bias"], np.float64)
    top = np.argsort(-sel, axis=1, kind="stable")[:, :k]
    wt = np.take_along_axis(s, top, axis=1)
    if config.get("norm_topk_prob", True):
        wt = wt / wt.sum(1, keepdims=True)
    return top, wt * float(config["routed_scaling_factor"])


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


def experts(h, p, config):
    """An expert layer on its normed input: the held experts' part and
    the shared expert."""
    return routed(h, p, config) + _swiglu(h, p, "mlp.shared_experts.")


@jax.jit
def _add(x, y):
    return x + y


def hidden(params, ids, config):
    """The residual stream of one sequence ``ids`` [s] after the last
    layer, before the final norm: float32 [s, C]."""
    eps = float(config["rms_norm_eps"])
    sc = config["rope_scaling"]
    m_all = _mscale(float(sc["factor"]), float(sc["mscale_all_dim"]))
    dn, dr = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    attn = dict(heads=int(config["num_attention_heads"]), dn=dn, dr=dr,
                dv=int(config["v_head_dim"]), eps=eps,
                scale=(dn + dr) ** -0.5 * m_all * m_all,
                cos_scale=_mscale(float(sc["factor"]), float(sc["mscale"]))
                / m_all)
    inv_freq = yarn_inv_freq(config)
    x = params["model.embed_tokens.weight"][jnp.asarray(ids, jnp.int32)] \
        .astype(F32)
    for i in range(int(config["num_hidden_layers"])):
        pre = "model.layers.%d." % i
        p = {k[len(pre):]: v for k, v in params.items()
             if k.startswith(pre)}
        h = _norm(x, p["input_layernorm.weight"], eps=eps)
        x = _add(x, _attention(h, p, inv_freq, **attn))
        h = _norm(x, p["post_attention_layernorm.weight"], eps=eps)
        if i < int(config["first_k_dense_replace"]):
            x = _add(x, _swiglu(h, p, "mlp."))
        else:
            x = _add(x, experts(h, p, config))
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
                eps=float(config["rms_norm_eps"])))
    return out
