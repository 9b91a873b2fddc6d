"""Xing4.0 decoder (XingChen-AGI Xing4.0-29B-A4B, ``config.json``'s
``model_type`` ``xing4_0``): a DeepSeek-V3-style stack whose residual path
is FOUR streams a token, served through the model-generic decode engine.

What a layer is (the plain float32 reference,
``benchmark/references/xing4.py``, has the equations once more, and the
configuration file's ``assumed`` every choice ``config.json`` leaves open):

* **latent attention (MLA)**: queries through a low-rank bottleneck
  (``q_lora_rank``), keys and values through ONE shared latent a token,
  ``kv_lora_rank`` normed values plus ``qk_rope_head_dim`` rope-rotated
  ones: that vector is all a token leaves in the cache
  (``Xing4DecodeAdapter.latent_dim`` values a layer, 576 of them where
  per-head keys and values would be 10,240). The decode engine attends in
  the absorbed form: the per-head key expansion is folded into the query,
  the value expansion applied to the attended latent.
* **experts**: ``first_k_dense_replace`` leading layers have a dense
  SwiGLU, the rest ``n_routed_experts`` SwiGLU experts of
  ``moe_intermediate_size``, of which a token takes the
  ``num_experts_per_tok`` with the largest ``sigmoid`` score plus a
  selection bias, weighted by their normalised scores times
  ``routed_scaling_factor``, beside ``n_shared_experts`` shared one. No
  token is dropped and no capacity is set: the pairs are sorted by expert
  and run as grouped matmuls (``incubate/nn/pallas/moe_dispatch.py``).
* **mHC, the changed residual path**: ``hc_mult`` streams a token. Around
  each sublayer three maps are made from the streams themselves: one that
  mixes them into the sublayer's input, one that spreads its output over
  them, and one, made doubly stochastic by ``hc_sinkhorn_iters`` Sinkhorn
  iterations, that mixes the streams among themselves.

Serving only: ``forward`` is the decode engine's prefill over a whole
sequence (no autograd; the grouped matmul has no backward pass), and
there is no multi-token-prediction module (``num_nextn_predict_layers``
is a training objective and a draft head the main model runs without).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..core import random as _rng
from ..core.tensor import Tensor

__all__ = ["Xing4Config", "Xing4Model", "Xing4ForCausalLM", "xing4_tiny",
           "xing4_29B_A4B", "yarn_inv_freq", "yarn_mscale", "LatentCausalLM"]


@dataclass
class Xing4Config:
    vocab_size: int = 131072
    hidden_size: int = 3584
    num_layers: int = 40
    num_heads: int = 32
    intermediate_size: int = 9216          # the leading dense layers' SwiGLU
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    # latent attention
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rope_factor: float = 64.0              # YaRN, as rope_scaling has them
    rope_original_max_position_embeddings: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    # experts
    first_k_dense_replace: int = 2
    moe_intermediate_size: int = 1024
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 2.0
    norm_topk_prob: bool = True
    # the residual path
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    rms_norm_eps: float = 1e-6
    # how the weights start, for a model that is served without trained
    # weights (benchmark/configs/xing4-29b-a4b-l6.json says why each)
    initializer_range: float = 0.02
    # the share of a routed expert's starting weights that is its own:
    # W_e = sqrt(1 - s^2) W_common + s W_e'. A top-k router is
    # discontinuous: rounding changes some token's last-ranked expert in a
    # few of a hundred (token, layer) pairs whatever the precision, and
    # between independent random experts (s = 1) one such change moves a
    # logit by more than any useful margin. With s under 1 the change is
    # s times as large, while the routed experts keep their full share of
    # the stream, so that a pair dropped, weighted or gathered wrongly
    # still shows in full
    expert_init_spread: float = 1.0
    # q_b_proj starts at this multiple of initializer_range: at 1 a random
    # model's attention scores have a spread under 1, every query averages
    # thousands of keys and the attention sublayer is a fiftieth of the
    # stream at 8k tokens of context: nothing downstream could see what
    # the cache holds. A larger start gives the peaked attention of a
    # trained model
    query_init_scale: float = 1.0
    # None, or a float8 dtype's name: every projection's, expert's and the
    # head's weights are rounded to it as the decode engine reads them
    # (weight-only fp8, the next precision below bfloat16). The
    # lower-precision control that a serving cell's margin is set
    # against; no cell runs with it
    control_operand_dtype: Optional[str] = None

    def __post_init__(self):
        if not 0 <= self.first_k_dense_replace <= self.num_layers:
            raise ValueError("first_k_dense_replace must lie in "
                             "[0, num_layers]")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("num_experts_per_tok exceeds n_routed_experts")
        if not 0.0 < self.expert_init_spread <= 1.0:
            raise ValueError("expert_init_spread must lie in (0, 1]")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Values a token leaves in a layer's cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def published(self) -> dict:
        """These sizes under the keys of the published ``config.json``,
        which the plain reference (``benchmark/references/xing4.py``)
        reads."""
        out = {k: getattr(self, k) for k in (
            "hidden_size", "vocab_size", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "q_lora_rank",
            "rope_theta", "first_k_dense_replace", "n_routed_experts",
            "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
            "hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
            "mhc_h_res_clamp_max", "rms_norm_eps")}
        out.update(
            num_hidden_layers=self.num_layers,
            num_attention_heads=self.num_heads,
            rope_scaling={
                "type": "yarn", "factor": self.rope_factor,
                "original_max_position_embeddings":
                    self.rope_original_max_position_embeddings,
                "beta_fast": self.rope_beta_fast,
                "beta_slow": self.rope_beta_slow,
                "mscale": self.rope_mscale,
                "mscale_all_dim": self.rope_mscale_all_dim})
        return out


def xing4_tiny(**kw) -> Xing4Config:
    kw = dict(dict(vocab_size=512, hidden_size=64, num_layers=3,
                   num_heads=4, intermediate_size=128,
                   max_position_embeddings=512, q_lora_rank=24,
                   kv_lora_rank=32, qk_nope_head_dim=16,
                   qk_rope_head_dim=8, v_head_dim=16,
                   rope_original_max_position_embeddings=64,
                   first_k_dense_replace=1, moe_intermediate_size=32,
                   n_routed_experts=8, num_experts_per_tok=2,
                   hc_sinkhorn_iters=4), **kw)
    return Xing4Config(**kw)


def xing4_29B_A4B(**kw) -> Xing4Config:
    return Xing4Config(**kw)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg):
    """YaRN's inverse frequencies over the rope dims, as the DeepSeek-V3
    family's rotary embedding makes them: high frequencies kept, low ones
    divided by ``factor``, a linear ramp between the dims that turn
    ``beta_fast`` and ``beta_slow`` times over the original window.
    -> float32 [qk_rope_head_dim / 2]."""
    d, base = cfg.qk_rope_head_dim, cfg.rope_theta
    orig = cfg.rope_original_max_position_embeddings

    def correction_dim(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), d - 1)
    if low == high:
        high += 0.001
    extra = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / cfg.rope_factor * ramp + extra * (1.0 - ramp)


class Xing4HyperConnection(nn.Layer):
    """One sublayer's mHC parameters: ``phi`` [n C, n + n + n n] reads the
    normed streams into the three maps' pre-activations, ``bias`` shifts
    them, ``alpha`` (pre, post, res) scales them."""

    def __init__(self, config: Xing4Config):
        super().__init__()
        n, c = config.hc_mult, config.hidden_size
        self.phi = self.create_parameter(
            [n * c, 2 * n + n * n],
            default_initializer=nn.initializer.Normal(
                0.0, (n * c) ** -0.5))
        # B_res = 2 I: the stream-mixing map leans to the identity
        bias = jnp.concatenate([
            jnp.zeros((2 * n,), jnp.float32),
            2.0 * jnp.eye(n, dtype=jnp.float32).reshape(-1)])
        self.bias = self.create_parameter(
            [2 * n + n * n],
            default_initializer=nn.initializer.Assign(bias))
        self.alpha = self.create_parameter(
            [3], default_initializer=nn.initializer.Constant(1.0))


class Xing4Attention(nn.Layer):
    def __init__(self, config: Xing4Config):
        super().__init__()
        c, nh = config.hidden_size, config.num_heads
        init = nn.initializer.Normal(0.0, config.initializer_range)

        def lin(i, o):
            return nn.Linear(i, o, weight_attr=init, bias_attr=False)

        self.q_a_proj = lin(c, config.q_lora_rank)
        self.q_a_layernorm = nn.RMSNorm(config.q_lora_rank,
                                        config.rms_norm_eps)
        self.q_b_proj = nn.Linear(
            config.q_lora_rank, nh * config.qk_head_dim, bias_attr=False,
            weight_attr=nn.initializer.Normal(
                0.0, config.initializer_range * config.query_init_scale))
        self.kv_a_proj_with_mqa = lin(c, config.latent_dim)
        self.kv_a_layernorm = nn.RMSNorm(config.kv_lora_rank,
                                         config.rms_norm_eps)
        self.kv_b_proj = lin(
            config.kv_lora_rank,
            nh * (config.qk_nope_head_dim + config.v_head_dim))
        self.o_proj = lin(nh * config.v_head_dim, c)


class _ExpertStack(nn.initializer.Initializer):
    """[E, ...] expert weights: ``spread`` of each expert's is a draw of
    its own, the rest one draw common to all (``Xing4Config.
    expert_init_spread`` says why). Drawn and mixed in one program, so
    that no float32 copy of the stack is ever held."""

    def __init__(self, std, spread):
        self.std, self.spread = std, spread

    def __call__(self, shape, dtype=jnp.float32):
        shape = tuple(shape)

        def draw(k_own, k_common):
            own = jax.random.normal(k_own, shape) * self.spread
            if self.spread < 1.0:
                own = own + jax.random.normal(k_common, shape[1:]) \
                    * (1.0 - self.spread ** 2) ** 0.5
            return (own * self.std).astype(dtype)

        return jax.jit(draw)(_rng.next_key(), _rng.next_key())


class Xing4MLP(nn.Layer):
    """SwiGLU of one width: a leading layer's dense FFN, or the shared
    expert."""

    def __init__(self, config: Xing4Config, width: int):
        super().__init__()
        init = nn.initializer.Normal(0.0, config.initializer_range)
        c = config.hidden_size
        self.gate_proj = nn.Linear(c, width, weight_attr=init,
                                   bias_attr=False)
        self.up_proj = nn.Linear(c, width, weight_attr=init,
                                 bias_attr=False)
        self.down_proj = nn.Linear(width, c, weight_attr=init,
                                   bias_attr=False)


class Xing4MoE(nn.Layer):
    """The routed experts, stacked for the grouped matmuls (gate and up
    side by side: ``gate_up`` [E, C, 2 I], ``down`` [E, I, C]), their
    router, and the shared expert."""

    def __init__(self, config: Xing4Config):
        super().__init__()
        c, e = config.hidden_size, config.n_routed_experts
        i = config.moe_intermediate_size
        init = nn.initializer.Normal(0.0, config.initializer_range)
        # h is unit-RMS over C dims: columns of std C^-0.5 give a token's
        # router logits unit variance, so the sigmoid scores spread (at
        # initializer_range all read 0.5); the selection bias small
        self.gate_weight = self.create_parameter(
            [c, e], default_initializer=nn.initializer.Normal(
                0.0, c ** -0.5))
        self.e_score_correction_bias = self.create_parameter(
            [e], default_initializer=nn.initializer.Normal(0.0, 0.01))
        stack = _ExpertStack(config.initializer_range,
                             config.expert_init_spread)
        self.experts_gate_up = self.create_parameter(
            [e, c, 2 * i], default_initializer=stack)
        self.experts_down = self.create_parameter(
            [e, i, c], default_initializer=stack)
        self.shared_experts = Xing4MLP(config, i * config.n_shared_experts)


class Xing4Block(nn.Layer):
    def __init__(self, config: Xing4Config, dense: bool):
        super().__init__()
        c, eps = config.hidden_size, config.rms_norm_eps
        self.hc_attn = Xing4HyperConnection(config)
        self.input_layernorm = nn.RMSNorm(c, eps)
        self.self_attn = Xing4Attention(config)
        self.hc_mlp = Xing4HyperConnection(config)
        self.post_attention_layernorm = nn.RMSNorm(c, eps)
        self.mlp = Xing4MLP(config, config.intermediate_size) if dense \
            else Xing4MoE(config)


class Xing4Model(nn.Layer):
    def __init__(self, config: Xing4Config):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=nn.initializer.Normal(0.0, config.initializer_range))
        self.layers = nn.LayerList([
            Xing4Block(config, dense=i < config.first_k_dense_replace)
            for i in range(config.num_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)


class LatentCausalLM(nn.Layer):
    """A served decoder with a latent cache: the body ``model``
    (``embed_tokens``, ``layers``, ``norm``), the head, ``forward`` and
    ``generate`` through the model-generic decode engine. A family gives
    its body and its ``decode_adapter()``."""

    def __init__(self, config, body):
        super().__init__()
        self.config = config
        self.model = body
        self._forward = None
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(
                config.hidden_size, config.vocab_size, bias_attr=False,
                weight_attr=nn.initializer.Normal(
                    0.0, config.initializer_range))

    def forward(self, input_ids):
        """Logits [b, s, vocab] of whole sequences: the decode engine's
        prefill (serving arithmetic, no autograd)."""
        from .generation import _as_ids

        ad = self.decode_adapter()
        w, ad.weights = ad.weights, None
        if self._forward is None:      # one jit a model: a trace a shape
            self._forward = jax.jit(lambda w, ids: ad.logits(
                w, ad.prefill(w, ids, ids.shape[1])[0]))
        return Tensor(self._forward(w, _as_ids(input_ids)))

    def generate(self, input_ids, max_new_tokens=32, temperature=0.0,
                 top_p=None, eos_token_id=None):
        """Fully-compiled autoregressive decoding via the model-generic
        fused decode engine (models/generation.py)."""
        from .generation import generate as _gen

        return _gen(self, input_ids, max_new_tokens=max_new_tokens,
                    temperature=temperature, top_p=top_p,
                    eos_token_id=eos_token_id)


class Xing4ForCausalLM(LatentCausalLM):
    def __init__(self, config: Xing4Config):
        super().__init__(config, Xing4Model(config))

    def decode_adapter(self):
        """Weight-extraction protocol for the model-generic fused decode
        engine (models/generation.py)."""
        from .generation import Xing4DecodeAdapter

        return Xing4DecodeAdapter(self)
