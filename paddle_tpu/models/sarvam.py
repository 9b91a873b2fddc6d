"""sarvam-105b decoder (sarvamai/sarvam-105b, ``config.json``'s
``model_type`` ``sarvam_mla``): a DeepSeek-V3-style stack with ONE
residual stream, served through the model-generic decode engine, whose
expert layers can be told which of the routed experts they hold.

What a layer is (the plain float32 reference,
``benchmark/references/sarvam.py``, has the equations once more, and the
configuration file's ``assumed`` every choice ``config.json`` leaves open):

* **latent attention (MLA) with a full-rank query**: the config has no
  ``q_lora_rank``, so queries come straight from the hidden state, each
  head's ``qk_nope_head_dim + qk_rope_head_dim`` values RMS-normed with a
  gain (``use_qk_norm``) before RoPE; keys and values through ONE shared
  latent a token, ``kv_lora_rank`` normed values plus ``qk_rope_head_dim``
  rope-rotated ones, which is all a token leaves in the cache. The decode
  engine attends in the absorbed form; the sublayer's body is
  ``LatentDecodeAdapter.latent_attention``, the Xing4.0 adapter's too.
* **experts, and a chip's share of them**: ``first_k_dense_replace``
  leading layers have a dense SwiGLU, the rest ``num_experts`` routed
  SwiGLU experts, of which a token takes the ``num_experts_per_tok`` with
  the largest ``sigmoid`` score plus a selection bias
  (``moe_router_enable_expert_bias``), weighted by their scores normalised
  over the chosen and times ``routed_scaling_factor``, beside
  ``num_shared_experts`` shared one. ``num_experts_held`` and
  ``expert_first`` say which contiguous range of the routed experts THIS
  model holds (one chip of an expert-parallel deployment): the router
  keeps all ``num_experts`` outputs and the choice and the weights are
  over all of them, the expert stacks hold ``num_experts_held``, and the
  layer's result is the held experts' part of the sum plus the shared
  expert; what the absent experts would add is another chip's. All of
  them held (the default) is the whole layer: the share is data, not a
  mode.

Serving only, as ``xing4.py``: ``forward`` is the decode engine's
prefill (the grouped matmul has no backward pass).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from .. import nn
from .xing4 import LatentCausalLM, Xing4MLP, _ExpertStack

__all__ = ["SarvamMLAConfig", "SarvamModel", "SarvamForCausalLM",
           "sarvam_tiny"]


@dataclass
class SarvamMLAConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 64
    intermediate_size: int = 16384         # the leading dense layer's SwiGLU
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = False
    # latent attention
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    use_qk_norm: bool = True
    rope_theta: float = 10000.0
    rope_factor: float = 40.0              # deepseek_yarn, as rope_scaling
    rope_original_max_position_embeddings: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    # experts
    first_k_dense_replace: int = 1
    moe_intermediate_size: int = 2048
    num_experts: int = 128                 # the router's width
    num_experts_held: Optional[int] = None     # None: all of them
    expert_first: int = 0                  # held: [first, first + held)
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    moe_router_enable_expert_bias: bool = True
    rms_norm_eps: float = 1e-6
    # how the weights start, for a model that is served without trained
    # weights (benchmark/configs/sarvam-105b-l6-ep4.json says why each;
    # the first two as Xing4Config's)
    initializer_range: float = 0.02
    expert_init_spread: float = 1.0
    # the per-head query norm's gain starts at this: the norm fixes a
    # query's size whatever q_proj holds, so the gain alone sets how
    # peaked a random model's attention is
    query_init_scale: float = 1.0
    # the routed experts' down projection starts at this multiple of
    # initializer_range (the shared expert and every other weight
    # untouched). With a share of the experts held, a rounding-made change
    # of a token's last-ranked expert is, 3 times in 8, between a held
    # and an absent one, and then the program and a reference differ by
    # one WHOLE pair, common part and all, which expert_init_spread cannot
    # make small; this scales what one pair adds
    routed_init_scale: float = 1.0
    # None, or a float8 dtype's name: Xing4Config's lower-precision
    # control; no cell runs with it
    control_operand_dtype: Optional[str] = None

    def __post_init__(self):
        if self.num_experts_held is None:
            self.num_experts_held = self.num_experts
        if not 0 <= self.first_k_dense_replace <= self.num_layers:
            raise ValueError("first_k_dense_replace must lie in "
                             "[0, num_layers]")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("num_experts_per_tok exceeds num_experts")
        if self.num_experts_held < 1 or self.expert_first < 0 or \
                self.expert_first + self.num_experts_held > self.num_experts:
            raise ValueError("the held experts [expert_first, expert_first "
                             "+ num_experts_held) must be a range of the "
                             "num_experts routed ones, and not empty")
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
        and the share under the configuration file's, which the plain
        reference (``benchmark/references/sarvam.py``) reads."""
        out = {k: getattr(self, k) for k in (
            "hidden_size", "vocab_size", "intermediate_size",
            "moe_intermediate_size", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "rope_theta",
            "first_k_dense_replace", "num_experts", "num_experts_held",
            "expert_first", "num_shared_experts", "num_experts_per_tok",
            "routed_scaling_factor", "norm_topk_prob", "use_qk_norm",
            "moe_router_enable_expert_bias", "rms_norm_eps",
            "max_position_embeddings", "tie_word_embeddings")}
        out.update(
            num_hidden_layers=self.num_layers,
            num_attention_heads=self.num_heads,
            q_head_dim=self.qk_head_dim, head_dim=self.latent_dim,
            rope_scaling={
                "type": "deepseek_yarn", "factor": self.rope_factor,
                "original_max_position_embeddings":
                    self.rope_original_max_position_embeddings,
                "beta_fast": self.rope_beta_fast,
                "beta_slow": self.rope_beta_slow,
                "mscale": self.rope_mscale,
                "mscale_all_dim": self.rope_mscale_all_dim})
        return out


def sarvam_tiny(**kw) -> SarvamMLAConfig:
    kw = dict(dict(vocab_size=512, hidden_size=64, num_layers=3,
                   num_heads=4, intermediate_size=128,
                   max_position_embeddings=512, kv_lora_rank=32,
                   qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                   rope_original_max_position_embeddings=64,
                   first_k_dense_replace=1, moe_intermediate_size=32,
                   num_experts=8, num_experts_per_tok=3), **kw)
    return SarvamMLAConfig(**kw)


class SarvamAttention(nn.Layer):
    def __init__(self, config: SarvamMLAConfig):
        super().__init__()
        c, nh = config.hidden_size, config.num_heads
        init = nn.initializer.Normal(0.0, config.initializer_range)

        def lin(i, o):
            return nn.Linear(i, o, weight_attr=init, bias_attr=False)

        self.q_proj = lin(c, nh * config.qk_head_dim)
        if config.use_qk_norm:
            self.q_norm = nn.RMSNorm(
                config.qk_head_dim, config.rms_norm_eps,
                weight_attr=nn.initializer.Constant(
                    config.query_init_scale))
        self.kv_a_proj_with_mqa = lin(c, config.latent_dim)
        self.kv_a_layernorm = nn.RMSNorm(config.kv_lora_rank,
                                         config.rms_norm_eps)
        self.kv_b_proj = lin(
            config.kv_lora_rank,
            nh * (config.qk_nope_head_dim + config.v_head_dim))
        self.o_proj = lin(nh * config.v_head_dim, c)


class SarvamMoE(nn.Layer):
    """The HELD routed experts, stacked for the grouped matmuls
    (``gate_up`` [held, C, 2 I], ``down`` [held, I, C]), the router over
    all ``num_experts``, and the shared expert."""

    def __init__(self, config: SarvamMLAConfig):
        super().__init__()
        c, e = config.hidden_size, config.num_experts
        i, held = config.moe_intermediate_size, config.num_experts_held
        # columns of std C^-0.5: a token's router logits have unit
        # variance, so the sigmoid scores spread (xing4.py); the
        # selection bias small
        self.gate_weight = self.create_parameter(
            [c, e], default_initializer=nn.initializer.Normal(
                0.0, c ** -0.5))
        self.e_score_correction_bias = self.create_parameter(
            [e], default_initializer=nn.initializer.Normal(
                0.0, 0.01 if config.moe_router_enable_expert_bias
                else 0.0))
        std = config.initializer_range
        self.experts_gate_up = self.create_parameter(
            [held, c, 2 * i], default_initializer=_ExpertStack(
                std, config.expert_init_spread))
        self.experts_down = self.create_parameter(
            [held, i, c], default_initializer=_ExpertStack(
                std * config.routed_init_scale, config.expert_init_spread))
        self.shared_experts = Xing4MLP(config,
                                       i * config.num_shared_experts)


class SarvamBlock(nn.Layer):
    def __init__(self, config: SarvamMLAConfig, dense: bool):
        super().__init__()
        c, eps = config.hidden_size, config.rms_norm_eps
        self.input_layernorm = nn.RMSNorm(c, eps)
        self.self_attn = SarvamAttention(config)
        self.post_attention_layernorm = nn.RMSNorm(c, eps)
        self.mlp = Xing4MLP(config, config.intermediate_size) if dense \
            else SarvamMoE(config)


class SarvamModel(nn.Layer):
    def __init__(self, config: SarvamMLAConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=nn.initializer.Normal(0.0, config.initializer_range))
        self.layers = nn.LayerList([
            SarvamBlock(config, dense=i < config.first_k_dense_replace)
            for i in range(config.num_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)


class SarvamForCausalLM(LatentCausalLM):
    def __init__(self, config: SarvamMLAConfig):
        super().__init__(config, SarvamModel(config))

    def decode_adapter(self):
        """Weight-extraction protocol for the model-generic fused decode
        engine (models/generation.py)."""
        from .generation import SarvamDecodeAdapter

        return SarvamDecodeAdapter(self)
