"""Command A+ decoder (CohereLabs command-a-plus-05-2026, ``config.json``'s
``model_type`` ``cohere2_moe``), served through the model-generic decode
engine. Every layer is one PARALLEL block,

  h = LN(x),  x <- x + Attn(h) + MoE(h),

with Cohere's LayerNorm (mean and variance, a weight, no bias) read by both
branches. ``layer_types`` says which attention each layer has:

* ``sliding_attention``: grouped-query attention over the last
  ``sliding_window`` keys (key j is seen from query i iff i - W < j <= i),
  q and k rotated by RoPE in GPT-J's INTERLEAVED form (the pairs (x[2i],
  x[2i+1]), ``generation._rope_interleaved``) over the whole head;
* ``full_attention``: causal attention over every key with no position
  encoding (NoPE).

The expert branch: a float32 sigmoid router over ``num_experts``, the
``num_experts_per_tok`` largest scores chosen (no selection bias, no group
limit), their weights normalised over the chosen (``norm_topk_prob``);
SwiGLU experts of ``intermediate_size``; beside them ``num_shared_experts``
SwiGLU experts of the same width, AVERAGED. The shared experts are kept
side by side as one SwiGLU of ``num_shared_experts`` times the width, whose
output is their sum (the configuration file's ``assumed`` says why the
average and not the other reading). ``num_experts_held`` and
``expert_first`` say which contiguous range of the routed experts THIS
model holds (one chip of an expert-parallel deployment; ``sarvam.py`` says
how a share is served). After the last layer: LN and the head, tied to the
embedding, times ``logit_scale``.

The serving engine keeps a windowed layer's keys and values in a RING of
pages a slot, bounded by the window, beside the full layers' paged pools
(``serving/engine.py``). The plain float32 reference,
``benchmark/references/cohere2_moe.py``, has the equations once more.
Serving only, as ``xing4.py``: ``forward`` is the decode engine's prefill
(the grouped matmul has no backward pass).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

from .. import nn
from .xing4 import LatentCausalLM, _ExpertStack

__all__ = ["Cohere2MoeConfig", "Cohere2MoeModel", "Cohere2MoeForCausalLM",
           "cohere2_moe_tiny"]

LAYER_TYPES = ("sliding_attention", "full_attention")


def _period(n: int) -> List[str]:
    """The published order: three sliding layers, then a full one
    (``order_of_interleaved_layers`` ``local_attn_first``)."""
    return [LAYER_TYPES[(i + 1) % 4 == 0] for i in range(n)]


@dataclass
class Cohere2MoeConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 128
    num_kv_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 4096          # one expert's width
    max_position_embeddings: int = 200000
    tie_word_embeddings: bool = True
    use_bias: bool = False
    layer_norm_eps: float = 1e-5
    layer_types: Optional[List[str]] = None    # None: the published order
    sliding_window: int = 4096
    rope_theta: float = 50000.0
    use_parallel_block: bool = True
    logit_scale: float = 1.0
    # experts
    num_experts: int = 128                 # the router's width
    num_experts_held: Optional[int] = None     # None: all of them
    expert_first: int = 0                  # held: [first, first + held)
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    norm_topk_prob: bool = True
    # how the weights start, for a model served without trained weights
    # (benchmark/configs/command-a-plus-l4-ep8.json says why each)
    initializer_range: float = 0.02
    expert_init_spread: float = 1.0        # as SarvamMLAConfig's
    routed_init_scale: float = 1.0
    # None, or a float8 dtype's name: the margin's lower-precision
    # control, as SarvamMLAConfig's; no cell runs with it
    control_operand_dtype: Optional[str] = None

    # the family scales no routed weight (_ExpertBody reads it)
    routed_scaling_factor = 1.0

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = _period(self.num_layers)
        self.layer_types = list(self.layer_types)
        if self.num_experts_held is None:
            self.num_experts_held = self.num_experts
        if len(self.layer_types) != self.num_layers or \
                set(self.layer_types) - set(LAYER_TYPES):
            raise ValueError("layer_types must give one of %s for each of "
                             "the num_layers layers" % (LAYER_TYPES,))
        if self.use_bias:
            raise ValueError("the family's projections have no bias")
        if not self.use_parallel_block:
            raise ValueError("the family's layers are parallel blocks")
        if self.num_heads % self.num_kv_heads or self.head_dim % 2:
            raise ValueError("query heads must divide into KV groups, and "
                             "the head into rotated pairs")
        if self.sliding_window < 1:
            raise ValueError("sliding_window must be at least 1")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("num_experts_per_tok exceeds num_experts")
        if self.num_experts_held < 1 or self.expert_first < 0 or \
                self.expert_first + self.num_experts_held > self.num_experts:
            raise ValueError("the held experts [expert_first, expert_first "
                             "+ num_experts_held) must be a range of the "
                             "num_experts routed ones, and not empty")
        if not 0.0 < self.expert_init_spread <= 1.0:
            raise ValueError("expert_init_spread must lie in (0, 1]")

    def windowed(self) -> List[bool]:
        return [t == "sliding_attention" for t in self.layer_types]

    def published(self) -> dict:
        """These sizes under the keys of the published ``config.json``,
        and the share under the configuration file's, which the plain
        reference (``benchmark/references/cohere2_moe.py``) reads."""
        out = {k: getattr(self, k) for k in (
            "vocab_size", "hidden_size", "head_dim", "intermediate_size",
            "max_position_embeddings", "tie_word_embeddings",
            "layer_norm_eps", "layer_types", "sliding_window", "rope_theta",
            "num_experts_per_tok", "num_shared_experts", "norm_topk_prob",
            "logit_scale", "num_experts_held", "expert_first")}
        out.update(num_hidden_layers=self.num_layers,
                   num_attention_heads=self.num_heads,
                   num_key_value_heads=self.num_kv_heads,
                   num_experts=self.num_experts)
        return out


def cohere2_moe_tiny(**kw) -> Cohere2MoeConfig:
    kw = dict(dict(vocab_size=512, hidden_size=64, num_layers=4,
                   num_heads=4, num_kv_heads=2, head_dim=16,
                   intermediate_size=32, max_position_embeddings=512,
                   sliding_window=24, num_experts=8, num_experts_per_tok=3,
                   num_shared_experts=2), **kw)
    return Cohere2MoeConfig(**kw)


def _linear(config, i, o):
    return nn.Linear(i, o, bias_attr=False, weight_attr=nn.initializer.Normal(
        0.0, config.initializer_range))


class Cohere2MoeAttention(nn.Layer):
    def __init__(self, config: Cohere2MoeConfig):
        super().__init__()
        c, hd = config.hidden_size, config.head_dim
        self.q_proj = _linear(config, c, config.num_heads * hd)
        self.k_proj = _linear(config, c, config.num_kv_heads * hd)
        self.v_proj = _linear(config, c, config.num_kv_heads * hd)
        self.o_proj = _linear(config, config.num_heads * hd, c)


class Cohere2MoeMLP(nn.Layer):
    """SwiGLU of one width: the shared experts side by side."""

    def __init__(self, config: Cohere2MoeConfig, width: int):
        super().__init__()
        c = config.hidden_size
        self.gate_proj = _linear(config, c, width)
        self.up_proj = _linear(config, c, width)
        self.down_proj = _linear(config, width, c)


class Cohere2MoeSparseMoE(nn.Layer):
    """The HELD routed experts, stacked for the grouped matmuls
    (``gate_up`` [held, C, 2 I], ``down`` [held, I, C]), the router over
    all ``num_experts`` (no selection bias), and the shared experts."""

    def __init__(self, config: Cohere2MoeConfig):
        super().__init__()
        c, e = config.hidden_size, config.num_experts
        i, held = config.intermediate_size, config.num_experts_held
        # columns of std C^-0.5: a token's router logits have unit
        # variance, so the sigmoid scores spread (xing4.py)
        self.gate_weight = self.create_parameter(
            [c, e], default_initializer=nn.initializer.Normal(
                0.0, c ** -0.5))
        std = config.initializer_range
        self.experts_gate_up = self.create_parameter(
            [held, c, 2 * i], default_initializer=_ExpertStack(
                std, config.expert_init_spread))
        self.experts_down = self.create_parameter(
            [held, i, c], default_initializer=_ExpertStack(
                std * config.routed_init_scale, config.expert_init_spread))
        self.shared_experts = Cohere2MoeMLP(config,
                                            i * config.num_shared_experts)


class Cohere2MoeLayer(nn.Layer):
    def __init__(self, config: Cohere2MoeConfig, layer_type: str):
        super().__init__()
        self.layer_type = layer_type
        self.input_layernorm = nn.LayerNorm(
            config.hidden_size, config.layer_norm_eps, bias_attr=False)
        self.self_attn = Cohere2MoeAttention(config)
        self.mlp = Cohere2MoeSparseMoE(config)


class Cohere2MoeModel(nn.Layer):
    def __init__(self, config: Cohere2MoeConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=nn.initializer.Normal(0.0, config.initializer_range))
        self.layers = nn.LayerList([Cohere2MoeLayer(config, t)
                                    for t in config.layer_types])
        self.norm = nn.LayerNorm(config.hidden_size, config.layer_norm_eps,
                                 bias_attr=False)


class Cohere2MoeForCausalLM(LatentCausalLM):
    def __init__(self, config: Cohere2MoeConfig):
        if not config.tie_word_embeddings:
            raise ValueError("the family ties its head to the embedding")
        super().__init__(config, Cohere2MoeModel(config))

    def decode_adapter(self):
        """Weight-extraction protocol for the model-generic fused decode
        engine (models/generation.py)."""
        from .generation import Cohere2MoeDecodeAdapter

        return Cohere2MoeDecodeAdapter(self)
