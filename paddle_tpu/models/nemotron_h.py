"""Nemotron-H decoder (nvidia NVIDIA-Nemotron-3-Nano-30B-A3B,
``config.json``'s ``model_type`` ``nemotron_h``): a HYBRID stack of
single-mixer blocks, ``x <- x + f(RMSNorm(x))``, served through the
model-generic decode engine. ``hybrid_override_pattern`` says which f
each block is, a letter a block:

* ``M``, a **Mamba-2 (SSD) mixer**: ``in_proj`` to [z | xBC | dt]; a
  causal depthwise conv (``conv_kernel`` taps, with bias) over xBC, then
  SiLU, split into x (``mamba_num_heads`` heads of ``mamba_head_dim``),
  B and C (``n_groups`` groups of ``ssm_state_size``); dt =
  softplus(dt + dt_bias), A = -exp(A_log); per head the recurrence
  S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + D x_t;
  y <- RMSNorm over groups of d_inner / n_groups (y * SiLU(z)); ``out_proj``.
  Its state, a [heads, head_dim, state] matrix and the conv's last
  ``conv_kernel - 1`` inputs, is all it carries from token to token: the
  serving engine keeps it per slot beside the paged KV of the attention
  layers (``incubate/nn/pallas/ssd_scan.py`` scans a ragged step).
* ``*``, **grouped-query attention** with no position encoding (the
  family's Mamba layers carry position) and no bias.
* ``E``, an **expert layer**: a float32 sigmoid router over
  ``num_experts``, the ``num_experts_per_tok`` largest of score plus a
  selection bias, weights normalised over the chosen and times
  ``routed_scaling_factor``; non-gated relu² experts,
  down(relu(up(h))²), beside one shared relu² expert.
  ``num_experts_held`` and ``expert_first`` say which contiguous range of
  the routed experts THIS model holds (one chip of an expert-parallel
  deployment; ``sarvam.py`` says how a share is served). All held (the
  default) is the whole layer.
* ``-``, a dense relu² MLP of ``intermediate_size``.

The plain float32 reference, ``benchmark/references/nemotron_h.py``, has
the equations once more; the configuration file's ``assumed`` lists every
choice ``config.json`` leaves open. Serving only, as ``xing4.py``:
``forward`` is the decode engine's prefill (the grouped matmul and the
scan have no backward pass).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..core import random as _rng
from .xing4 import LatentCausalLM, _ExpertStack

__all__ = ["NemotronHConfig", "NemotronHModel", "NemotronHForCausalLM",
           "nemotron_h_tiny"]

KINDS = {"M": "mamba", "*": "attention", "E": "moe", "-": "mlp"}


@dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_layers: int = 52
    num_heads: int = 32                    # attention's query heads
    num_kv_heads: int = 2
    head_dim: int = 128
    intermediate_size: int = 1856          # a "-" block's MLP
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    use_bias: bool = False
    hybrid_override_pattern: str = (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    # the Mamba-2 mixer
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # the recurrent state's dtype as the engine keeps it (the conv's last
    # inputs are kept in the weights' dtype)
    ssm_state_dtype: str = "float32"
    # experts
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    num_experts: int = 128                 # the router's width
    num_experts_held: Optional[int] = None     # None: all of them
    expert_first: int = 0                  # held: [first, first + held)
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    # how the weights start, for a model served without trained weights
    # (benchmark/configs/nemotron3-nano-l18-ep8.json says why each)
    initializer_range: float = 0.02
    # out_proj starts at initializer_range / sqrt(this)
    # (rescale_prenorm_residual); None: num_layers
    residual_init_layers: Optional[int] = None
    expert_init_spread: float = 1.0        # as SarvamMLAConfig's
    routed_init_scale: float = 1.0
    # None, or a float8 dtype's name: the margin's lower-precision
    # control, as SarvamMLAConfig's; no cell runs with it
    control_operand_dtype: Optional[str] = None

    def __post_init__(self):
        if self.num_experts_held is None:
            self.num_experts_held = self.num_experts
        if len(self.hybrid_override_pattern) != self.num_layers or \
                set(self.hybrid_override_pattern) - set(KINDS):
            raise ValueError("hybrid_override_pattern must give one of %s "
                             "for each of the num_layers blocks"
                             % "".join(KINDS))
        if self.use_bias:
            raise ValueError("the family's projections have no bias")
        if self.mamba_num_heads % self.n_groups or \
                self.num_heads % self.num_kv_heads:
            raise ValueError("heads must divide into their groups")
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
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    def kinds(self):
        return [KINDS[c] for c in self.hybrid_override_pattern]

    def published(self) -> dict:
        """These sizes under the keys of the published ``config.json``,
        and the share under the configuration file's, which the plain
        reference (``benchmark/references/nemotron_h.py``) reads."""
        out = {k: getattr(self, k) for k in (
            "vocab_size", "hidden_size", "head_dim", "intermediate_size",
            "max_position_embeddings", "tie_word_embeddings",
            "hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim",
            "ssm_state_size", "n_groups", "conv_kernel",
            "moe_intermediate_size", "moe_shared_expert_intermediate_size",
            "num_experts_per_tok", "routed_scaling_factor",
            "norm_topk_prob", "num_experts_held", "expert_first")}
        out.update(num_hidden_layers=self.num_layers,
                   num_attention_heads=self.num_heads,
                   num_key_value_heads=self.num_kv_heads,
                   n_routed_experts=self.num_experts,
                   layer_norm_epsilon=self.rms_norm_eps)
        return out


def nemotron_h_tiny(**kw) -> NemotronHConfig:
    kw = dict(dict(vocab_size=512, hidden_size=64, num_layers=6,
                   hybrid_override_pattern="ME*M-E", num_heads=4,
                   num_kv_heads=2, head_dim=16, intermediate_size=96,
                   max_position_embeddings=512, mamba_num_heads=8,
                   mamba_head_dim=8, ssm_state_size=16, n_groups=2,
                   moe_intermediate_size=32,
                   moe_shared_expert_intermediate_size=48, num_experts=8,
                   num_experts_per_tok=3), **kw)
    return NemotronHConfig(**kw)


class _DtBias(nn.initializer.Initializer):
    """Mamba-2's start: dt log-uniform in [min, max], floored, and the
    bias its softplus inverse."""

    def __init__(self, lo, hi, floor):
        self.lo, self.hi, self.floor = lo, hi, floor

    def __call__(self, shape, dtype=jnp.float32):
        u = jax.random.uniform(_rng.next_key(), tuple(shape))
        dt = jnp.exp(u * (math.log(self.hi) - math.log(self.lo))
                     + math.log(self.lo))
        dt = jnp.maximum(dt, self.floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class _ALog(nn.initializer.Initializer):
    """A_log = log U[1, 16] (Mamba-2's start)."""

    def __call__(self, shape, dtype=jnp.float32):
        return jnp.log(jax.random.uniform(
            _rng.next_key(), tuple(shape), minval=1.0, maxval=16.0)) \
            .astype(dtype)


def _linear(config, i, o, std=None):
    return nn.Linear(i, o, bias_attr=False, weight_attr=nn.initializer.Normal(
        0.0, config.initializer_range if std is None else std))


class NemotronHMamba2Mixer(nn.Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        c, h, di = config.hidden_size, config.mamba_num_heads, config.d_inner
        self.in_proj = _linear(config, c, di + config.conv_dim + h)
        # PyTorch's default for a depthwise conv of 4 taps: U(+-1/2)
        self.conv_weight = self.create_parameter(
            [config.conv_kernel, config.conv_dim],
            default_initializer=nn.initializer.Uniform(-0.5, 0.5))
        self.conv_bias = self.create_parameter(
            [config.conv_dim],
            default_initializer=nn.initializer.Uniform(-0.5, 0.5))
        self.dt_bias = self.create_parameter(
            [h], default_initializer=_DtBias(
                config.time_step_min, config.time_step_max,
                config.time_step_floor))
        self.A_log = self.create_parameter([h], default_initializer=_ALog())
        self.D = self.create_parameter(
            [h], default_initializer=nn.initializer.Constant(1.0))
        self.norm = nn.RMSNorm(di, config.rms_norm_eps)
        layers = config.residual_init_layers or config.num_layers
        self.out_proj = _linear(config, di, c,
                                config.initializer_range / layers ** 0.5)


class NemotronHAttention(nn.Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        c, hd = config.hidden_size, config.head_dim
        self.q_proj = _linear(config, c, config.num_heads * hd)
        self.k_proj = _linear(config, c, config.num_kv_heads * hd)
        self.v_proj = _linear(config, c, config.num_kv_heads * hd)
        self.o_proj = _linear(config, config.num_heads * hd, c)


class NemotronHMLP(nn.Layer):
    """relu² of one width: a "-" block, or the shared expert."""

    def __init__(self, config: NemotronHConfig, width: int):
        super().__init__()
        self.up_proj = _linear(config, config.hidden_size, width)
        self.down_proj = _linear(config, width, config.hidden_size)


class NemotronHMoE(nn.Layer):
    """The HELD routed experts, stacked for the grouped matmuls (``up``
    [held, I, C], out x in as a Linear keeps it: the chip lays a [held,
    C, 1856] stack out with C minor, which the kernel could not read
    without a copy a call; ``down`` [held, I, C]), the router over all
    ``num_experts`` and the shared expert."""

    def __init__(self, config: NemotronHConfig):
        super().__init__()
        c, e = config.hidden_size, config.num_experts
        i, held = config.moe_intermediate_size, config.num_experts_held
        # columns of std C^-0.5: the sigmoid scores spread (xing4.py)
        self.gate_weight = self.create_parameter(
            [c, e], default_initializer=nn.initializer.Normal(
                0.0, c ** -0.5))
        self.e_score_correction_bias = self.create_parameter(
            [e], default_initializer=nn.initializer.Normal(0.0, 0.01))
        std = config.initializer_range
        self.experts_up = self.create_parameter(
            [held, i, c], default_initializer=_ExpertStack(
                std, config.expert_init_spread))
        self.experts_down = self.create_parameter(
            [held, i, c], default_initializer=_ExpertStack(
                std * config.routed_init_scale, config.expert_init_spread))
        self.shared_experts = NemotronHMLP(
            config, config.moe_shared_expert_intermediate_size)


class NemotronHBlock(nn.Layer):
    def __init__(self, config: NemotronHConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.mixer = {"mamba": NemotronHMamba2Mixer,
                      "attention": NemotronHAttention,
                      "moe": NemotronHMoE,
                      "mlp": lambda c: NemotronHMLP(c, c.intermediate_size)
                      }[kind](config)


class NemotronHModel(nn.Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=nn.initializer.Normal(0.0, config.initializer_range))
        self.layers = nn.LayerList([NemotronHBlock(config, k)
                                    for k in config.kinds()])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)


class NemotronHForCausalLM(LatentCausalLM):
    def __init__(self, config: NemotronHConfig):
        super().__init__(config, NemotronHModel(config))

    def decode_adapter(self):
        """Weight-extraction protocol for the model-generic fused decode
        engine (models/generation.py)."""
        from .generation import NemotronHDecodeAdapter

        return NemotronHDecodeAdapter(self)
