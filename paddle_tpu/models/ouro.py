"""Ouro LoopLM decoder (ByteDance Ouro 1.4B / 2.6B, arXiv:2510.25741): ONE
stack of Llama-style layers applied ``total_ut_steps`` times on shared
weights, TPU-first.

What differs from ``models/llama.py``, whose attention and SwiGLU modules
are reused as they are:

* the block is a SANDWICH: the output of each sublayer is RMS-normed too
  before it joins the residual stream (four RMSNorms a block);
* the whole stack runs R = ``total_ut_steps`` times over the same
  weights; the final norm closes EVERY pass, and its output is both that
  pass's hidden state ``h_r`` and the next pass's input;
* every (pass, layer) keeps its own keys and values: a decode cache has
  R x L entries, index ``r * L + l`` (``OuroDecodeAdapter.cache_layers``);
* an exit gate ``Linear(hidden, 1)`` reads each ``h_r``:
  ``lambda_r = sigmoid(gate(h_r))``, exit mass ``p_r = lambda_r *
  prod_{j<r}(1 - lambda_j)`` with the remainder on the last pass. A
  token leaves at the first pass whose cumulative mass reaches
  ``early_exit_threshold`` and its logits are read from that pass's
  hidden state. Every pass still runs for every token (later tokens
  attend to its keys and values in all R caches). At the published
  threshold of 1 every token leaves at pass R.

The plain float32 reference is ``benchmark/references/ouro.py``. There is
no training loss here: the published one (an entropy-regularised
expectation over the exits) has constants ``config.json`` does not give.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp

from .. import nn
from ..distributed.auto_parallel.constraint import annotate_param, shard_activation
from ..nn import functional as F
from ..ops._helpers import run_op
from .llama import LlamaAttention, LlamaMLP

__all__ = ["OuroConfig", "OuroModel", "OuroForCausalLM", "ouro_tiny",
           "ouro_2p6B", "exit_pass", "exit_hidden"]


@dataclass
class OuroConfig:
    vocab_size: int = 49152
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 16
    num_kv_heads: Optional[int] = None  # None -> MHA
    intermediate_size: int = 5632
    max_position_embeddings: int = 65536
    rope_base: float = 1000000.0
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    # how the weights start, for a model that is served without trained
    # weights (benchmark/configs/ouro-2p6b.json says why): the gain the
    # two norms on the sublayers' OUTPUTS start at (the published code's
    # 1 makes a randomly initialised stack double any rounding error a
    # pass; 1 / sqrt(2 L) keeps the residual stream's RMS near 1), and
    # the sigma of a log-normal scale on v_proj's output channels (0:
    # none; trained values have a few channels far larger than the rest)
    sublayer_norm_init: float = 1.0
    value_channel_spread: float = 0.0
    tie_word_embeddings: bool = False
    total_ut_steps: int = 4             # R: passes over the one stack
    early_exit_threshold: float = 1.0   # cumulative exit mass to leave at
    # read by the reused LlamaAttention ("gspmd" | "ring" | "ulysses")
    sequence_parallel_mode: str = "gspmd"

    def __post_init__(self):
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads
        if self.total_ut_steps < 1:
            raise ValueError("total_ut_steps must be >= 1")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    def published(self) -> dict:
        """These sizes under the keys of the published ``config.json``,
        which the plain reference (``benchmark/references/ouro.py``) reads."""
        return dict(
            num_hidden_layers=self.num_layers,
            num_attention_heads=self.num_heads,
            num_key_value_heads=self.num_kv_heads,
            rms_norm_eps=self.rms_norm_eps, rope_theta=self.rope_base,
            total_ut_steps=self.total_ut_steps,
            early_exit_threshold=self.early_exit_threshold)


def ouro_tiny(**kw) -> OuroConfig:
    kw = dict(dict(vocab_size=512, hidden_size=64, num_layers=3,
                   num_heads=4, intermediate_size=128,
                   max_position_embeddings=256, total_ut_steps=3), **kw)
    return OuroConfig(**kw)


def ouro_2p6B(**kw) -> OuroConfig:
    return OuroConfig(**kw)


def exit_pass(lambdas, threshold):
    """The pass (1-based) at which each token leaves: the first whose
    cumulative exit mass reaches ``threshold``, the last where none does.
    ``lambdas``: the R gate values per token, a sequence of float32
    arrays of one shape. -> int8 array of that shape."""
    remaining = jnp.ones_like(lambdas[0])
    cum = jnp.zeros_like(lambdas[0])
    out = jnp.full(lambdas[0].shape, len(lambdas), jnp.int8)
    for r, lam in enumerate(lambdas[:-1]):
        cum = cum + lam * remaining
        remaining = remaining * (1.0 - lam)
        out = jnp.where((cum >= threshold) & (out == len(lambdas)),
                        jnp.int8(r + 1), out)
    return out


def exit_hidden(hs, ex):
    """Each token's hidden state at its exit pass: ``hs`` the R passes'
    hidden states [..., h], ``ex`` from :func:`exit_pass`."""
    out = hs[-1]
    for r, h in enumerate(hs[:-1]):
        out = jnp.where((ex == r + 1)[..., None], h, out)
    return out


class OuroBlock(nn.Layer):
    """Sandwich block: norm -> attention -> norm -> add, norm -> SwiGLU ->
    norm -> add."""

    def __init__(self, config: OuroConfig):
        super().__init__()
        h, eps = config.hidden_size, config.rms_norm_eps
        out_gain = nn.initializer.Constant(config.sublayer_norm_init)
        self.input_layernorm = nn.RMSNorm(h, eps)
        self.self_attn = LlamaAttention(config)
        self.input_layernorm_2 = nn.RMSNorm(h, eps, weight_attr=out_gain)
        self.post_attention_layernorm = nn.RMSNorm(h, eps)
        self.mlp = LlamaMLP(config)
        self.post_attention_layernorm_2 = nn.RMSNorm(h, eps,
                                                     weight_attr=out_gain)
        if config.value_channel_spread:
            # a log-normal scale a value channel, the matrix's RMS kept
            w = self.self_attn.v_proj.weight
            s = jnp.exp(nn.initializer.Normal(
                0.0, config.value_channel_spread)([w.shape[1]]))
            s = s / jnp.sqrt(jnp.mean(s * s))
            w.set_value((w.value.astype(jnp.float32) * s)
                        .astype(w.value.dtype))

    def forward(self, x, position_ids=None):
        a = self.self_attn(self.input_layernorm(x),
                           position_ids=position_ids)
        x = x + self.input_layernorm_2(a)
        m = self.mlp(self.post_attention_layernorm(x))
        x = x + self.post_attention_layernorm_2(m)
        return shard_activation(x, ("dp", "sp", None))


class OuroModel(nn.Layer):
    def __init__(self, config: OuroConfig):
        super().__init__()
        self.config = config
        init = nn.initializer.Normal(0.0, config.initializer_range)
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size, weight_attr=init)
        annotate_param(self.embed_tokens.weight, ("mp", None))
        self.layers = nn.LayerList([OuroBlock(config)
                                    for _ in range(config.num_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.early_exit_gate = nn.Linear(config.hidden_size, 1,
                                         weight_attr=init)

    def forward(self, input_ids, position_ids=None):
        """-> (hidden states at each token's exit pass [b, s, h], the
        exit pass [b, s] int8)."""
        cfg = self.config
        x = self.embed_tokens(input_ids)
        x = shard_activation(x, ("dp", "sp", None))
        hs, lambdas = [], []
        for _ in range(cfg.total_ut_steps):
            for block in self.layers:
                x = block(x, position_ids=position_ids)
            x = self.norm(x)
            hs.append(x)
            lambdas.append(F.sigmoid(
                self.early_exit_gate(x).astype("float32"))[..., 0])
        thr = float(cfg.early_exit_threshold)
        ex = run_op(lambda *lam: exit_pass(lam, thr),
                    [lam.detach() for lam in lambdas], name="ouro_exit_pass")
        return run_op(lambda e, *h: exit_hidden(h, e), [ex] + hs,
                      name="ouro_exit_hidden"), ex


class OuroForCausalLM(nn.Layer):
    def __init__(self, config: OuroConfig):
        super().__init__()
        self.config = config
        self.ouro = OuroModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(
                config.hidden_size, config.vocab_size, bias_attr=False,
                weight_attr=nn.initializer.Normal(
                    0.0, config.initializer_range))
            annotate_param(self.lm_head.weight, (None, "mp"))

    def forward(self, input_ids, position_ids=None):
        x, _ = self.ouro(input_ids, position_ids)
        if self.lm_head is not None:
            logits = self.lm_head(x)
        else:
            logits = run_op(lambda a, w: jnp.matmul(a, w.T),
                            [x, self.ouro.embed_tokens.weight],
                            name="lm_head_tied")
        return shard_activation(logits, ("dp", "sp", "mp"))

    def generate(self, input_ids, max_new_tokens=32, temperature=0.0,
                 top_p=None, eos_token_id=None, weight_quant=None,
                 kv_cache_quant=None):
        """Fully-compiled autoregressive decoding via the model-generic
        fused decode engine (models/generation.py)."""
        from .generation import generate as _gen

        return _gen(self, input_ids, max_new_tokens=max_new_tokens,
                    temperature=temperature, top_p=top_p,
                    eos_token_id=eos_token_id, weight_quant=weight_quant,
                    kv_cache_quant=kv_cache_quant)

    def decode_adapter(self):
        """Weight-extraction protocol for the model-generic fused decode
        engine (models/generation.py)."""
        from .generation import OuroDecodeAdapter

        return OuroDecodeAdapter(self)
