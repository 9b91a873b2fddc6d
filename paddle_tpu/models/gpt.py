"""GPT-3 family decoder-only LM, TPU-first.

Reference analogs: the GPT models driven by the reference's hybrid-parallel
tests (test/collective/fleet/hybrid_parallel_*; PaddleNLP GPT) and BASELINE
config #4 (GPT-3 1.3B/6.7B/13B, mp×pp×sharding 1F1B).

TPU-native design notes:
  - Megatron-style tensor parallel is expressed as *sharding annotations*
    (qkv/fc1 column-split on "mp", out/fc2 row-split on "mp", embedding
    vocab-split on "mp"); GSPMD inserts the all-reduces the reference does
    explicitly in fleet/layers/mpu/mp_layers.py:336,543.
  - Sequence parallel = activations sharded on "sp" along the seq dim
    (reference: fleet/utils/sequence_parallel_utils.py) — GSPMD turns the
    mp all-reduces into reduce-scatter/all-gather pairs automatically.
  - Attention runs through F.scaled_dot_product_attention which dispatches
    to the Pallas flash-attention kernel on TPU.
  - Everything is static-shape, bfloat16-friendly, and jit-traceable.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..distributed.auto_parallel.constraint import annotate_param, shard_activation
from ..nn import functional as F
from ..observability import scopes as _scopes
import numpy as np

from ..ops._helpers import as_tensor, run_op, unwrap

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM",
           "GPTPretrainingCriterion", "gpt_tiny", "gpt3_125M", "gpt3_1p3B",
           "gpt3_6p7B", "gpt3_13B"]


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: Optional[int] = None  # default 4*hidden
    max_position_embeddings: int = 2048
    dropout: float = 0.0
    attention_dropout: float = 0.0
    layer_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    tie_word_embeddings: bool = True
    use_bias: bool = True
    # recompute (reference: fleet/recompute) — rematerialize each block
    recompute: bool = False
    # selective remat: skip rematerialization on every k-th block (its
    # activations are saved instead). 1 = full per-block remat; 2 halves
    # the recompute FLOPs at the cost of saving every other block's
    # activations. The 6N-credited MFU ceiling with full remat is
    # 6/8 = 0.75 of hardware util — this knob buys back most of it.
    recompute_interval: int = 1
    # fused chunked lm_head+CE (reference analog: the fused softmax-CE
    # kernels under phi/kernels/fusion/): >0 computes the training loss in
    # this many token chunks under jax.checkpoint, never materializing the
    # full [tokens, vocab] logits (1.6GB at b16 s1024) nor its gradient
    lm_ce_chunks: int = 0
    # "gspmd" | "ring" | "ulysses" — how attention handles a seq-sharded
    # layout over the "sp" mesh axis (see models/_sp_attention.py)
    sequence_parallel_mode: str = "gspmd"
    # MoE: >0 replaces every block's MLP with a top-2 GShard mixture of
    # this many experts (expert weights sharded over the "ep" mesh axis;
    # GSPMD places the dispatch/combine all-to-alls — the jit analog of
    # incubate/distributed/models/moe, reference moe_layer.py:263)
    moe_num_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01

    def __post_init__(self):
        if self.intermediate_size is None:
            self.intermediate_size = 4 * self.hidden_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


def gpt_tiny(**kw) -> GPTConfig:
    return GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                     num_heads=4, max_position_embeddings=256, **kw)


def gpt3_125M(**kw) -> GPTConfig:
    return GPTConfig(hidden_size=768, num_layers=12, num_heads=12, **kw)


def gpt3_1p3B(**kw) -> GPTConfig:
    return GPTConfig(hidden_size=2048, num_layers=24, num_heads=16, **kw)


def gpt3_6p7B(**kw) -> GPTConfig:
    return GPTConfig(hidden_size=4096, num_layers=32, num_heads=32, **kw)


def gpt3_13B(**kw) -> GPTConfig:
    return GPTConfig(hidden_size=5120, num_layers=40, num_heads=40, **kw)


def _offset_causal_mask(q_len: int, past: int):
    """Bool mask [1,1,q,past+q] for chunked prefill (q>1 with a non-empty
    cache): query t may attend keys <= past+t. None when is_causal or the
    single-token decode path already gives the right semantics."""
    if q_len <= 1 or past == 0:
        return None
    kv = past + q_len
    qi = jnp.arange(q_len)[:, None]
    ki = jnp.arange(kv)[None, :]
    return Tensor((ki <= qi + past)[None, None])


class GPTAttention(nn.Layer):
    """Causal self-attention; qkv fused column-parallel, out row-parallel."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        h = config.hidden_size
        init = nn.initializer.Normal(0.0, config.initializer_range)
        self.qkv_proj = nn.Linear(
            h, 3 * h, weight_attr=init,
            bias_attr=None if config.use_bias else False)
        self.out_proj = nn.Linear(
            h, h, weight_attr=nn.initializer.Normal(
                0.0, config.initializer_range / math.sqrt(2 * config.num_layers)),
            bias_attr=None if config.use_bias else False)
        annotate_param(self.qkv_proj.weight, (None, "mp"))
        annotate_param(self.out_proj.weight, ("mp", None))
        if config.use_bias:
            annotate_param(self.qkv_proj.bias, ("mp",))
            annotate_param(self.out_proj.bias, (None,))

    def forward(self, x, cache=None):
        from .. import fusion

        cfg = self.config
        b, s = x.shape[0], x.shape[1]
        # column-parallel projection: decomposed chunks let the bwd
        # input-grad psum ride inside the GEMM loop (overlap off -> None)
        qkv = fusion.overlap_linear(x, self.qkv_proj.weight,
                                    self.qkv_proj.bias, op="gpt_qkv")
        if qkv is None:
            qkv = self.qkv_proj(x)  # [b, s, 3h]
        qkv = qkv.reshape([b, s, 3, cfg.num_heads, cfg.head_dim])
        q, k, v = (qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2])
        past = 0
        if cache is not None:
            from ..ops.manipulation import concat

            past = cache[0].shape[1]
            k = concat([cache[0], k], axis=1)
            v = concat([cache[1], v], axis=1)
            cache = (k, v)
        q = shard_activation(q, ("dp", "sp", "mp", None))
        out = None
        dropout_p = cfg.attention_dropout if self.training else 0.0
        with _scopes.phase("attn.kernel"):
            if cache is None and s > 1 and dropout_p == 0.0:
                # ring/ulysses paths carry no dropout; keep gspmd semantics
                # when attention dropout is active
                from ._sp_attention import sp_attention

                out = sp_attention(q, k, v, cfg.sequence_parallel_mode,
                                   causal=True)
            if out is None:
                out = F.scaled_dot_product_attention(
                    q, k, v, is_causal=s > 1 and past == 0,
                    attn_mask=_offset_causal_mask(s, past),
                    dropout_p=dropout_p,
                    training=self.training)  # [b, s, heads, head_dim]
        out = out.reshape([b, s, cfg.num_heads * cfg.head_dim])
        # row-parallel projection: per-chunk partial-sum collectives ride
        # the GEMM loop instead of one psum after it
        proj = fusion.overlap_linear(out, self.out_proj.weight,
                                     self.out_proj.bias, op="gpt_out_proj")
        out = proj if proj is not None else self.out_proj(out)
        if cache is not None:
            return out, cache
        return out


class GPTMLP(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        h, ffn = config.hidden_size, config.intermediate_size
        init = nn.initializer.Normal(0.0, config.initializer_range)
        self.fc1 = nn.Linear(h, ffn, weight_attr=init,
                             bias_attr=None if config.use_bias else False)
        self.fc2 = nn.Linear(
            ffn, h, weight_attr=nn.initializer.Normal(
                0.0, config.initializer_range / math.sqrt(2 * config.num_layers)),
            bias_attr=None if config.use_bias else False)
        annotate_param(self.fc1.weight, (None, "mp"))
        annotate_param(self.fc2.weight, ("mp", None))
        if config.use_bias:
            annotate_param(self.fc1.bias, ("mp",))
            annotate_param(self.fc2.bias, (None,))

    def forward(self, x):
        from .. import fusion

        if fusion.route("bias_gelu"):
            # fc1 + bias + gelu as one traced region (one tape node, one
            # XLA fusion candidate); quantized matmuls when requested
            qm = fusion.quant_route("gpt_mlp")
            h = fusion.linear_gelu(x, self.fc1.weight, self.fc1.bias,
                                   approximate=True,
                                   shard_axes=("dp", "sp", "mp"),
                                   quant_mode=qm)
            out = fusion.overlap_linear(h, self.fc2.weight, self.fc2.bias,
                                        op="gpt_fc2", quant_mode=qm)
            if out is not None:
                return out
            if qm != "off":
                return fusion.quantized_linear(h, self.fc2.weight,
                                               self.fc2.bias, mode=qm)
            return self.fc2(h)
        x = self.fc1(x)
        x = shard_activation(x, ("dp", "sp", "mp"))
        x = F.gelu(x, approximate=True)
        return self.fc2(x)


class GPTMoEMLP(nn.Layer):
    """jit/SPMD mixture-of-experts FFN: stacked expert weights [E, ...]
    sharded over the "ep" mesh axis; top-2 GShard capacity routing with
    one-hot einsum dispatch/combine (static shapes — GSPMD emits the
    expert all-to-alls on the mesh). Aux load-balance loss is exposed via
    ``last_aux_loss`` and summed into the LM loss by GPTForCausalLM.
    Reference analog: incubate/distributed/models/moe/moe_layer.py:263 +
    phi spmd rules moe_gate_dispatch.cc (here: GSPMD)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        h, ffn = config.hidden_size, config.intermediate_size
        E = config.moe_num_experts
        self.config = config
        self.num_experts = E
        init = nn.initializer.Normal(0.0, config.initializer_range)
        self.gate_weight = self.create_parameter(
            [h, E], default_initializer=init)
        self.w1 = self.create_parameter([E, h, ffn],
                                        default_initializer=init)
        self.b1 = self.create_parameter([E, ffn], is_bias=True)
        self.w2 = self.create_parameter(
            [E, ffn, h], default_initializer=nn.initializer.Normal(
                0.0, config.initializer_range
                / math.sqrt(2 * config.num_layers)))
        self.b2 = self.create_parameter([E, h], is_bias=True)
        annotate_param(self.w1, ("ep", None, "mp"))
        annotate_param(self.b1, ("ep", "mp"))
        annotate_param(self.w2, ("ep", "mp", None))
        annotate_param(self.b2, ("ep", None))
        self.last_aux_loss = None

    def forward(self, x):
        from .. import fusion

        cfg = self.config
        b, s, d = x.shape[0], x.shape[1], x.shape[2]
        E = self.num_experts
        cap = max(4, int(cfg.moe_capacity_factor * b * s * 2 / E))

        if fusion.route("moe_dispatch"):
            # scatter/gather dispatch — no [S, E, C] one-hot tensors
            y, aux = fusion.fused_moe_mlp(x, self.gate_weight, self.w1,
                                          self.b1, self.w2, self.b2, E, cap)
            self.last_aux_loss = aux
            return y

        def fn(xa, gw, w1, b1, w2, b2):
            S = b * s
            xf = xa.reshape(S, d)
            gates = jax.nn.softmax(
                (xf @ gw).astype(jnp.float32), axis=-1)
            idx1 = jnp.argmax(gates, -1)
            m1 = jax.nn.one_hot(idx1, E, dtype=jnp.float32)
            g1 = jnp.sum(gates * m1, -1)
            gates2 = gates * (1.0 - m1)
            idx2 = jnp.argmax(gates2, -1)
            m2 = jax.nn.one_hot(idx2, E, dtype=jnp.float32)
            g2 = jnp.sum(gates2 * m2, -1)
            aux = jnp.sum(jnp.mean(m1, 0) * jnp.mean(gates, 0)) * E

            pos1 = jnp.cumsum(m1, 0) * m1 - m1
            pos2 = (jnp.cumsum(m2, 0) - 1.0 + jnp.sum(m1, 0)[None]) * m2
            m1 = m1 * (pos1 < cap)
            m2 = m2 * (pos2 < cap)
            p1 = jnp.sum(pos1, -1).astype(jnp.int32)
            p2 = jnp.sum(pos2, -1).astype(jnp.int32)
            g1 = g1 * jnp.sum(m1, -1)
            g2 = g2 * jnp.sum(m2, -1)
            denom = jnp.where(g1 + g2 > 0, g1 + g2, 1.0)
            g1, g2 = g1 / denom, g2 / denom
            oh1 = jax.nn.one_hot(p1, cap, dtype=jnp.float32)
            oh2 = jax.nn.one_hot(p2, cap, dtype=jnp.float32)
            cw = (g1[:, None, None] * m1[:, :, None] * oh1[:, None, :]
                  + g2[:, None, None] * m2[:, :, None] * oh2[:, None, :])
            dm = (cw > 0).astype(xf.dtype)
            cw = cw.astype(xf.dtype)

            xe = jnp.einsum("sec,sm->ecm", dm, xf)
            h1 = jax.nn.gelu(
                jnp.einsum("ecm,emh->ech", xe, w1) + b1[:, None, :],
                approximate=True)
            ye = jnp.einsum("ech,ehm->ecm", h1, w2) + b2[:, None, :]
            y = jnp.einsum("sec,ecm->sm", cw, ye)
            return y.reshape(b, s, d), aux.astype(jnp.float32)

        y, aux = run_op(fn, [x, self.gate_weight, self.w1, self.b1,
                             self.w2, self.b2], name="moe_mlp")
        self.last_aux_loss = aux
        return y


class GPTBlock(nn.Layer):
    def __init__(self, config: GPTConfig, layer_idx: int = 0):
        super().__init__()
        self.ln_1 = nn.LayerNorm(config.hidden_size, config.layer_norm_eps)
        self.attn = GPTAttention(config)
        self.ln_2 = nn.LayerNorm(config.hidden_size, config.layer_norm_eps)
        self.mlp = (GPTMoEMLP(config) if config.moe_num_experts
                    else GPTMLP(config))
        self.dropout = nn.Dropout(config.dropout)
        interval = int(getattr(config, "recompute_interval", 1)) or 1
        # selective recompute: interval k>0 skips remat on every k-th
        # block; k<0 remats ONLY every (-k)-th block (saves the rest)
        if interval > 0:
            remat_this = interval == 1 or \
                layer_idx % interval != interval - 1
        else:
            remat_this = layer_idx % (-interval) == 0
        self._recompute = config.recompute and remat_this

    def _body(self, x, cache=None):
        from .. import fusion

        fused = cache is None and fusion.route("dropout_add")
        with _scopes.phase("attn.proj"):
            if cache is None:
                a = self.attn(self.ln_1(x))
                x = fusion.dropout_add(a, x, self.dropout.p, self.training) \
                    if fused else x + self.dropout(a)
            else:
                a, cache = self.attn(self.ln_1(x), cache=cache)
                x = x + self.dropout(a)
        with _scopes.phase("ffn"):
            m = self.mlp(self.ln_2(x))
            x = fusion.dropout_add(m, x, self.dropout.p, self.training) \
                if fused else x + self.dropout(m)
            x = shard_activation(x, ("dp", "sp", None))
        return x if cache is None else (x, cache)

    def forward(self, x, cache=None):
        if self._recompute and self.training and cache is None:
            # jax.checkpoint = the reference's fleet/recompute/recompute.py:124
            import jax

            params = [p for _, p in self.named_parameters()]

            is_moe = isinstance(self.mlp, GPTMoEMLP)

            def fn(xa, *pa):
                from ..incubate.nn.functional.flash_attention import (
                    _entering_recompute)

                saved = [p._data for p in params]
                for p, a in zip(params, pa):
                    p._data = a
                try:
                    with _entering_recompute():
                        out = self._body(Tensor(xa, stop_gradient=False))
                finally:
                    for p, a in zip(params, saved):
                        p._data = a
                if is_moe:
                    # thread the aux loss out of the checkpointed graph —
                    # the inner-trace Tensor on last_aux_loss must not leak
                    return out._data, self.mlp.last_aux_loss._data
                return out._data

            outs = run_op(jax.checkpoint(fn), [x] + params,
                          name="gpt_block_rc")
            if is_moe:
                out, aux = outs
                self.mlp.last_aux_loss = aux
                return out
            return outs
        return self._body(x, cache=cache)


class GPTModel(nn.Layer):
    """Embeddings + N blocks + final LN."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        init = nn.initializer.Normal(0.0, config.initializer_range)
        self.wte = nn.Embedding(config.vocab_size, config.hidden_size,
                                weight_attr=init)
        self.wpe = nn.Embedding(config.max_position_embeddings,
                                config.hidden_size, weight_attr=init)
        annotate_param(self.wte.weight, ("mp", None))
        annotate_param(self.wpe.weight, (None, None))
        self.drop = nn.Dropout(config.dropout)
        self.h = nn.LayerList([GPTBlock(config, layer_idx=i)
                               for i in range(config.num_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size, config.layer_norm_eps)

    def forward(self, input_ids, position_ids=None, caches=None):
        b, s = input_ids.shape[0], input_ids.shape[1]
        with _scopes.phase("embed"):
            if position_ids is None:
                past = caches[0][0].shape[1] if caches is not None else 0
                position_ids = Tensor(
                    jnp.arange(past, past + s, dtype=jnp.int32)[None, :]
                    + jnp.zeros((b, 1), dtype=jnp.int32))
            x = self.wte(input_ids) + self.wpe(position_ids)
            x = self.drop(x)
            x = shard_activation(x, ("dp", "sp", None))
        new_caches = [] if caches is not None else None
        for i, block in enumerate(self.h):
            if caches is not None:
                x, c = block(x, cache=caches[i])
                new_caches.append(c)
            else:
                x = block(x)
        with _scopes.phase("head"):
            x = self.ln_f(x)
        if caches is not None:
            return x, new_caches
        return x


class GPTForCausalLM(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     bias_attr=False)
            annotate_param(self.lm_head.weight, (None, "mp"))

    def forward(self, input_ids, position_ids=None, labels=None, caches=None):
        if caches is not None:
            x, new_caches = self.gpt(input_ids, position_ids, caches=caches)
        else:
            x = self.gpt(input_ids, position_ids)
        chunks = int(getattr(self.config, "lm_ce_chunks", 0) or 0)
        if labels is not None and chunks > 1 \
                and int(np.prod(x.shape[:-1])) % chunks == 0:
            with _scopes.phase("loss"):
                loss = self._chunked_lm_ce(x, labels, chunks)
        else:
            with _scopes.phase("head"):
                if self.lm_head is not None:
                    logits = self.lm_head(x)
                else:
                    logits = run_op(lambda a, w: jnp.matmul(a, w.T),
                                    [x, self.gpt.wte.weight],
                                    name="lm_head_tied")
                logits = shard_activation(logits, ("dp", "sp", "mp"))
            if labels is None:
                if caches is not None:
                    return logits, new_caches
                return logits
            with _scopes.phase("loss"):
                loss = GPTPretrainingCriterion()(logits, labels)
        if self.config.moe_num_experts:
            for blk in self.gpt.h:
                aux = getattr(blk.mlp, "last_aux_loss", None)
                if aux is not None:
                    loss = loss + aux * self.config.moe_aux_weight
        return loss

    def _chunked_lm_ce(self, x, labels, chunks, ignore_index=-100):
        """Fused lm_head + softmax-CE in token chunks: each chunk's
        [T/C, vocab] logits live only inside a jax.checkpoint scope
        (forward keeps per-chunk scalars; backward recomputes the chunk
        matmul). The TPU rendering of the reference's fused CE kernels
        (phi/kernels/fusion/) — the full logits tensor and its gradient
        never hit HBM."""
        import jax

        from .. import fusion

        tied = self.lm_head is None
        w = self.gpt.wte.weight if tied else self.lm_head.weight
        if fusion.route("lm_ce"):
            # shared chunked-epilogue path (fusion/chunked.py), also used
            # by the Llama head; mirrors F.cross_entropy op for op so the
            # loss is invariant to the chunk count
            return fusion.lm_head_chunked_ce(x, w, labels, chunks,
                                             transpose_weight=tied,
                                             ignore_index=ignore_index)
        lab = unwrap(as_tensor(labels)).reshape(-1)

        def fn(a, wa):
            h = a.shape[-1]
            t = math.prod(a.shape[:-1])
            xc = a.reshape(chunks, t // chunks, h)
            lc = lab.astype(jnp.int32).reshape(chunks, t // chunks)

            def chunk(args):
                xi, li = args
                logits = (xi @ (wa.T if tied else wa)).astype(jnp.float32)
                lse = jax.scipy.special.logsumexp(logits, axis=-1)
                valid = li != ignore_index
                safe = jnp.where(valid, li, 0)
                tgt = jnp.take_along_axis(
                    logits, safe[:, None], axis=-1)[:, 0]
                nll = jnp.where(valid, lse - tgt, 0.0)
                return nll.sum(), valid.sum()

            sums, counts = jax.lax.map(jax.checkpoint(chunk), (xc, lc))
            return sums.sum() / jnp.maximum(counts.sum(), 1).astype(
                jnp.float32)

        return run_op(fn, [x, w], name="fused_lm_ce")

    def generate(self, input_ids, max_new_tokens=32, temperature=0.0,
                 top_p=None, eos_token_id=None, weight_quant=None,
                 kv_cache_quant=None):
        """Fully-compiled autoregressive decoding (fused decode path,
        models/generation.py — the fused_multi_transformer/masked-MHA
        serving analog). Returns new token ids [b, max_new_tokens]."""
        from .generation import generate as _gen

        return _gen(self, input_ids, max_new_tokens=max_new_tokens,
                    temperature=temperature, top_p=top_p,
                    eos_token_id=eos_token_id, weight_quant=weight_quant,
                    kv_cache_quant=kv_cache_quant)

    def beam_search(self, input_ids, max_new_tokens=32, num_beams=4,
                    length_penalty=0.0, eos_token_id=None,
                    weight_quant=None, kv_cache_quant=None):
        """Compiled beam search over the fused decode path (gather_tree
        backtrace). Returns the best beam's ids [b, max_new_tokens]."""
        from .generation import beam_search as _beam

        return _beam(self, input_ids, max_new_tokens=max_new_tokens,
                     num_beams=num_beams, length_penalty=length_penalty,
                     eos_token_id=eos_token_id, weight_quant=weight_quant,
                     kv_cache_quant=kv_cache_quant)

    def decode_adapter(self):
        """Weight-extraction protocol for the model-generic fused decode
        engine (models/generation.py)."""
        from .generation import GPTDecodeAdapter

        return GPTDecodeAdapter(self)

    def init_caches(self, batch_size: int):
        from ..ops.creation import zeros

        cfg = self.config
        return [(zeros([batch_size, 0, cfg.num_heads, cfg.head_dim]),
                 zeros([batch_size, 0, cfg.num_heads, cfg.head_dim]))
                for _ in range(cfg.num_layers)]


class GPTPretrainingCriterion(nn.Layer):
    """Token-level cross entropy, mean over non-ignored positions. Labels
    must already be shifted (labels[t] = next token after input_ids[t]) —
    no shift happens here (reference analog: the GPT pretraining criterion
    in the Fleet tests, which also takes pre-shifted labels)."""

    def __init__(self, ignore_index: int = -100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, logits, labels):
        loss = F.cross_entropy(
            logits.reshape([-1, logits.shape[-1]]),
            labels.reshape([-1]),
            reduction="mean", ignore_index=self.ignore_index)
        return loss
