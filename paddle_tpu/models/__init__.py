"""Flagship model families (reference analogs: GPT-3/Llama configs used by
the reference's hybrid-parallel and semi-auto tests —
test/auto_parallel/hybrid_strategy/semi_auto_parallel_llama_model.py and the
PaddleNLP GPT models the Fleet pipeline tests exercise).

All models are built from ``paddle_tpu.nn`` layers and carry mesh-axis
sharding annotations (dp/mp/sp) consumed by the jit train-step builder, so
the same model runs single-chip eager, jit single-chip, and jit SPMD over a
multi-chip mesh.
"""
from .gpt import (  # noqa: F401
    GPTConfig,
    GPTForCausalLM,
    GPTModel,
    GPTPretrainingCriterion,
    gpt3_13B,
    gpt3_125M,
    gpt3_1p3B,
    gpt3_6p7B,
    gpt_tiny,
)
from .llama import (  # noqa: F401
    LlamaConfig,
    LlamaForCausalLM,
    LlamaModel,
    llama2_7B,
    llama_tiny,
)
from .ouro import (  # noqa: F401
    OuroConfig,
    OuroForCausalLM,
    OuroModel,
    ouro_2p6B,
    ouro_tiny,
)
from .xing4 import (  # noqa: F401
    Xing4Config,
    Xing4ForCausalLM,
    Xing4Model,
    xing4_29B_A4B,
    xing4_tiny,
)
from .sarvam import (  # noqa: F401
    SarvamForCausalLM,
    SarvamMLAConfig,
    SarvamModel,
    sarvam_tiny,
)
from .bert import (  # noqa: F401
    BertConfig,
    BertForPreTraining,
    BertForSequenceClassification,
    BertModel,
    BertPretrainingCriterion,
    bert_base,
    bert_large,
    bert_tiny,
)
from .generation import (  # noqa: F401
    beam_search,
    generate,
    speculative_generate,
)
