"""paddle_tpu.models — flagship model families (PaddleNLP/PaddleClas parity).

The reference ships its model zoo out-of-tree (PaddleNLP: ERNIE/Llama,
PaddleClas: ResNet — see BASELINE.json configs); this package provides the
TPU-native implementations the benchmarks and the graft entry run: a
Llama-family causal LM (GQA + RoPE + SwiGLU + RMSNorm, flash/ring attention)
and a BERT/ERNIE-style encoder.  Vision models live in paddle_tpu.vision.
"""
from paddle_tpu.models.llama import (  # noqa: F401
    LlamaConfig,
    LlamaForCausalLM,
    LlamaModel,
    llama_shardings,
    shard_llama,
)
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM, GPTModel  # noqa: F401
from paddle_tpu.models.bert import (  # noqa: F401
    BertConfig, BertForMaskedLM, BertForSequenceClassification, BertModel,
    ErnieConfig, ErnieForMaskedLM, ErnieForSequenceClassification, ErnieModel,
)
from paddle_tpu.models.moe_llm import MoEConfig, MoEForCausalLM  # noqa: F401
from paddle_tpu.models.falcon_h1 import (  # noqa: F401
    FalconH1Config, FalconH1ForCausalLM,
)
from paddle_tpu.models.glm4_moe_lite import (  # noqa: F401
    Glm4MoeLiteConfig, Glm4MoeLiteForCausalLM,
)
