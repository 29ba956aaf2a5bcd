"""Language-model families for the baseline configs (GPT-3, BERT, LLaMA).

The reference keeps its NLP zoo in PaddleNLP; the baseline workloads
(BASELINE.json configs: BERT-base DP+AMP, GPT-3 1.3B TP+PP hybrid,
LLaMA-7B ZeRO-3) need these in-framework, built on paddle_tpu.nn and the
TP/SP parallel layers.
"""
from .gpt import (GPTConfig, GPTModel, GPTForPretraining,  # noqa: F401
                  GPTPretrainingCriterion, gpt3_125m, gpt3_1p3b, gpt3_tiny)
from .bert import (BertConfig, BertModel, BertForPretraining,  # noqa: F401
                   bert_base, bert_tiny)
from .llama import (LlamaConfig, LlamaModel, LlamaForCausalLM,  # noqa: F401
                    llama_7b, llama_tiny)
from .gpt_moe import (GPTMoEConfig, GPTMoEModel,  # noqa: F401
                      GPTMoEForPretraining, GPTMoEPretrainingCriterion,
                      gpt_moe_tiny, gpt_moe_small)
from .mla_moe import (MLAMoEConfig, MLAMoEModel,  # noqa: F401
                      MLAMoEForCausalLM, mla_moe_tiny)
from .generation import generate  # noqa: F401
