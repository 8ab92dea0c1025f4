"""Model zoo (reference: python/paddle/vision/models + the GPT/BERT configs
of BASELINE.json; vision models live in paddle_tpu.vision.models)."""
from .gpt import (GPTConfig, GPTModel, GPTForCausalLM,  # noqa: F401
                  gpt_tiny, gpt_125m, gpt_350m, gpt_1p3b, gpt_6p7b)
from .gpt_pipeline import GPTPipeline  # noqa: F401
from .deepseek_v2 import (DeepseekV2Config,  # noqa: F401
                          DeepseekV2ForCausalLM, deepseek_v2_tiny)
from .glm5 import Glm5Config, Glm5ForCausalLM, glm5_tiny  # noqa: F401
from .mimo_v2 import (MimoV2Config, MimoV2ForCausalLM,  # noqa: F401
                      mimo_v2_tiny)
from .bert import (BertConfig, BertModel, BertForPretraining,  # noqa: F401
                   BertForSequenceClassification, bert_tiny,
                   bert_base, bert_large)

__all__ = ["BertConfig", "BertModel", "BertForPretraining",
           "BertForSequenceClassification", "bert_tiny", "bert_base",
           "bert_large",
           "DeepseekV2Config", "DeepseekV2ForCausalLM", "deepseek_v2_tiny",
           "Glm5Config", "Glm5ForCausalLM", "glm5_tiny",
           "MimoV2Config", "MimoV2ForCausalLM", "mimo_v2_tiny",
           "GPTConfig", "GPTModel", "GPTForCausalLM", "GPTPipeline", "gpt_tiny",
           "gpt_125m", "gpt_350m", "gpt_1p3b", "gpt_6p7b"]
