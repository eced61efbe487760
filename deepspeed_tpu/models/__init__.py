"""Model zoo — TPU-native model families (the reference has none in-tree;
its model tests drive an external Megatron GPT-2, SURVEY.md §1)."""

from .bert import Bert, BertConfig, bert_config, BERT_SIZES
from .cohere2_moe import Cohere2Moe, Cohere2MoeConfig
from .deepseek_v2 import DeepSeekV2, DeepSeekV2Config
from .evabyte import EvaByte, EvaByteConfig
from .granite_hybrid import GraniteHybrid, GraniteHybridConfig
from .gpt import GPT, GPTConfig, gpt2_config, GPT2_SIZES
from .layer_spec import LayerSpec
from .gpt_pipe import gpt_pipeline_module
from .generation import generate
from .hf import (bert_config_from_hf, gpt2_config_from_hf,
                 load_hf_bert, load_hf_gpt2)

__all__ = ["GPT", "GPTConfig", "gpt2_config", "GPT2_SIZES",
           "gpt_pipeline_module",
           "Bert", "BertConfig", "bert_config", "BERT_SIZES",
           "EvaByte", "EvaByteConfig", "DeepSeekV2", "DeepSeekV2Config",
           "Cohere2Moe", "Cohere2MoeConfig",
           "GraniteHybrid", "GraniteHybridConfig",
           "LayerSpec",
           "load_hf_gpt2", "gpt2_config_from_hf",
           "load_hf_bert", "bert_config_from_hf", "generate"]
