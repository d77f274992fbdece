"""paddle_tpu.models — flagship model families.

Reference analog: the model zoo the reference ecosystem trains (GPT via
fleet hybrid-parallel is the north-star config in BASELINE.md; vision
models live in paddle_tpu.vision.models mirroring python/paddle/vision/models/).
"""
from .gpt import (
    GPTConfig,
    GPTModel,
    GPTForCausalLM,
    GPTPretrainingCriterion,
    gpt_test_config,
    gpt2_124m_config,
    gpt3_1p3b_config,
    gpt3_6p7b_config,
)
from .afmoe import AfmoeConfig, AfmoeForCausalLM, afmoe_test_config
from .lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM, lfm2_test_config
from .mistral4 import (Mistral4Config, Mistral4ForCausalLM,
                       mistral4_test_config)
from .brumby import BrumbyConfig, BrumbyForCausalLM, brumby_test_config
from .keye import KeyeConfig, KeyeForCausalLM, keye_test_config
from .falcon_h1 import (FalconH1Config, FalconH1ForCausalLM,
                        falcon_h1_test_config)
from .serving_form import (LatentSpec, LayerSpec, ServingForm, SparseSpec,
                           StateSpec)
from .bert import BertConfig, BertModel, BertForSequenceClassification, bert_base_config

__all__ = [
    "GPTConfig", "GPTModel", "GPTForCausalLM", "GPTPretrainingCriterion",
    "gpt_test_config", "gpt2_124m_config", "gpt3_1p3b_config",
    "gpt3_6p7b_config",
    "AfmoeConfig", "AfmoeForCausalLM", "afmoe_test_config",
    "Lfm2MoeConfig", "Lfm2MoeForCausalLM", "lfm2_test_config",
    "Mistral4Config", "Mistral4ForCausalLM", "mistral4_test_config",
    "BrumbyConfig", "BrumbyForCausalLM", "brumby_test_config",
    "KeyeConfig", "KeyeForCausalLM", "keye_test_config",
    "FalconH1Config", "FalconH1ForCausalLM", "falcon_h1_test_config",
    "LatentSpec", "LayerSpec", "ServingForm", "SparseSpec", "StateSpec",
    "BertConfig", "BertModel", "BertForSequenceClassification",
    "bert_base_config",
]
