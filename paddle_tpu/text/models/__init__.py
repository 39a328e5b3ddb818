from .bert import BertModel, BertForSequenceClassification  # noqa: F401
from .deepseek_v2 import (  # noqa: F401
    DeepseekV2Config, DeepseekV2ForCausalLM, DeepseekV2Model,
)
from .ernie import (  # noqa: F401
    ErnieConfig, ErnieForSequenceClassification, ErnieModel,
)
from .gpt import GPTForCausalLM, GPTModel  # noqa: F401
from .keye_vl2 import (  # noqa: F401
    KeyeVL2Config, KeyeVL2ForCausalLM, KeyeVL2Model,
)
from .kimi_linear import (  # noqa: F401
    KimiLinearConfig, KimiLinearForCausalLM, KimiLinearModel,
)
from .laguna import LagunaConfig, LagunaForCausalLM, LagunaModel  # noqa: F401
from .lfm2 import LFM2Config, LFM2ForCausalLM, LFM2Model  # noqa: F401

__all__ = ["BertModel", "BertForSequenceClassification", "GPTModel",
           "GPTForCausalLM", "ErnieConfig", "ErnieModel",
           "ErnieForSequenceClassification", "LFM2Config", "LFM2Model",
           "LFM2ForCausalLM", "KimiLinearConfig", "KimiLinearModel",
           "KimiLinearForCausalLM", "KeyeVL2Config", "KeyeVL2Model",
           "KeyeVL2ForCausalLM", "DeepseekV2Config", "DeepseekV2Model",
           "DeepseekV2ForCausalLM", "LagunaConfig", "LagunaModel",
           "LagunaForCausalLM"]
