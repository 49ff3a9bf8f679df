"""Model zoo (LLM families). Vision models live in paddle_tpu.vision.models."""

from .llama import (LlamaConfig, LlamaModel, LlamaForCausalLM, llama_tiny,  # noqa: F401
                    llama_7b, llama_13b)
from .gpt import GPTConfig, GPTModel, GPTForCausalLM, gpt_tiny, gpt3_1p3b  # noqa: F401
from .bert import (BertConfig, BertModel, BertForPretraining,  # noqa: F401
                   BertForSequenceClassification, bert_tiny, bert_base)
from .granite_moe_hybrid import (GraniteMoeHybridConfig,  # noqa: F401
                                 GraniteMoeHybridModel,
                                 GraniteMoeHybridForCausalLM,
                                 granite_hybrid_tiny)
from .brumby import (BrumbyConfig, BrumbyModel, BrumbyForCausalLM,  # noqa: F401
                     brumby_tiny)
from .mellum import (MellumConfig, MellumModel, MellumForCausalLM,  # noqa: F401
                     mellum_tiny)
from .phi4flash import (Phi4FlashConfig, Phi4FlashModel,  # noqa: F401
                        Phi4FlashForCausalLM, phi4flash_tiny)
