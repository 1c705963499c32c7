"""Reference training models (SURVEY.md §7.0: the model zoo lives downstream in the
reference; these are the in-repo baseline-config drivers)."""
from .gpt import (  # noqa: F401
    GPTConfig, GPTForCausalLM, GPTModel, gpt3_1p3b, gpt_350m, gpt_tiny,
)
from .llama import (  # noqa: F401
    LlamaConfig, LlamaForCausalLM, LlamaModel, llama2_7b, llama_tiny,
)
from .bert import (  # noqa: F401
    BertConfig, BertForMaskedLM, BertModel, bert_base, bert_mlm_mask,
    bert_tiny, masked_lm_loss,
)
from .dots3 import (  # noqa: F401
    Dots3Config, Dots3ForCausalLM, Dots3Model, dots3_tiny,
)
