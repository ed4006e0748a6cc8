from .llama import (  # noqa: F401
    LlamaConfig,
    LlamaDecoderLayer,
    LlamaForCausalLM,
    LlamaModel,
    llama_7b,
    llama_tiny,
)
from .mla_moe import (  # noqa: F401
    MlaMoeConfig,
    MlaMoeForCausalLM,
    MlaMoeModel,
    mla_moe_tiny,
)
from .window_moe import (  # noqa: F401
    WindowMoeConfig,
    WindowMoeForCausalLM,
    WindowMoeModel,
    window_moe_tiny,
)
from .cca_moe import (  # noqa: F401
    CcaMoeConfig,
    CcaMoeForCausalLM,
    CcaMoeModel,
    cca_moe_tiny,
)
from .bert import (  # noqa: F401
    BertConfig,
    BertForMaskedLM,
    BertForSequenceClassification,
    BertModel,
    ErnieConfig,
    ErnieForSequenceClassification,
    ErnieModel,
    bert_tiny,
)
from .gpt import (  # noqa: F401
    GPTConfig,
    GPTForCausalLM,
    GPTModel,
    gpt_tiny,
    shard_gpt,
)
