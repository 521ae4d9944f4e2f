from .bert import (  # noqa: F401
    BertConfig,
    BertForPretraining,
    BertForSequenceClassification,
    BertModel,
    ErnieModel,
    bert_base,
    bert_large,
    bert_tiny,
    ernie_1_5b,
    ernie_3_0_medium,
)
from .falcon_h1 import (  # noqa: F401
    FalconH1Config,
    FalconH1ForCausalLM,
    FalconH1Model,
    falcon_h1_decode_fns,
    falcon_h1_tiny,
)
from .gpt import (  # noqa: F401
    GPT,
    GPTConfig,
    GPTForCausalLM,
    gpt2_medium,
    gpt2_small,
    gpt2_tiny,
)
from .kimi_linear import (  # noqa: F401
    KimiLinearConfig,
    KimiLinearForCausalLM,
    KimiLinearModel,
    kimi_linear_tiny,
)
from .pangu_ultra_moe import (  # noqa: F401
    PanguUltraMoEConfig,
    PanguUltraMoEForCausalLM,
    PanguUltraMoEModel,
    pangu_decode_fns,
    pangu_ultra_moe_tiny,
)
from .longcat_flash import (  # noqa: F401
    LongcatFlashConfig,
    LongcatFlashForCausalLM,
    LongcatFlashModel,
    longcat_decode_fns,
    longcat_flash_tiny,
)
