from .transformer import (
    BertConfig,
    LlamaConfig,
    bert_forward,
    bert_loss,
    bert_shard_rules,
    draft_config,
    draft_params,
    init_bert,
    init_llama,
    llama_forward,
    llama_loss,
    llama_shard_rules,
)
from .cohere2_moe import (
    Cohere2MoeConfig,
    cohere2_moe_forward,
    init_cohere2_moe,
)
from .mellum import (
    MellumConfig,
    init_mellum,
    mellum_forward,
)
from .lfm2 import (
    Lfm2Config,
    init_lfm2,
    lfm2_forward,
)
from .resnet import (
    ResNetConfig,
    init_resnet,
    resnet_forward,
    resnet_loss,
    resnet_shard_rules,
)
from .convert import (
    bert_params_from_hf,
    llama_params_from_hf,
    t5_params_from_hf,
)
from .t5 import (
    T5Config,
    init_t5,
    t5_decode,
    t5_encode,
    t5_forward,
    t5_greedy_generate,
    t5_loss,
    t5_shard_rules,
)
