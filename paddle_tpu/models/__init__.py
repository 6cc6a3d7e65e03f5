"""paddle_tpu.models — flagship model family.

The reference ships transformers through python/paddle/nn/layer/
transformer.py plus example configs in its test suite (dist_transformer.py,
ERNIE/BERT in downstream repos).  Here the flagship models are built
TPU-first: stacked-parameter decoder trunks driven by lax.scan (one compile
regardless of depth), remat per layer, DistAttrs for dp/mp/pp/sp hybrid
sharding, flash/ring attention.
"""
from paddle_tpu.models.gpt import (  # noqa: F401
    GPT, GPTConfig, gpt_loss, gpt2_small, gpt2_medium, gpt2_345m, gpt_tiny)
from paddle_tpu.models.bert import (  # noqa: F401
    Bert, BertConfig, bert_base, bert_tiny, bert_pretrain_loss, Ernie,
    ErnieConfig)
from paddle_tpu.models.nemotron_h import (  # noqa: F401
    NemotronH, NemotronHConfig, nemotron_h_loss, nemotron_h_tiny,
    routing_load)
from paddle_tpu.models.bailing_hybrid import (  # noqa: F401
    BailingHybrid, BailingHybridConfig, bailing_hybrid_loss,
    bailing_hybrid_tiny)
from paddle_tpu.models.rank import WideDeep, DeepFM, WideDeepHost  # noqa: F401
