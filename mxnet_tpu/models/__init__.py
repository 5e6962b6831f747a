"""Flagship model implementations (BERT, Transformer NMT, GPT-style LM,
the `afmoe` window/full-attention expert decoder).

These are the benchmark/workload-parity models named in BASELINE.json's
configs; vision classification models live in `gluon.model_zoo.vision`.
"""
from . import bert  # noqa: F401
from .bert import BertModel, BertForPretraining, bert_base, bert_large  # noqa: F401
from . import gpt  # noqa: F401
from .gpt import GPTConfig, GPTModel, GPTForCausalLM, gpt_small, gpt_medium  # noqa: F401
from . import afmoe  # noqa: F401
from .afmoe import AfmoeConfig, AfmoeForCausalLM  # noqa: F401
from . import transformer  # noqa: F401
from .transformer import (TransformerConfig, TransformerEncoder,  # noqa: F401
                          TransformerDecoder, TransformerNMT,
                          transformer_base)
