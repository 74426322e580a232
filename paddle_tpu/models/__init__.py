"""Flagship NLP model zoo (the reference keeps these in fleet examples;
here they are first-class because they drive the distributed benches)."""
from .gpt import (  # noqa: F401
    GPTConfig, GPT, GPTForCausalLM, gpt_tiny, gpt_small, gpt_1p3b,
    gpt_moe_tiny)
from .widedeep import WideDeep, DeepFM  # noqa: F401
from .bert import (  # noqa: F401
    BertConfig, BertModel, BertForPretraining, bert_tiny, bert_base,
    bert_large)
from .ernie import (  # noqa: F401
    ErnieConfig, ErnieModel, ErnieForPretraining, ernie_base,
    ernie_tiny)
from .retention import (  # noqa: F401
    RetentionConfig, RetentionForCausalLM, retention_tiny)

__all__ = ['GPTConfig', 'GPT', 'GPTForCausalLM', 'gpt_tiny', 'gpt_small',
           'gpt_1p3b', 'gpt_moe_tiny', 'WideDeep', 'DeepFM', 'BertConfig', 'BertModel',
           'BertForPretraining', 'bert_tiny', 'bert_base', 'bert_large',
           'ErnieConfig', 'ErnieModel', 'ErnieForPretraining',
           'ernie_base', 'ernie_tiny', 'RetentionConfig',
           'RetentionForCausalLM', 'retention_tiny']
