"""apex_tpu.models — model zoo backing the BASELINE configs.

The reference ships no models (it accelerates torchvision/Megatron
models); these TPU-first implementations exist so every BASELINE config
trains end-to-end inside this framework.
"""

from apex_tpu.models.resnet import (BasicBlock, Bottleneck, ResNet,
                                    resnet18, resnet34, resnet50,
                                    resnet101, resnet152)
from apex_tpu.models.gpt import GPTLayer, GPTModel, GPTStage
from apex_tpu.models.bert import (BertLayer, BertModel, bert_base,
                                  bert_large)
from apex_tpu.models.looped import (LoopedDecoder, LoopedDecoderLayer,
                                    LoopedPass)
from apex_tpu.models.sparse_moe import (SparseMoEDecoder,
                                        SparseMoEDecoderLayer)

__all__ = [
    "BasicBlock", "Bottleneck", "ResNet",
    "resnet18", "resnet34", "resnet50", "resnet101", "resnet152",
    "GPTLayer", "GPTModel", "GPTStage",
    "BertLayer", "BertModel", "bert_base", "bert_large",
    "LoopedDecoder", "LoopedDecoderLayer", "LoopedPass",
    "SparseMoEDecoder", "SparseMoEDecoderLayer",
]
