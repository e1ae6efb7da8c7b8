"""Model zoo registry.

Families are lazy-imported so importing the package costs nothing until a
server actually builds a model. Families map to BASELINE.json's configs:
mlp (iris parity), resnet50 (REST image path), bert (gRPC text path),
llm (generate() with dynamic batching). The ``llm`` family's block variants
(llama, afmoe, qwen3_next, joyai_llm_flash, evabyte) have a registry of
their own, ``family.FAMILIES``, which ``DecoderLM(block=...)`` looks up.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict

_FAMILIES: Dict[str, str] = {
    "mlp": "seldon_core_tpu.models.mlp.MLP",
    "resnet50": "seldon_core_tpu.models.resnet.ResNet50",
    "bert": "seldon_core_tpu.models.bert.BertClassifier",
    "llm": "seldon_core_tpu.models.llm.DecoderLM",
    "vit": "seldon_core_tpu.models.vit.ViTClassifier",
    "retrieval": "seldon_core_tpu.models.retrieval.RetrievalIndex",
    "reranker": "seldon_core_tpu.models.retrieval.Reranker",
}


def build(family: str, **config) -> Any:
    if family not in _FAMILIES:
        raise ValueError(f"unknown model family {family!r}; have {sorted(_FAMILIES)}")
    module_name, cls_name = _FAMILIES[family].rsplit(".", 1)
    cls = getattr(importlib.import_module(module_name), cls_name)
    return cls(**config)


def register(family: str, path: str) -> None:
    _FAMILIES[family] = path
