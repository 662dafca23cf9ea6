"""NLP model zoo (BERT/ERNIE/GPT-2/Transformer) — the BASELINE.json configs.

Reference models: ERNIE/BERT-large pretraining + GPT-2 with fused attention
(BASELINE.json configs; fluid transformer ops). These are the flagship models
for the benchmark's cells (`benchmark/`) and __graft_entry__.py.
"""
from __future__ import annotations

from .bert import Bert, BertConfig, Ernie, ErnieConfig  # noqa: F401
from .gpt2 import GPT2, GPT2Config  # noqa: F401
from .transformer import TransformerConfig, TransformerModel  # noqa: F401
