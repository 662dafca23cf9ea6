"""The bert family: masked-language-model pretraining of a BERT
configuration through `models.bert.build_train_step`, and its plain
reference.  Keys of the configuration file are the published
config.json's.  BERT does not serve."""
from __future__ import annotations

OBJECTIVE = "mlm"
CAUSAL = False
KERNELS_PER_LAYER_TRAIN = 3   # flash forward, delta, fused backward


def program_config(cfg):
    from paddle_tpu.models.bert import BertConfig

    return BertConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        dropout=cfg["deployment"]["dropout"],
        layer_norm_epsilon=cfg["layer_norm_eps"])


def shape(cfg):
    return {"layers": cfg["num_hidden_layers"],
            "heads": cfg["num_attention_heads"],
            "hidden": cfg["hidden_size"],
            "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
            "vocab": cfg["vocab_size"],
            "positions": cfg["max_position_embeddings"]}


def train_program(cfg):
    from paddle_tpu.models.bert import build_train_step

    loss_fn, init_params, _model = build_train_step(
        program_config(cfg), remat=cfg["deployment"]["train"]["remat"])
    return loss_fn, init_params


def reference_loss(cfg):
    from reference import bert as ref

    return lambda params, batch: ref.loss(
        params, batch, cfg["num_hidden_layers"], cfg["num_attention_heads"])
