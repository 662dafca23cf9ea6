"""The gpt2 family: what a cell needs from the program to train or serve a
GPT-2 configuration, through the entry points a user calls
(`models.gpt2.build_train_step`, `models.gpt2.GPT2`), and the plain
reference it is checked against.  Keys of the configuration file are the
published config.json's."""
from __future__ import annotations

OBJECTIVE = "lm"        # bench_data.SeededSequences objective
CAUSAL = True
KERNELS_PER_LAYER_TRAIN = 3   # flash forward, delta, fused backward
KERNELS_PER_LAYER_SERVE = 1   # one paged stream kernel per attention program


def program_config(cfg):
    from paddle_tpu.models.gpt2 import GPT2Config

    return GPT2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        max_position=cfg["n_positions"], intermediate_size=cfg["n_inner"],
        dropout=cfg["deployment"]["dropout"],
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        tie_embeddings=cfg["tie_word_embeddings"])


def shape(cfg):
    """The sizes flops.py needs, under its own names."""
    return {"layers": cfg["n_layer"], "heads": cfg["n_head"],
            "hidden": cfg["n_embd"],
            "head_dim": cfg["n_embd"] // cfg["n_head"],
            "vocab": cfg["vocab_size"], "positions": cfg["n_positions"]}


def train_program(cfg):
    """(loss_fn(params, batch, key), init_params()) — the program's own."""
    from paddle_tpu.models.gpt2 import build_train_step

    loss_fn, init_params, _model = build_train_step(
        program_config(cfg), remat=cfg["deployment"]["train"]["remat"])
    return loss_fn, init_params


def served_model(cfg, dtype):
    from paddle_tpu.models.gpt2 import GPT2

    model = GPT2(program_config(cfg))
    model.eval()
    if dtype != "float32":
        model.to(dtype=dtype)
    return model


def reference_loss(cfg):
    from reference import gpt2 as ref

    return lambda params, batch: ref.loss(params, batch, cfg["n_layer"],
                                          cfg["n_head"])


def reference_logits(cfg):
    from reference import gpt2 as ref

    return lambda params, ids: ref.logits(params, ids, cfg["n_layer"],
                                          cfg["n_head"])
