"""The kanana2 family: what a serving cell needs from the program to serve a
Kanana-2 configuration through the entry points a user calls
(`models.kanana2.Kanana2`, `inference.PagedGenerationServer`), and the
plain reference it is checked against.  Keys of the configuration file are
the published config.json's; `layers` is what this chip holds (`reduced`),
`assumed_sizes` what the config is silent on."""
from __future__ import annotations

import importlib.util
import os
import sys

# imported before anything is built: a checkout that cannot serve this
# family fails here, at once, as run.py's own failures do (one line on
# standard error, exit code 2, no result line)
try:
    from paddle_tpu.models.kanana2 import Kanana2, Kanana2Config
except ImportError as e:
    print(f"benchmark: FAILED: this checkout cannot serve the kanana2 "
          f"family: {e}", file=sys.stderr, flush=True)
    raise SystemExit(2) from None


def _kimi_family():
    """families/kimi_linear.py, whose `balance_routers` works on any
    description with "experts" layers."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "kimi_linear.py")
    spec = importlib.util.spec_from_file_location("bench_families_kimi_linear",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def program_config(cfg):
    return Kanana2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        n_routed_experts=cfg["n_routed_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"], norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        scoring_func=cfg["scoring_func"], rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"], rope_interleave=cfg["rope_interleave"],
        rope_scaling=cfg["rope_scaling"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        attention_bias=cfg["attention_bias"],
        max_position_embeddings=cfg["max_position_embeddings"],
        mla_tile=cfg["assumed_sizes"]["mla_tile"], held_layers=cfg["layers"])


def serve_kernels(cfg):
    """Pallas kernels a program holds, by program and kernel name: every
    layer's decode step walks its latents with `mla_decode`, every expert
    layer runs one `moe_gmm` in both programs.  The latent prefill,
    rotary, the norms, the SwiGLUs and the head are XLA."""
    kinds = program_config(cfg).layer_kinds()
    moe = sum(1 for _m, f in kinds if f == "experts")
    return {"decode_step": {"mla_decode": len(kinds), "moe_gmm": moe},
            "packed_prefill": {"moe_gmm": moe}}


def shape(cfg):
    """The sizes flops_kimi_linear.py and the `.serve` metric readers
    need, under their names."""
    c = program_config(cfg)
    kinds = c.layer_kinds()
    return {
        "layers": c.held_layers, "hidden": c.hidden_size,
        "vocab": c.vocab_size, "heads": c.num_attention_heads,
        "mla_layers": len(kinds),
        "expert_layers": sum(1 for _m, f in kinds if f == "experts"),
        "latent": c.kv_lora_rank + c.qk_rope_head_dim,
        "lora": c.kv_lora_rank, "expert_width": c.moe_intermediate_size,
        "held_experts": c.n_routed_experts,
        "top_k": c.num_experts_per_tok,
    }


def served_model(cfg, dtype):
    """The model with its weights from `paddle.seed` and its routers'
    correction biases balanced on seeded random tokens (`assumed.weights`
    of the configuration file says why); `model.router_balance` keeps the
    experts' largest load over the mean, before and after."""
    model = Kanana2(program_config(cfg), dtype=dtype)
    model.eval()
    model.router_balance = _kimi_family().balance_routers(
        model, cfg["assumed_sizes"]["balance_tokens"])
    return model


def serving_path(_cfg):
    """Which form the decode-side ops take on this backend: the platform
    alone chooses (`ops.attention._on_tpu`), nothing falls back."""
    from paddle_tpu.ops.attention import _on_tpu

    return "pallas" if _on_tpu() else "xla"


def reference(cfg):
    """(arch, hidden(params, ids, **kw) -> (x, found), head(params,
    rows) -> logits, query block) of benchmark/reference/kanana2.py for
    this cut."""
    from reference import kanana2 as ref

    a = ref.arch(cfg)
    return (a, lambda params, ids, **kw: ref.hidden(params, ids, a, **kw),
            lambda params, rows: ref.head(params, rows, a),
            ref.ASSUMED["query_block"])
