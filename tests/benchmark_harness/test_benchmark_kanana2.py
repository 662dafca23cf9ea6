"""The kanana2 family and its cell's configuration, on the CPU: the sizes
`family.shape` hands the `.serve` readers, the latent and expert counts of
flops_kimi_linear.py at the cell's shape, the kernels each program must
hold, and the configuration file against the published config.json."""
import json
import os

from bench_paths import BENCH
import flops_kimi_linear as fk
from run import load_plugin

with open(os.path.join(BENCH, "configs", "kanana2_30b_l6.json")) as _f:
    CFG = json.load(_f)

# kanana-2-30b-a3b-instruct-2601's config.json, key for key
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 48,
    "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 128256}


def test_the_file_holds_every_published_key_and_cuts_depth_alone():
    assert {k: v for k, v in CFG["published"].items() if k != "what"} \
        == PUBLISHED
    assert {k: CFG[k] for k in PUBLISHED} == PUBLISHED
    assert CFG["reduced"] == ["layers"] and CFG["layers"] == 6
    assert len(CFG["source"]) <= 200


def test_the_parameters_are_the_layers_held_whole():
    e, h, f, v = 2048, 32, 768, 128256
    mla = e * h * 192 + e * 576 + 512 + 512 * h * 256 + h * 128 * e
    expert_layer = mla + e * 128 + 3 * e * 2 * f + 128 * 3 * e * f + 2 * e
    dense_layer = mla + 3 * e * 6144 + 2 * e
    assert (mla, expert_layer, dense_layer) == (26_345_984, 640_029_184,
                                                64_098_816)
    held = dense_layer + 5 * expert_layer + 2 * v * e + e
    assert held == CFG["assumed"]["parameters"] == 3_789_583_360
    # the latent pool: 6 layers of 640-wide bf16 rows
    assert 6 * 640 * 2 * 5120 * 128 == 5_033_164_800


def test_shape_and_kernels():
    family = load_plugin("families", "kanana2")
    assert family.shape(CFG) == {
        "layers": 6, "hidden": 2048, "vocab": 128256, "heads": 32,
        "mla_layers": 6, "expert_layers": 5, "latent": 576, "lora": 512,
        "expert_width": 768, "held_experts": 128, "top_k": 6}
    assert family.serve_kernels(CFG) == {
        "decode_step": {"mla_decode": 6, "moe_gmm": 5},
        "packed_prefill": {"moe_gmm": 5}}


def test_the_latent_and_expert_counts_at_the_cells_shape():
    sh = load_plugin("families", "kanana2").shape(CFG)
    # a decode step at 64 rows of ~6,100 positions: every row's latents
    # (576 of the 640-wide row used) once for all heads, in 6 layers
    ctx = 64 * 6100
    assert fk.mla_decode_bytes(ctx, sh["mla_layers"], sh["latent"]) \
        == 2_698_444_800
    assert fk.mla_decode_flops(ctx, sh["mla_layers"], sh["heads"],
                               sh["latent"], sh["lora"]) \
        == 2 * ctx * 6 * 32 * 1088
    # an expert is 3 x 2,048 x 768 bf16; 122 of 128 touched in 5 layers by
    # 384 picks a layer
    assert fk.expert_weight_bytes(sh["hidden"], sh["expert_width"]) \
        == 9_437_184
    assert fk.moe_gmm_bytes(5 * 122, 5 * 384, sh["hidden"],
                            sh["expert_width"]) \
        == 610 * 9_437_184 + 2 * 1920 * 2048 * 2
    assert fk.moe_gmm_flops(1920, 2048, 768) == 6 * 1920 * 2048 * 768
