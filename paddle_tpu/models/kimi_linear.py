"""Kimi-Linear — a hybrid decoder: KDA (gated delta-rule linear attention)
layers beside MLA (latent attention) layers, a routed-expert FFN with a
shared expert after a leading dense layer, RMSNorm pre-norm blocks, no
position embedding, an untied head.  Served through the paged engine
(`inference.PagedGenerationServer(model, ...)`): the model is its weights
and the description `nn.decode` builds its programs from; the mathematics
lives in `nn/decode_blocks.py` and `ops/{kda,mla}.py`,
`parallel/moe.routed_expert_ffn`.

One chip of an expert-parallel deployment holds some layers and some of
each layer's experts: `held_layers` (the first n of the published stack)
and `held_experts` (first, count).  The router keeps its published width
and top-k; the layer computes its own experts' share of the routed sum.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax.numpy as jnp

from .. import nn
from ..nn import initializer as I


@dataclass
class KimiLinearConfig:
    """The published `config.json` keys (Kimi-Linear-48B-A3B-Instruct's
    values as defaults) plus what this chip holds."""
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 27
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 1
    num_experts: int = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    moe_renormalize: bool = True
    routed_scaling_factor: float = 2.446
    rms_norm_eps: float = 1e-5
    model_max_length: int = 1048576
    # linear_attn_config
    kda_layers: tuple = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                         19, 21, 22, 23, 25, 26)
    full_attn_layers: tuple = (4, 8, 12, 16, 20, 24, 27)
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    # not in the config; the released implementation's
    kda_gate_rank: int = 128        # low rank of the decay and output gates
    kda_chunk: int = 64             # positions a chunk of the prefill form
    # what this chip holds
    held_layers: int = None         # the first n layers (None: all)
    held_experts: tuple = None      # (first, count) (None: all)
    init_std: float = 0.02
    #: the serving engine's horizon (prompt + new tokens), as GPT2Config
    max_position: int = field(default=None)

    def __post_init__(self):
        if self.held_layers is None:
            self.held_layers = self.num_hidden_layers
        if self.held_experts is None:
            self.held_experts = (0, self.num_experts)
        self.held_experts = tuple(int(v) for v in self.held_experts)
        first, count = self.held_experts
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError(f"held_experts {self.held_experts} outside "
                             f"the {self.num_experts} experts")
        if not 1 <= self.held_layers <= self.num_hidden_layers:
            raise ValueError(f"held_layers {self.held_layers} outside the "
                             f"{self.num_hidden_layers} layers")
        if self.num_shared_experts != 1:
            raise ValueError("one shared expert is what is built")
        if self.max_position is None:
            self.max_position = self.model_max_length
        self.kda_layers = tuple(self.kda_layers)
        self.full_attn_layers = tuple(self.full_attn_layers)

    @property
    def num_layers(self):
        return self.held_layers

    def layer_kinds(self):
        """("kda" | "mla", "dense" | "experts") of each held layer, in
        order; the config numbers layers from 1."""
        out = []
        for i in range(1, self.held_layers + 1):
            mixer = "kda" if i in self.kda_layers else "mla"
            if mixer == "mla" and i not in self.full_attn_layers:
                raise ValueError(f"layer {i} is in neither kda_layers nor "
                                 f"full_attn_layers")
            out.append((mixer, "dense" if i <= self.first_k_dense_replace
                        else "experts"))
        return tuple(out)

    @classmethod
    def tiny(cls, **kw):
        """A CPU-test size with every mechanism: a dense KDA layer, then
        KDA, MLA, KDA with experts; 8 experts, top 2."""
        base = dict(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=4,
            num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, num_experts=8,
            num_experts_per_token=2, kda_layers=(1, 2, 4),
            full_attn_layers=(3,), kda_num_heads=4, kda_head_dim=16,
            kda_gate_rank=8, kda_chunk=8, model_max_length=4096)
        base.update(kw)
        return cls(**base)


class _W(nn.Layer):
    """One named weight."""

    def __init__(self, shape, init, dtype):
        super().__init__()
        self.weight = self.create_parameter(tuple(shape), dtype=dtype,
                                            default_initializer=init)


class _KDA(nn.Layer):
    def __init__(self, cfg, dt):
        super().__init__()
        e, h, d = cfg.hidden_size, cfg.kda_num_heads, cfg.kda_head_dim
        r, n = cfg.kda_gate_rank, I.Normal(0.0, cfg.init_std)
        self.qkv_proj = _W((e, 3 * h * d), n, dt)         # q | k | v
        # depthwise taps, oldest first; ~1/K each so the conv passes signal
        self.qkv_conv = _W((cfg.short_conv_kernel_size, 3 * h * d),
                           I.Normal(0.0, cfg.short_conv_kernel_size ** -0.5),
                           dt)
        self.f_down, self.f_up = _W((e, r), n, dt), _W((r, h * d), n, dt)
        self.g_down, self.g_up = _W((e, r), n, dt), _W((r, h * d), n, dt)
        self.b_proj = _W((e, h), n, dt)
        self.o_norm = _W((d,), I.Constant(1.0), dt)
        self.o_proj = _W((h * d, e), n, dt)
        # the decay's own parameters stay float32 (as released: A in
        # [1, 16], dt in [1e-3, 1e-1], both log-uniform)
        self.A_log = self.create_parameter(
            (h,), dtype="float32",
            default_initializer=I.Uniform(0.0, math.log(16.0)))
        self.dt_bias = self.create_parameter(
            (h * d,), dtype="float32",
            default_initializer=I.Uniform(math.log(1e-3), math.log(1e-1)))
        # dt_bias = softplus^-1(dt): softplus(dt_bias) is dt itself
        dtv = jnp.exp(self.dt_bias._value)
        self.dt_bias._value = dtv + jnp.log(-jnp.expm1(-dtv))


class _MLA(nn.Layer):
    def __init__(self, cfg, dt):
        super().__init__()
        e, h, n = cfg.hidden_size, cfg.num_attention_heads, \
            I.Normal(0.0, cfg.init_std)
        nope, pe, vd, lora = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                              cfg.v_head_dim, cfg.kv_lora_rank)
        self.q_proj = _W((e, h * (nope + pe)), n, dt)     # per head nope|pe
        self.kva_proj = _W((e, lora + pe), n, dt)         # c | k_pe
        self.kv_norm = _W((lora,), I.Constant(1.0), dt)
        self.kvb_proj = _W((lora, h * (nope + vd)), n, dt)  # per head k|v
        self.o_proj = _W((h * vd, e), n, dt)


class _SwiGLU(nn.Layer):
    def __init__(self, e, f, std, dt):
        super().__init__()
        n = I.Normal(0.0, std)
        self.gate_proj, self.up_proj = _W((e, f), n, dt), _W((e, f), n, dt)
        self.down_proj = _W((f, e), n, dt)


class _Experts(nn.Layer):
    """The SwiGLU weights of the experts held here, stacked."""

    def __init__(self, held, e, f, std, dt):
        super().__init__()
        n = I.Normal(0.0, std)
        self.gate = self.create_parameter((held, e, f), dtype=dt,
                                          default_initializer=n)
        self.up = self.create_parameter((held, e, f), dtype=dt,
                                        default_initializer=n)
        self.down = self.create_parameter((held, f, e), dtype=dt,
                                          default_initializer=n)


class _Router(nn.Layer):
    def __init__(self, cfg, dt):
        super().__init__()
        self.weight = self.create_parameter(
            (cfg.hidden_size, cfg.num_experts), dtype=dt,
            default_initializer=I.Normal(0.0, cfg.init_std))
        # e_score_correction_bias: float32, added for the choice alone
        self.bias = self.create_parameter(
            (cfg.num_experts,), dtype="float32",
            default_initializer=I.Constant(0.0))


class _MoE(nn.Layer):
    def __init__(self, cfg, dt):
        super().__init__()
        e, f = cfg.hidden_size, cfg.moe_intermediate_size
        self.router = _Router(cfg, dt)
        self.experts = _Experts(cfg.held_experts[1], e, f, cfg.init_std, dt)
        self.shared = _SwiGLU(e, f, cfg.init_std, dt)


class _Block(nn.Layer):
    def __init__(self, cfg, mixer, ffn, dt):
        super().__init__()
        one = I.Constant(1.0)
        self.norm_1 = _W((cfg.hidden_size,), one, dt)
        self.norm_2 = _W((cfg.hidden_size,), one, dt)
        if mixer == "kda":
            self.kda = _KDA(cfg, dt)
        else:
            self.mla = _MLA(cfg, dt)
        if ffn == "dense":
            self.mlp = _SwiGLU(cfg.hidden_size, cfg.intermediate_size,
                               cfg.init_std, dt)
        else:
            self.moe = _MoE(cfg, dt)


class KimiLinear(nn.Layer):
    """The weights (seeded by `paddle.seed`, built directly in `dtype`:
    a float32 copy of the published widths does not fit a chip) and the
    decoder description.  `functional_state()` gives the flat names
    `benchmark/reference/kimi_linear.py` lists."""

    def __init__(self, cfg: KimiLinearConfig = None, dtype="float32", **kw):
        super().__init__()
        cfg = cfg or KimiLinearConfig(**kw)
        self.cfg = cfg
        n = I.Normal(0.0, cfg.init_std)
        self.embed = _W((cfg.vocab_size, cfg.hidden_size), n, dtype)
        self.layers = nn.LayerList(
            [_Block(cfg, mixer, ffn, dtype)
             for mixer, ffn in cfg.layer_kinds()])
        self.norm_f = _W((cfg.hidden_size,), I.Constant(1.0), dtype)
        self.lm_head = _W((cfg.hidden_size, cfg.vocab_size), n, dtype)

    def decoder_description(self):
        """What `nn.decode.PagedDecoder` builds this model's programs
        from (`nn.decode_blocks.DecoderDescription`)."""
        from ..nn.decode_blocks import DecoderDescription, LayerDescription

        c = self.cfg
        return DecoderDescription(
            hidden=c.hidden_size, vocab=c.vocab_size, eps=c.rms_norm_eps,
            layers=tuple(LayerDescription(mixer, ffn)
                         for mixer, ffn in c.layer_kinds()),
            heads=c.num_attention_heads, nope_dim=c.qk_nope_head_dim,
            pe_dim=c.qk_rope_head_dim, v_dim=c.v_head_dim,
            lora=c.kv_lora_rank, kda_heads=c.kda_num_heads,
            kda_dim=c.kda_head_dim, conv=c.short_conv_kernel_size,
            kda_chunk=c.kda_chunk, experts=c.num_experts,
            held_first=c.held_experts[0], held=c.held_experts[1],
            top_k=c.num_experts_per_token,
            renormalize=c.moe_renormalize,
            scaling=c.routed_scaling_factor)

    def forward(self, *_a, **_k):
        raise NotImplementedError(
            "KimiLinear is served: PagedGenerationServer(model, ...) or "
            "nn.decode.PagedDecoder(model.decoder_description(), ...); the "
            "cache-free forward is benchmark/reference/kimi_linear.py")
