"""ZAYA1 — a decoder of compressed convolutional attention (CCA) and
top-1 routed experts: every layer is an attention sublayer (8 query heads
on 2 K/V heads in a compressed width, two causal convolutions on q and k,
a q-k mean, a value shifted by one token, rotary positions on half of a
head) and an expert sublayer (an MLP router with a softmax whose state is
carried from layer to layer, one of 16 SwiGLU experts, no shared expert),
each pre-norm with learned residual scaling; RMSNorm, a tied head.
Served through the paged engine (`inference.PagedGenerationServer(model,
...)`): the model is its weights and the description `nn.decode` builds
its programs from; the mathematics lives in `nn/decode_blocks.py`,
`ops/{cca,rotary}.py`, `ops/attention.py` and
`parallel/moe.dispatch_experts`.

One chip of a deployment holds some layers (`held_layers`, the first n of
the published stack; the rest are further pipeline stages) and some of
each layer's experts (`held_experts` = (first, count)).  The router keeps
its published width and top-1; the layer computes its own experts' share.
"""
from __future__ import annotations

from dataclasses import dataclass, field

from .. import nn
from ..nn import initializer as I
from .kimi_linear import _Experts, _W


@dataclass
class ZayaConfig:
    """The published `config.json` keys (ZAYA1-8B's values as defaults)
    plus what this chip holds."""
    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2              # taps of the depthwise convolution
    cca_time1: int = 2              # taps of the per-head convolution
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5000000.0   # rope_parameters.hybrid
    num_experts: int = 16
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    attention_bias: bool = False
    max_position_embeddings: int = 131072
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
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads share K/V heads in whole groups")
        if self.num_key_value_heads % 2:
            raise ValueError("the shifted value is half of the K/V heads")
        if self.rotary_dim % 2:
            raise ValueError("rotary channels are rotated in pairs")
        if not self.tie_word_embeddings or self.attention_bias:
            raise ValueError("a tied head and no attention bias are what "
                             "is built")
        if self.max_position is None:
            self.max_position = self.max_position_embeddings

    @property
    def num_layers(self):
        return self.held_layers

    @property
    def rotary_dim(self):
        return int(self.partial_rotary_factor * self.head_dim)

    @classmethod
    def tiny(cls, **kw):
        """A CPU-test size with every mechanism: 4 layers, 4 query heads
        on 2 K/V heads of 16 (rotary on 8), 4 experts of width 32."""
        base = dict(
            vocab_size=512, hidden_size=64, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            num_experts=4, moe_intermediate_size=32, router_hidden_size=16,
            max_position_embeddings=4096)
        base.update(kw)
        return cls(**base)


class _Conv(nn.Layer):
    """Taps (index j multiplies the input j positions back) and a bias."""

    def __init__(self, shape, channels, std, dt):
        super().__init__()
        self.weight = self.create_parameter(
            tuple(shape), dtype=dt, default_initializer=I.Normal(0.0, std))
        self.bias = self.create_parameter(
            (channels,), dtype=dt, default_initializer=I.Normal(0.0, 0.02))


class _Scaling(nn.Layer):
    """A sublayer's learned residual scaling: x <- (a_res * x + b_res) +
    (a_out * f(norm(x)) + b_out)."""

    def __init__(self, e, dt):
        super().__init__()
        for name, value in (("a_res", 1.0), ("b_res", 0.0),
                            ("a_out", 1.0), ("b_out", 0.0)):
            setattr(self, name, self.create_parameter(
                (e,), dtype=dt, default_initializer=I.Constant(value)))


class _CCA(nn.Layer):
    def __init__(self, cfg, dt):
        super().__init__()
        e, d = cfg.hidden_size, cfg.head_dim
        cq = cfg.num_attention_heads * d
        ck = cfg.num_key_value_heads * d
        n = I.Normal(0.0, cfg.init_std)
        self.q_proj, self.k_proj = _W((e, cq), n, dt), _W((e, ck), n, dt)
        # this token's half of the value, and the half the NEXT token takes
        self.v1_proj = _W((e, ck // 2), n, dt)
        self.v2_proj = _W((e, ck // 2), n, dt)
        # depthwise over the cq + ck channels; then one [D, D] matrix a
        # head and tap; both ~1/taps so that the convolutions pass signal
        self.conv0 = _Conv((cfg.cca_time0, cq + ck), cq + ck,
                           cfg.cca_time0 ** -0.5, dt)
        self.conv1 = _Conv((cfg.cca_time1, (cq + ck) // d, d, d), cq + ck,
                           (cfg.cca_time1 * d) ** -0.5, dt)
        # tau: k's learned temperature, one a K/V head, float32
        self.k_scale = self.create_parameter(
            (cfg.num_key_value_heads,), dtype="float32",
            default_initializer=I.Constant(0.0))
        self.o_proj = _W((cq, e), n, dt)


class _Router(nn.Layer):
    """r = W_down a + gamma * r_before; z = W_3 gelu(W_2 gelu(W_1
    RMSNorm(r))); s = softmax(z); the choice is argmax(s + bias)."""

    def __init__(self, cfg, dt):
        super().__init__()
        e, r = cfg.hidden_size, cfg.router_hidden_size
        self.down = _W((e, r), I.Normal(0.0, cfg.init_std), dt)
        # the MLP is small and decides the choice: float32, fan-in scaled
        # so that its logits spread (N(0, 0.02) would leave every expert
        # tied at 1/16)
        wide = I.Normal(0.0, r ** -0.5)
        self.norm = _W((r,), I.Constant(1.0), "float32")
        self.w1, self.w2 = _W((r, r), wide, "float32"), \
            _W((r, r), wide, "float32")
        self.w3 = _W((r, cfg.num_experts), wide, "float32")
        self.gamma = self.create_parameter(
            (1,), dtype="float32", default_initializer=I.Constant(0.5))
        self.bias = self.create_parameter(
            (cfg.num_experts,), dtype="float32",
            default_initializer=I.Constant(0.0))


class _MoE(nn.Layer):
    def __init__(self, cfg, dt):
        super().__init__()
        self.router = _Router(cfg, dt)
        self.experts = _Experts(cfg.held_experts[1], cfg.hidden_size,
                                cfg.moe_intermediate_size, cfg.init_std, dt)


class _Block(nn.Layer):
    def __init__(self, cfg, dt):
        super().__init__()
        e, one = cfg.hidden_size, I.Constant(1.0)
        self.norm_1, self.norm_2 = _W((e,), one, dt), _W((e,), one, dt)
        self.res_1, self.res_2 = _Scaling(e, dt), _Scaling(e, dt)
        self.cca = _CCA(cfg, dt)
        self.moe = _MoE(cfg, dt)


class Zaya(nn.Layer):
    """The weights (seeded by `paddle.seed`, built directly in `dtype`, one
    parameter at a time: a float32 copy of the published widths does not
    fit a chip) and the decoder description.  `functional_state()` gives
    the flat names `benchmark/reference/zaya.py` lists."""

    def __init__(self, cfg: ZayaConfig = None, dtype="float32", **kw):
        super().__init__()
        cfg = cfg or ZayaConfig(**kw)
        self.cfg = cfg
        self.embed = _W((cfg.vocab_size, cfg.hidden_size),
                        I.Normal(0.0, cfg.init_std), dtype)
        self.layers = nn.LayerList(
            [_Block(cfg, dtype) for _ in range(cfg.held_layers)])
        self.norm_f = _W((cfg.hidden_size,), I.Constant(1.0), dtype)

    def decoder_description(self):
        """What `nn.decode.PagedDecoder` builds this model's programs
        from (`nn.decode_blocks.DecoderDescription`)."""
        from ..nn.decode_blocks import (CCADescription, DecoderDescription,
                                        LayerDescription)

        c = self.cfg
        return DecoderDescription(
            hidden=c.hidden_size, vocab=c.vocab_size, eps=c.rms_norm_eps,
            layers=(LayerDescription("cca", "mlp_routed"),) * c.held_layers,
            cca=CCADescription(
                heads=c.num_attention_heads,
                kv_heads=c.num_key_value_heads, head_dim=c.head_dim,
                rotary_dim=c.rotary_dim, theta=float(c.rope_theta),
                time0=c.cca_time0, time1=c.cca_time1),
            router_width=c.router_hidden_size,
            experts=c.num_experts, held_first=c.held_experts[0],
            held=c.held_experts[1], top_k=c.num_experts_per_tok,
            renormalize=False, scaling=1.0, tied_head=True,
            residual_scaling=True)

    def forward(self, *_a, **_k):
        raise NotImplementedError(
            "Zaya is served: PagedGenerationServer(model, ...) or "
            "nn.decode.PagedDecoder(model.decoder_description(), ...); the "
            "cache-free forward is benchmark/reference/zaya.py")
