"""Brumby — Qwen3's dense decoder block with the softmax attention replaced
by gated power retention of degree 2: every layer is a mixer (40 query
heads on 8 K/V heads of 128, an RMSNorm of every q and k head, rotary
positions on the whole head, the log of a sigmoid gate a K/V head, and the
retention itself: weights (q . k / sqrt(d))^2, normalised by their sum, no
softmax) and a SwiGLU, each pre-norm; RMSNorm, an untied head, no bias.
Served through the paged engine (`inference.PagedGenerationServer(model,
...)`) with NO paged pool: a sequence is a slot of float32 state in the
store, of constant size whatever its length.  The model is its weights
and the description `nn.decode` builds its programs from; the mathematics
lives in `nn/decode_blocks.py`, `ops/power_retention.py`,
`ops/pallas/power_decode.py` and `ops/rotary.py`.

One chip of a deployment holds some layers (`held_layers`, the first n of
the published stack; the rest are further pipeline stages).
"""
from __future__ import annotations

from dataclasses import dataclass, field

from .. import nn
from ..nn import initializer as I
from .kimi_linear import _W


# Random weights have no direction that a gate without a bias could hold
# open: sigmoid(W a) is 1/2 on average and a state would forget in two
# tokens.  The embedding's rows share a small mean (EMBED_MEAN_RATIO of
# their standard deviation, on every channel) and the gate's columns a mean
# that reads it, so that the first layer's gate logit on an embedding row is
# GATE_LOGIT (its state remembers thousands of positions, as a trained
# gate's does); the layers after it see the sublayers' outputs and gate
# near 1/2
EMBED_MEAN_RATIO, GATE_LOGIT = 0.25, 9.0


@dataclass
class BrumbyConfig:
    """The published `config.json` keys (Brumby-14B-Base's values as
    defaults: Qwen3-14B's, key for key) plus what the config is silent on
    and what this chip holds."""
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    max_position_embeddings: int = 32768
    # the retention: the config has no key for it
    power_tile: int = 32            # channels a tile of the feature map
    power_chunk: int = 512          # positions a prefill chunk
    # what this chip holds
    held_layers: int = None         # the first n layers (None: all)
    init_std: float = 0.02
    #: the serving engine's horizon (prompt + new tokens), as GPT2Config
    max_position: int = field(default=None)

    def __post_init__(self):
        if self.held_layers is None:
            self.held_layers = self.num_hidden_layers
        if not 1 <= self.held_layers <= self.num_hidden_layers:
            raise ValueError(f"held_layers {self.held_layers} outside the "
                             f"{self.num_hidden_layers} layers")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads share K/V heads in whole groups")
        if self.head_dim % 2 or self.head_dim % self.power_tile:
            raise ValueError("a head rotates in pairs and is whole tiles "
                             "of the feature map")
        if self.tie_word_embeddings or self.attention_bias:
            raise ValueError("an untied head and no bias are what is "
                             "built")
        if self.max_position is None:
            self.max_position = self.max_position_embeddings

    @property
    def num_layers(self):
        return self.held_layers

    @property
    def num_heads(self):
        return self.num_attention_heads

    @classmethod
    def tiny(cls, **kw):
        """A CPU-test size with every mechanism: 3 layers, 4 query heads
        on 2 K/V heads of 16, tiles of 8 (D = 192), chunks of 8."""
        base = dict(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, power_tile=8,
            power_chunk=8, max_position_embeddings=4096)
        base.update(kw)
        return cls(**base)


class _Power(nn.Layer):
    def __init__(self, cfg, dt):
        super().__init__()
        e, d = cfg.hidden_size, cfg.head_dim
        hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
        n, one = I.Normal(0.0, cfg.init_std), I.Constant(1.0)
        self.q_proj, self.k_proj = _W((e, hq * d), n, dt), \
            _W((e, hkv * d), n, dt)
        self.v_proj, self.o_proj = _W((e, hkv * d), n, dt), \
            _W((hq * d, e), n, dt)
        self.q_norm, self.k_norm = _W((d,), one, dt), _W((d,), one, dt)
        # see GATE_LOGIT: a normed embedding row sums to e * share over its
        # channels
        share = EMBED_MEAN_RATIO / (1.0 + EMBED_MEAN_RATIO ** 2) ** 0.5
        self.gate_proj = _W((e, hkv), I.Normal(GATE_LOGIT / (e * share),
                                               cfg.init_std / 4), dt)


class _MLP(nn.Layer):
    def __init__(self, cfg, dt):
        super().__init__()
        e, w, n = cfg.hidden_size, cfg.intermediate_size, \
            I.Normal(0.0, cfg.init_std)
        self.gate_proj, self.up_proj = _W((e, w), n, dt), _W((e, w), n, dt)
        self.down_proj = _W((w, e), n, dt)


class _Block(nn.Layer):
    def __init__(self, cfg, dt):
        super().__init__()
        e, one = cfg.hidden_size, I.Constant(1.0)
        self.norm_1, self.norm_2 = _W((e,), one, dt), _W((e,), one, dt)
        self.power = _Power(cfg, dt)
        self.mlp = _MLP(cfg, dt)


class Brumby(nn.Layer):
    """The weights (seeded by `paddle.seed`, built directly in `dtype`, one
    parameter at a time: a float32 copy of the published widths does not
    fit a chip) and the decoder description.  `functional_state()` gives
    the flat names `benchmark/reference/brumby.py` lists."""

    def __init__(self, cfg: BrumbyConfig = None, dtype="float32", **kw):
        super().__init__()
        cfg = cfg or BrumbyConfig(**kw)
        self.cfg = cfg
        self.embed = _W((cfg.vocab_size, cfg.hidden_size),
                        I.Normal(EMBED_MEAN_RATIO * cfg.init_std,
                                 cfg.init_std), dtype)
        self.layers = nn.LayerList(
            [_Block(cfg, dtype) for _ in range(cfg.held_layers)])
        self.norm_f = _W((cfg.hidden_size,), I.Constant(1.0), dtype)
        self.lm_head = _W((cfg.hidden_size, cfg.vocab_size),
                          I.Normal(0.0, cfg.init_std), dtype)

    def decoder_description(self):
        """What `nn.decode.PagedDecoder` builds this model's programs
        from (`nn.decode_blocks.DecoderDescription`)."""
        from ..nn.decode_blocks import (DecoderDescription,
                                        LayerDescription, PowerDescription)

        c = self.cfg
        return DecoderDescription(
            hidden=c.hidden_size, vocab=c.vocab_size, eps=c.rms_norm_eps,
            layers=(LayerDescription("power", "dense"),) * c.held_layers,
            power=PowerDescription(
                heads=c.num_attention_heads,
                kv_heads=c.num_key_value_heads, head_dim=c.head_dim,
                theta=float(c.rope_theta), tile=c.power_tile,
                chunk=c.power_chunk))

    def forward(self, *_a, **_k):
        raise NotImplementedError(
            "Brumby is served: PagedGenerationServer(model, ...) or "
            "nn.decode.PagedDecoder(model.decoder_description(), ...); "
            "the cache-free forward is benchmark/reference/brumby.py")
