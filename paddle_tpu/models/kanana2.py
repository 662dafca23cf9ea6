"""Kanana-2 — a DeepSeek-V3-layout decoder: every layer mixes by latent
attention (MLA, no query low rank) with rotary positions on the 64
decoupled channels of every query head and of the one key head they share,
paired as neighbours (`rope_interleave`); the first layer feeds forward
through a dense SwiGLU, every later one through 128 routed experts (a
sigmoid router, top 6 chosen by score plus a correction bias, weights
renormalised and scaled) beside two shared experts; RMSNorm pre-norm
blocks, no position table, an untied head, no bias.  Served through the
paged engine (`inference.PagedGenerationServer(model, ...)`): a sequence is
its rows of a paged latent pool and nothing else (the store is empty).  The
model is its weights and the description `nn.decode` builds its programs
from; the mathematics lives in `nn/decode_blocks.py`, `ops/mla.py`,
`ops/rotary.py` and `parallel/moe.routed_expert_ffn`, the blocks are
`models/kimi_linear.py`'s.

One chip of a deployment holds some layers (`held_layers`, the first n of
the published stack; the rest are further pipeline stages), each whole.
"""
from __future__ import annotations

from dataclasses import dataclass, field

from .. import nn
from ..nn import initializer as I
from .kimi_linear import _MLA, _MoE, _SwiGLU, _W


@dataclass
class Kanana2Config:
    """The published `config.json` keys (kanana-2-30b-a3b-instruct-2601's
    values as defaults) that the layers read, plus what the config is
    silent on and what this chip holds."""
    vocab_size: int = 128256
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    q_lora_rank: int = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 1
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.448
    scoring_func: str = "sigmoid"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    rope_interleave: bool = True
    rope_scaling: dict = None
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    max_position_embeddings: int = 32768
    # not in the config: positions a tile of the latent's XLA prefill
    mla_tile: int = 64
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
        built = {"q_lora_rank": None, "rope_scaling": None,
                 "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
                 "tie_word_embeddings": False, "attention_bias": False}
        for key, want in built.items():
            if getattr(self, key) != want:
                raise ValueError(f"{key}={want!r} is what is built, not "
                                 f"{getattr(self, key)!r}")
        if self.max_position is None:
            self.max_position = self.max_position_embeddings

    @property
    def num_layers(self):
        return self.held_layers

    # the names `models.kimi_linear`'s blocks read: every expert is held
    @property
    def num_experts(self):
        return self.n_routed_experts

    @property
    def held_experts(self):
        return (0, self.n_routed_experts)

    def layer_kinds(self):
        """("mla", "dense" | "experts") of each held layer, in order."""
        return tuple(("mla", "dense" if i < self.first_k_dense_replace
                      else "experts") for i in range(self.held_layers))

    @classmethod
    def tiny(cls, **kw):
        """A CPU-test size with every mechanism: a dense layer, then 3
        expert layers of 8 experts, top 2, beside 2 shared; rotary on the
        interleaved `pe` channels; tiles of 8."""
        base = dict(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=4,
            num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
            num_experts_per_tok=2, rope_theta=10000.0, mla_tile=8,
            max_position_embeddings=4096)
        base.update(kw)
        return cls(**base)


class _Block(nn.Layer):
    def __init__(self, cfg, ffn, dt):
        super().__init__()
        one = I.Constant(1.0)
        self.norm_1 = _W((cfg.hidden_size,), one, dt)
        self.norm_2 = _W((cfg.hidden_size,), one, dt)
        self.mla = _MLA(cfg, dt)
        if ffn == "dense":
            self.mlp = _SwiGLU(cfg.hidden_size, cfg.intermediate_size,
                               cfg.init_std, dt)
        else:
            self.moe = _MoE(cfg, dt, shared=cfg.n_shared_experts)


class Kanana2(nn.Layer):
    """The weights (seeded by `paddle.seed`, built directly in `dtype`:
    a float32 copy of the published widths does not fit a chip) and the
    decoder description.  `functional_state()` gives the flat names
    `benchmark/reference/kanana2.py` lists."""

    def __init__(self, cfg: Kanana2Config = None, dtype="float32", **kw):
        super().__init__()
        cfg = cfg or Kanana2Config(**kw)
        self.cfg = cfg
        n = I.Normal(0.0, cfg.init_std)
        self.embed = _W((cfg.vocab_size, cfg.hidden_size), n, dtype)
        self.layers = nn.LayerList(
            [_Block(cfg, ffn, dtype) for _mixer, ffn in cfg.layer_kinds()])
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
            lora=c.kv_lora_rank, kda_chunk=c.mla_tile,
            experts=c.n_routed_experts, held_first=0,
            held=c.n_routed_experts, top_k=c.num_experts_per_tok,
            renormalize=c.norm_topk_prob, scaling=c.routed_scaling_factor,
            mla_theta=float(c.rope_theta),
            mla_interleaved=c.rope_interleave)

    def forward(self, *_a, **_k):
        raise NotImplementedError(
            "Kanana2 is served: PagedGenerationServer(model, ...) or "
            "nn.decode.PagedDecoder(model.decoder_description(), ...); the "
            "cache-free forward is benchmark/reference/kanana2.py")
