"""GPT-2 — decoder-only transformer LM.

Reference config: "GPT-2 medium with fused_attention_op → Pallas flash-attn,
pipeline-parallel Fleet" (BASELINE.json). TPU-first construction:
  * attention → ops.token_major_attention (Pallas flash-attn on TPU, on the
    fused projection's own layout)
  * pre-LN blocks, tied embeddings, bf16-friendly
  * `build_train_step` returns a pure (params, batch, key) -> loss function
    for pjit/fleet hybrid-parallel execution; `jax.checkpoint` per block when
    remat=True (recompute strategy).
"""
from __future__ import annotations

from dataclasses import dataclass

from .. import nn, ops
from ..core.tensor import Tensor
from ..nn import initializer as I


@dataclass
class GPT2Config:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_position: int = 1024
    intermediate_size: int = None  # defaults to 4*hidden
    dropout: float = 0.1
    layer_norm_epsilon: float = 1e-5
    tie_embeddings: bool = True

    def __post_init__(self):
        if self.intermediate_size is None:
            self.intermediate_size = 4 * self.hidden_size

    @classmethod
    def small(cls):
        return cls()

    @classmethod
    def medium(cls):
        return cls(hidden_size=1024, num_layers=24, num_heads=16)

    @classmethod
    def large(cls):
        return cls(hidden_size=1280, num_layers=36, num_heads=20)

    @classmethod
    def tiny(cls):
        return cls(vocab_size=1024, hidden_size=128, num_layers=2,
                   num_heads=4, max_position=256)


class GPT2Block(nn.Layer):
    """Pre-LN decoder block. Fused QKV: one [E, 3E] GEMM (vs 3 separate) —
    bigger MXU tiles, fewer HBM round-trips; the `qkv` name matches the
    column-parallel TP sharding rule."""

    def __init__(self, cfg: GPT2Config):
        super().__init__()
        h = cfg.hidden_size
        self.num_heads = cfg.num_heads
        self.head_dim = h // cfg.num_heads
        self.attn_dropout = cfg.dropout
        self.ln_1 = nn.LayerNorm(h, epsilon=cfg.layer_norm_epsilon)
        self.qkv_proj = nn.Linear(h, 3 * h)
        self.out_proj = nn.Linear(h, h)
        self.ln_2 = nn.LayerNorm(h, epsilon=cfg.layer_norm_epsilon)
        self.fc1 = nn.Linear(h, cfg.intermediate_size)
        self.fc2 = nn.Linear(cfg.intermediate_size, h)
        self.dropout = nn.Dropout(cfg.dropout)

    def forward(self, x, attn_mask=None):
        a = self.ln_1(x)
        # the fused projection goes to attention as the GEMM wrote it, and
        # the output to out_proj as attention wrote it: no head-major copy
        o = ops.token_major_attention(
            self.qkv_proj(a), num_heads=self.num_heads, attn_mask=attn_mask,
            is_causal=True,
            dropout_p=self.attn_dropout if self.training else 0.0)
        x = x + self.dropout(self.out_proj(o))
        m = self.ln_2(x)
        m = self.fc2(ops.gelu(self.fc1(m), approximate=True))
        return x + self.dropout(m)


class GPT2(nn.Layer):
    def __init__(self, cfg: GPT2Config = None, **kw):
        super().__init__()
        cfg = cfg or GPT2Config(**kw)
        self.cfg = cfg
        init_std = 0.02
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                weight_attr=I.Normal(0.0, init_std))
        self.wpe = nn.Embedding(cfg.max_position, cfg.hidden_size,
                                weight_attr=I.Normal(0.0, init_std))
        self.drop = nn.Dropout(cfg.dropout)
        self.h = nn.LayerList([GPT2Block(cfg) for _ in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size,
                                 epsilon=cfg.layer_norm_epsilon)
        if not cfg.tie_embeddings:
            self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                     bias_attr=False)

    def forward(self, input_ids, position_ids=None, attn_mask=None):
        s = input_ids.shape[1]
        if position_ids is None:
            position_ids = ops.arange(0, s, dtype="int32")
        x = self.wte(input_ids) + self.wpe(position_ids)
        x = self.drop(x)
        for block in self.h:
            x = block(x, attn_mask)
        x = self.ln_f(x)
        # head matmul on [B*S, E]: a 3-D head dot picks a sequence-minor
        # output layout on TPU and the loss's flatten then costs a full
        # [B,S,V] relayout copy (4.9ms/step at batch 16, r4 per-op profile
        # %copy.578); the 2-D dot emits logits vocab-minor, and both the
        # flatten here and the unflatten below are layout-free bitcasts
        b, s = input_ids.shape[0], input_ids.shape[1]
        x2 = ops.reshape(x, [-1, self.cfg.hidden_size])
        if self.cfg.tie_embeddings:
            logits2 = ops.matmul(x2, self.wte.weight, transpose_y=True)
        else:
            logits2 = self.lm_head(x2)
        return ops.reshape(logits2, [b, s, self.cfg.vocab_size])

    def loss(self, input_ids, labels):
        logits = self(input_ids)
        return ops.cross_entropy(
            ops.reshape(logits, [-1, self.cfg.vocab_size]),
            ops.reshape(labels, [-1]))

    def quantize_weights(self, params=None):
        """Weight-only int8 (W8A16) packing of the decode path's big 2-D
        weights: returns a NEW flat params dict where each quantized
        entry is replaced by `name::w8c` (int8 codes) + `name::w8s`
        (per-channel scales in the weight dtype); every other entry is
        passed through. This is the ONE shared implementation behind
        `generate(weight_quant="int8")`, the W8A16 deployment artifact
        (`export_generator`), and the serving engines — a
        `PagedGenerationServer(quantization="w8a16")` calls it ONCE at
        construction and reuses the packed params across every
        prefill/step/packed_prefill/packed_verify dispatch, which is
        why the old lazy per-generate weakref cache (`_w8_cache`) is
        gone: serving no longer re-quantizes per call, and offline
        callers hold the snapshot themselves if they loop.

        params: optional pre-snapshotted functional params; defaults to
        the model's current `functional_state()`."""
        if params is None:
            params, _ = self.functional_state()
        return _quantize_decode_weights_int8(params, self.cfg)

    def generate(self, input_ids, max_new_tokens, temperature=0.0,
                 eos_token_id=None, seed=0, top_k=0, top_p=1.0,
                 pad_token_id=None, weight_quant=None, kv_quant=None,
                 kv_cache="dense", prompt_lens=None, block_size=16,
                 sampling=None):
        """Autoregressive decoding with a KV cache (serving path; ref
        capability: fluid beam_search/sampling decode ops). TPU-first:
        static shapes throughout — prefill compiles once per prompt shape,
        then a `lax.scan` emits one token per step against a fixed-size
        cache, so the whole generate is two XLA computations regardless of
        token count. temperature=0 is greedy; >0 samples.

        kv_cache="dense" (default) is the contiguous-cache fast path
        above. kv_cache="paged" decodes against the block-pool
        PagedKVCache (inference/kv_cache.py): prompts are RIGHT-padded
        with per-row `prompt_lens` (no pad-value matching), block_size
        sets the pool granularity, and the step loop runs host-side —
        it is the engine the continuous-batching server drives, exposed
        here for parity testing and offline use. kv_quant="int8" on
        the paged path stores the pool as int8 codes + per-vector
        scales (PagedKVCache(kv_dtype="int8")) with dequant inside the
        attention kernels — the served int8-KV configuration, parity-
        tested here offline.

        sampling: optional `paddle_tpu.sampling.SamplingParams` applied
        to EVERY batch row; overrides the temperature/top_k/top_p/seed
        args. The paged path runs the full vectorized pipeline
        (including min_p and penalties; stop_token_ids stop a row like
        EOS); row r samples from stream seed+r, so each row draws an
        independent counter-based PRNG stream. The dense path maps the
        program-level subset (temperature/top_p/seed, one stop id) and
        rejects the rest eagerly."""
        import jax.numpy as jnp
        import numpy as np

        from ..core.tensor import Tensor
        from ..sampling import SamplingParams

        if sampling is not None and not isinstance(sampling,
                                                   SamplingParams):
            raise TypeError(f"sampling must be a SamplingParams, "
                            f"got {type(sampling).__name__}")
        if sampling is not None and sampling.stop_strings:
            raise ValueError("stop_strings need a detokenizer — serve "
                             "via PagedGenerationServer(detokenize=...)")
        if sampling is not None and sampling.max_new_tokens is not None:
            max_new_tokens = sampling.max_new_tokens
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(np.asarray(input_ids))
        max_new_tokens = int(max_new_tokens)
        if max_new_tokens < 0:
            raise ValueError("max_new_tokens must be >= 0")
        if max_new_tokens == 0:
            return Tensor(ids, stop_gradient=True)
        if kv_cache not in ("dense", "paged"):
            raise ValueError(f"unknown kv_cache {kv_cache!r} "
                             "(supported: 'dense', 'paged')")
        if kv_cache == "paged":
            if kv_quant not in (None, "int8"):
                raise ValueError(f"unknown kv_quant {kv_quant!r} "
                                 "(supported: 'int8')")
            if sampling is None:
                sampling = SamplingParams(
                    temperature=float(temperature), top_k=int(top_k),
                    top_p=float(top_p), seed=int(seed))
            return self._generate_paged(
                ids, max_new_tokens, eos_token_id, seed, pad_token_id,
                prompt_lens, block_size, weight_quant, sampling,
                kv_quant)
        if sampling is not None:
            # dense program-level subset: per-slot fields are a paged-
            # path feature (the dense decode is one fused program)
            for f in ("min_p", "repetition_penalty", "presence_penalty",
                      "frequency_penalty"):
                default = 1.0 if f == "repetition_penalty" else 0.0
                if getattr(sampling, f) != default:
                    raise ValueError(
                        f"kv_cache='dense' does not support "
                        f"SamplingParams.{f}={getattr(sampling, f)!r}; "
                        f"use kv_cache='paged'")
            if len(sampling.stop_token_ids) > 1:
                raise ValueError(
                    "kv_cache='dense' supports at most one stop token "
                    f"id (the eos), got {sampling.stop_token_ids!r}")
            temperature = sampling.temperature
            top_k = sampling.top_k
            top_p = sampling.top_p
            if sampling.seed is not None:
                seed = sampling.seed
            if sampling.stop_token_ids:
                eos_token_id = sampling.stop_token_ids[0]
        if prompt_lens is not None:
            raise ValueError("prompt_lens is only meaningful with "
                             "kv_cache='paged' (the dense path derives "
                             "lengths from LEFT padding)")
        if ids.shape[1] + max_new_tokens > self.cfg.max_position:
            raise ValueError(
                f"prompt ({ids.shape[1]}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_position "
                f"({self.cfg.max_position})")
        if pad_token_id is not None:
            # batched variable-length prompts must be LEFT-padded: the
            # decode reads the prompt's last token at position -1
            valid = np.asarray(ids) != pad_token_id
            if not valid.any(axis=1).all():
                raise ValueError("a prompt row is entirely padding")
            if (np.diff(valid.astype(np.int8), axis=1) < 0).any():
                raise ValueError(
                    "prompts must be LEFT-padded (pad tokens only at the "
                    "start of each row)")
        params, _ = self.functional_state()
        if weight_quant == "int8":
            # weight-only int8 (W8A16): decode is weight-STREAM bound, and
            # the int8->bf16 dequant fuses into the dot's operand pipeline
            # (measured ~1.9x on the streaming path, PERF.md) — halve the
            # per-token parameter stream, keep activations bf16. The
            # quantization itself is ~250 device ops over 124M params;
            # loops should snapshot quantize_weights() once — the
            # serving engines do exactly that at construction.
            params = self.quantize_weights(params)
        elif weight_quant is not None:
            raise ValueError(f"unknown weight_quant {weight_quant!r} "
                             "(supported: 'int8')")
        if kv_quant not in (None, "int8"):
            raise ValueError(f"unknown kv_quant {kv_quant!r} "
                             "(supported: 'int8')")
        out = _generate_jit(self.cfg, params, ids, max_new_tokens,
                            temperature,
                            -1 if eos_token_id is None else int(eos_token_id),
                            int(seed),
                            min(int(top_k), self.cfg.vocab_size), top_p,
                            -1 if pad_token_id is None else int(pad_token_id),
                            kv_quant == "int8")
        return Tensor(out, stop_gradient=True)

    def _generate_paged(self, ids, max_new, eos_token_id, seed,
                        pad_token_id, prompt_lens, block_size,
                        weight_quant, sampling, kv_quant=None):
        """Paged-cache decode: RIGHT-padded prompts + per-row lengths,
        host-side step loop over the jitted PagedDecoder (the same
        engine the continuous-batching server drives), with the full
        per-slot sampling pipeline (`sampling` applied to every row;
        row r uses PRNG stream seed+r). Output rows are [prompt,
        generated, fill]: generated tokens start at each row's true
        length; eos/stop padding continues after a hit like the dense
        path; the tail past len+max_new is filled with pad_token_id
        (else eos, else 0)."""
        import jax.numpy as jnp
        import numpy as np

        from ..core.tensor import Tensor
        from ..inference.kv_cache import PagedKVCache, blocks_for
        from ..nn.decode import PagedDecoder
        from ..sampling import SlotParamStore

        ids = np.asarray(ids).astype(np.int32)
        B, S0 = ids.shape
        if prompt_lens is None:
            lens = np.full((B,), S0, np.int32)
        else:
            lens = np.asarray(prompt_lens).astype(np.int32).reshape(-1)
            if lens.shape[0] != B:
                raise ValueError("prompt_lens must have one entry per row")
            if (lens < 1).any() or (lens > S0).any():
                raise ValueError(f"prompt_lens must be in [1, {S0}]")
        if S0 > self.cfg.max_position or \
                int(lens.max()) + max_new > self.cfg.max_position:
            raise ValueError(
                f"prompt ({int(lens.max())}) + max_new_tokens ({max_new}) "
                f"exceeds max_position ({self.cfg.max_position})")
        eos = -1 if eos_token_id is None else int(eos_token_id)
        params, _ = self.functional_state()
        if weight_quant == "int8":
            params = self.quantize_weights(params)
        elif weight_quant is not None:
            raise ValueError(f"unknown weight_quant {weight_quant!r} "
                             "(supported: 'int8')")
        dt = params["ln_f.weight"].dtype
        bs = int(block_size)
        m_width = blocks_for(max(S0, int(lens.max()) + max_new), bs)
        total_blocks = sum(blocks_for(int(n) + max_new, bs) for n in lens)
        # fixed pool label: offline generate() builds a transient cache
        # per call — an auto-assigned name would mint a new metric
        # series every call under telemetry
        cache = PagedKVCache(self.cfg.num_layers, self.cfg.num_heads,
                             self.cfg.hidden_size // self.cfg.num_heads,
                             block_size=bs, num_blocks=total_blocks + 1,
                             dtype=dt, kv_dtype=kv_quant,
                             name="gpt2-generate")
        for b in range(B):  # offline batch: reserve the full horizon
            cache.allocate(b, int(lens[b]) + max_new)
        tables = jnp.asarray(cache.table_array(range(B), m_width))
        dec = PagedDecoder.for_config(self.cfg, bs, kv_dtype=kv_quant)
        # per-row sampling buffers: the same params every row, stream
        # seed+r per row (independent counter-based PRNG streams)
        store = SlotParamStore(B, self.cfg.vocab_size)
        base_seed = sampling.seed if sampling.seed is not None \
            else int(seed)
        for b in range(B):
            store.set_slot(b, sampling, base_seed + b, eos=eos,
                           prompt_ids=ids[b, :int(lens[b])])
        fill = pad_token_id if pad_token_id is not None \
            else (eos if eos >= 0 else 0)
        stop_fill = eos if eos >= 0 else fill
        lens_j = jnp.asarray(lens)
        active = jnp.ones((B,), bool)
        sp, mode = store.step_args(np.zeros((B,), np.int32))
        tok, stopped, kc, vc, counts = dec.prefill(
            params, jnp.asarray(ids), lens_j, tables, cache.k_blocks,
            cache.v_blocks, sp, mode)
        cache.swap_arrays(kc, vc)
        store.swap_counts(counts)
        tok = np.asarray(tok)
        done = np.asarray(stopped)
        out_toks = [tok]
        pos = lens.copy()
        for step in range(1, max_new):
            sp, mode = store.step_args(np.full((B,), step, np.int32))
            nxt, stopped, kc, vc, counts = dec.step(
                params, jnp.asarray(out_toks[-1]), jnp.asarray(pos),
                active, tables, kc, vc, sp, mode)
            cache.swap_arrays(kc, vc)
            store.swap_counts(counts)
            nxt = np.asarray(nxt)
            # dense-path semantics: rows that hit eos (or a stop token)
            # keep emitting the stop-fill value
            nxt = np.where(done, stop_fill, nxt)
            done = done | np.asarray(stopped)
            out_toks.append(nxt)
            pos = pos + 1
        gen = np.stack(out_toks, axis=1)             # [B, max_new]
        out = np.full((B, S0 + max_new), fill, np.int32)
        for b in range(B):
            n = int(lens[b])
            out[b, :n] = ids[b, :n]
            out[b, n:n + max_new] = gen[b]
        return Tensor(jnp.asarray(out), stop_gradient=True)


def _quantize_decode_weights_int8(params, cfg):
    """Per-channel symmetric int8 for the decode path's big 2-D weights.
    Each quantized entry replaces `name` with `name + "::w8"` holding
    (codes int8, scale bf16); the decode fn detects the key at trace time
    and applies the scale AFTER the contraction (epilogue-fused). wte is
    quantized per-ROW so both the embedding gather and the tied head
    share one scale vector."""
    import jax.numpy as jnp

    out = dict(params)

    def quant(name, axis):
        w = out.pop(name)
        amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis,
                       keepdims=True)
        scale = (jnp.maximum(amax, 1e-12) / 127.0)
        codes = jnp.clip(jnp.round(w.astype(jnp.float32) / scale),
                         -127, 127).astype(jnp.int8)
        # FLAT keys (not tuples) so the dict serializes through the
        # standard .pdiparams npz artifact unchanged; scales stay in the
        # weight dtype (bf16 for serving) — an f32 scale vector measured
        # 0.41 vs 0.30 ms/token (promotion breaks the epilogue fusion)
        out[name + "::w8c"] = codes
        out[name + "::w8s"] = scale.squeeze(axis).astype(w.dtype)

    quant("wte.weight", 1)  # per-row: shared by gather and tied head
    if not cfg.tie_embeddings:
        quant("lm_head.weight", 0)
    for i in range(cfg.num_layers):
        for part in ("qkv_proj", "out_proj", "fc1", "fc2"):
            quant(f"h.{i}.{part}.weight", 0)  # per-output-column
    return out


def _generate_jit(cfg: GPT2Config, params, ids, max_new, temp, eos, seed,
                  top_k=0, top_p=1.0, pad=-1, kv_quant=False):
    import jax
    import jax.numpy as jnp

    spec = (cfg.num_layers, cfg.num_heads,
            cfg.hidden_size // cfg.num_heads, cfg.hidden_size,
            cfg.layer_norm_epsilon, cfg.tie_embeddings)
    fn = _generate_impl(spec, max_new, top_k, top_p < 1.0, bool(kv_quant))
    # key/temperature/eos/top_p/pad are traced arguments: new values reuse
    # the compiled program (static: max_new — the scan length — top_k,
    # which fixes the lax.top_k output shape, and WHETHER nucleus
    # filtering is on, so the default top_p=1.0 path never pays the
    # per-token sort)
    return fn(params, ids, jax.random.key(seed),
              jnp.float32(temp), jnp.int32(eos), jnp.float32(top_p),
              jnp.int32(pad))


import functools as _functools  # noqa: E402


@_functools.lru_cache(maxsize=16)
def _generate_impl(spec, max_new, top_k=0, nucleus=False, kv_quant=False):
    import jax
    return jax.jit(_build_decode_fn(spec, max_new, top_k, nucleus,
                                    kv_quant))


def _build_decode_fn(spec, max_new, top_k=0, nucleus=False,
                     kv_quant=False):
    """Build the raw (params, ids, key, temp, eos, top_p) -> tokens decode
    function for one static configuration. Two XLA computations total: a
    prefill over the prompt and a lax.scan of single-token steps against a
    fixed-size KV cache [L, B, H, S0+max_new, D]."""
    import jax
    import jax.numpy as jnp

    L, H, Dh, E, eps, tied = spec
    scale = Dh ** -0.5

    def ln(x, w, b):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * w + b

    def matw(p, name, x, dt):
        # weight-only int8 (W8A16): `name::w8` holds (codes, per-out-col
        # scale); the int8->dt convert fuses into the dot's operand
        # pipeline (halves the weight stream — decode is stream-bound)
        # and the scale multiplies the [.., N] OUTPUT (epilogue-fused)
        codes = p.get(name + "::w8c")
        if codes is None:
            return x @ p[name]
        return (x @ codes.astype(dt)) * p[name + "::w8s"].astype(dt)

    def mlp(p, i, x):
        dt = x.dtype
        hdn = jax.nn.gelu(
            matw(p, f"h.{i}.fc1.weight", x, dt) + p[f"h.{i}.fc1.bias"],
            approximate=True)
        return matw(p, f"h.{i}.fc2.weight", hdn, dt) + p[f"h.{i}.fc2.bias"]

    def qkv_split(p, i, a):
        # a: [..., E] -> q, k, v each [..., H, Dh]
        qkv = matw(p, f"h.{i}.qkv_proj.weight", a, a.dtype) \
            + p[f"h.{i}.qkv_proj.bias"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        new = q.shape[:-1] + (H, Dh)
        return q.reshape(new), k.reshape(new), v.reshape(new)

    def step_fn(params, ids, key0, temp, eos, top_p, pad):
        B, S0 = ids.shape
        S = S0 + max_new
        wpe = params["wpe.weight"]
        dt = params["ln_f.weight"].dtype
        wte_codes = params.get("wte.weight::w8c")
        if wte_codes is None:
            wte_full = params["wte.weight"]

            def embed(t):
                return wte_full[t]
        else:
            wte_rs = params["wte.weight::w8s"]  # [V] per-row scale

            def embed(t):
                return wte_codes[t].astype(dt) * wte_rs[t][..., None] \
                    .astype(dt)

        def head(xf):
            if tied:
                if wte_codes is None:
                    return (xf @ wte_full.T).astype(jnp.float32)
                return ((xf @ wte_codes.T.astype(dt))
                        * wte_rs[None, :].astype(dt)).astype(jnp.float32)
            return matw(params, "lm_head.weight", xf,
                        dt).astype(jnp.float32)

        # LEFT-padding support: pad is a traced token id (-1 = no padding,
        # valid everywhere). Pad keys are masked out of attention, pad
        # positions don't consume wpe slots, and the rightmost position is
        # always a real token, so x[:, -1] stays the correct read-out.
        valid = ids != pad                           # [B, S0] bool
        pos = jnp.maximum(jnp.cumsum(valid, axis=1) - 1, 0)
        n_valid = valid.sum(axis=1)                  # [B]

        # ---- prefill over the prompt (causal full attention) ----
        x = embed(ids) + wpe[pos]
        if kv_quant:
            # int8 KV cache, per-(position) vector scales: at large batch
            # the decode becomes cache-READ bound and halving the KV
            # stream is the remaining lever (weights: see ::w8c)
            ck = jnp.zeros((L, B, H, S, Dh), jnp.int8)
            cv = jnp.zeros((L, B, H, S, Dh), jnp.int8)
            ksc = jnp.zeros((L, B, H, S), dt)
            vsc = jnp.zeros((L, B, H, S), dt)
        else:
            ck = jnp.zeros((L, B, H, S, Dh), dt)
            cv = jnp.zeros((L, B, H, S, Dh), dt)
            ksc = vsc = jnp.zeros((0,), dt)

        def kv_enc(t):
            # [..., Dh] -> (int8 codes, per-vector scale [...])
            amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1)
            sc = jnp.maximum(amax, 1e-12) / 127.0
            codes = jnp.clip(jnp.round(t.astype(jnp.float32)
                                       / sc[..., None]),
                             -127, 127).astype(jnp.int8)
            return codes, sc.astype(dt)

        causal = jnp.tril(jnp.ones((S0, S0), bool))
        kmask = causal[None, None] & valid[:, None, None, :]
        for i in range(L):
            a = ln(x, params[f"h.{i}.ln_1.weight"],
                   params[f"h.{i}.ln_1.bias"])
            q, k, v = qkv_split(params, i, a)       # [B, S0, H, Dh]
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
            if kv_quant:
                kc, ks = kv_enc(k)
                vc, vs = kv_enc(v)
                ck = ck.at[i, :, :, :S0].set(kc)
                cv = cv.at[i, :, :, :S0].set(vc)
                ksc = ksc.at[i, :, :, :S0].set(ks)
                vsc = vsc.at[i, :, :, :S0].set(vs)
            else:
                ck = ck.at[i, :, :, :S0].set(k)
                cv = cv.at[i, :, :, :S0].set(v)
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(
                jnp.float32) * scale
            s = jnp.where(kmask, s, -1e30)
            w = jax.nn.softmax(s, axis=-1).astype(dt)
            o = jnp.einsum("bhqk,bhkd->bhqd", w, v)
            o = o.transpose(0, 2, 1, 3).reshape(B, S0, E)
            x = x + matw(params, f"h.{i}.out_proj.weight", o, dt) \
                + params[f"h.{i}.out_proj.bias"]
            m = ln(x, params[f"h.{i}.ln_2.weight"],
                   params[f"h.{i}.ln_2.bias"])
            x = x + mlp(params, i, m)
        xf = ln(x[:, -1], params["ln_f.weight"], params["ln_f.bias"])
        logits0 = head(xf)

        def pick(logits, key):
            # temp/top_p are traced: branch with lax.cond so every
            # sampling mode lives in one compiled program
            def sample():
                l = logits / jnp.maximum(temp, 1e-6)
                if top_k > 0:  # static: fixes the lax.top_k shape
                    kth = jax.lax.top_k(l, top_k)[0][..., -1:]
                    l = jnp.where(l < kth, -jnp.inf, l)
                if nucleus:  # static: the top_p=1 default skips the sort
                    # keep the smallest prefix of desc-sorted tokens whose
                    # exclusive cumulative prob stays under top_p (the
                    # top-1 token always survives)
                    sl = jnp.sort(l, axis=-1)[..., ::-1]
                    probs = jax.nn.softmax(sl, axis=-1)
                    cum = jnp.cumsum(probs, axis=-1) - probs
                    n_keep = jnp.maximum(
                        jnp.sum(cum < top_p, axis=-1, keepdims=True), 1)
                    kth_val = jnp.take_along_axis(sl, n_keep - 1, axis=-1)
                    l = jnp.where(l < kth_val, -jnp.inf, l)
                return jax.random.categorical(
                    key, l, axis=-1).astype(jnp.int32)

            return jax.lax.cond(
                temp > 0.0, sample,
                lambda: jnp.argmax(logits, axis=-1).astype(jnp.int32))

        key0, sub0 = jax.random.split(key0)
        tok0 = pick(logits0, sub0)
        done0 = (tok0 == eos) & (eos >= 0)

        # ---- decode: one token per scan step against the cache ----
        vfull = jnp.concatenate(
            [valid, jnp.ones((B, max_new), bool)], axis=1)  # [B, S]

        def body(carry, step):
            tok, done, ck, cv, ksc, vsc, key = carry
            t = S0 + step  # absolute cache slot of `tok`
            x = embed(tok) + wpe[n_valid + step]    # per-row position
            for i in range(L):
                a = ln(x, params[f"h.{i}.ln_1.weight"],
                       params[f"h.{i}.ln_1.bias"])
                q, k, v = qkv_split(params, i, a)   # [B, H, Dh]
                if kv_quant:
                    kc, ks = kv_enc(k)
                    vc, vs = kv_enc(v)
                    ck = ck.at[i, :, :, t].set(kc)
                    cv = cv.at[i, :, :, t].set(vc)
                    ksc = ksc.at[i, :, :, t].set(ks)
                    vsc = vsc.at[i, :, :, t].set(vs)
                    # fold the per-vector scales into the SMALL tensors so
                    # the big cache is consumed as raw int8 codes (the
                    # convert fuses into the einsum operand like the
                    # weight dot): scores scale per position AFTER the
                    # contraction; v's scale rides the [B,H,S] probs
                    s = jnp.einsum("bhd,bhsd->bhs", q,
                                   ck[i].astype(dt)).astype(jnp.float32) \
                        * ksc[i].astype(jnp.float32) * scale
                else:
                    ck = ck.at[i, :, :, t].set(k)
                    cv = cv.at[i, :, :, t].set(v)
                    s = jnp.einsum("bhd,bhsd->bhs", q, ck[i]).astype(
                        jnp.float32) * scale
                s = jnp.where((jnp.arange(s.shape[-1]) <= t)[None, None]
                              & vfull[:, None, :], s, -1e30)
                w = jax.nn.softmax(s, axis=-1).astype(dt)
                if kv_quant:
                    o = jnp.einsum("bhs,bhsd->bhd", w * vsc[i],
                                   cv[i].astype(dt)).reshape(B, E)
                else:
                    o = jnp.einsum("bhs,bhsd->bhd", w, cv[i]).reshape(B, E)
                x = x + matw(params, f"h.{i}.out_proj.weight", o, dt) \
                    + params[f"h.{i}.out_proj.bias"]
                m = ln(x, params[f"h.{i}.ln_2.weight"],
                       params[f"h.{i}.ln_2.bias"])
                x = x + mlp(params, i, m)
            xf = ln(x, params["ln_f.weight"], params["ln_f.bias"])
            logits = head(xf)
            key, sub = jax.random.split(key)
            nxt = pick(logits, sub)
            # eos is traced (-1 disables): once done, keep emitting eos
            nxt = jnp.where(done, eos, nxt)
            done = done | ((nxt == eos) & (eos >= 0))
            return (nxt, done, ck, cv, ksc, vsc, key), tok

        (last, *_), toks = jax.lax.scan(
            body, (tok0, done0, ck, cv, ksc, vsc, key0),
            jnp.arange(max_new - 1)) if max_new > 1 else \
            ((tok0,), jnp.zeros((0, B), jnp.int32))
        seq = jnp.concatenate([ids, toks.T.astype(jnp.int32),
                               last[:, None]], axis=1)
        return seq

    return step_fn


def export_generator(model: "GPT2", path_prefix, prompt_len,
                     max_new_tokens, top_k=0, top_p_enabled=False,
                     batch_size=None, weight_quant=None, kv_quant=None):
    """Serialize the KV-cache decode program as the standard deployment
    artifact (.pdmodel StableHLO + .pdiparams npz) so text generation runs
    in a serving process with NO Python model class:

        served = paddle.jit.load(path_prefix)
        tokens = served(ids, seed, temperature, eos, top_p, pad)

    ids: [B, prompt_len] int32 (B symbolic when batch_size is None);
    seed uint32; temperature/top_p float32 (top_p only filters when
    exported with top_p_enabled); eos int32 (-1 disables); pad int32
    (-1 = no padding, otherwise prompts must be LEFT-padded with this
    token id and pads are masked from attention)."""
    import jax
    import jax.numpy as jnp

    from .. import jit as jit_mod

    cfg = model.cfg
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1 for an exported "
                         "generator (a 0-token artifact has no decode)")
    if prompt_len + max_new_tokens > cfg.max_position:
        raise ValueError("prompt_len + max_new_tokens exceeds max_position")
    spec = (cfg.num_layers, cfg.num_heads,
            cfg.hidden_size // cfg.num_heads, cfg.hidden_size,
            cfg.layer_norm_epsilon, cfg.tie_embeddings)
    if kv_quant not in (None, "int8"):
        raise ValueError(f"unknown kv_quant {kv_quant!r} "
                         "(supported: 'int8')")
    decode = _build_decode_fn(spec, int(max_new_tokens),
                              min(int(top_k), cfg.vocab_size),
                              bool(top_p_enabled), kv_quant == "int8")

    def serving_fn(params, bufs, ids, seed, temp, eos, top_p, pad):
        del bufs  # GPT-2 has no buffers; kept for the artifact convention
        return decode(params, ids, jax.random.key(seed), temp, eos, top_p,
                      pad)

    params, _ = model.functional_state()
    if weight_quant == "int8":
        # W8A16 artifact: the served program streams int8 weights
        # (1.8-2.7x decode tokens/s at small batch, PERF.md); codes and
        # bf16 scales ride the standard npz as flat keys (the artifact
        # stores extension dtypes as bit-preserving views + dtype
        # sidecars, so the served program keeps the bf16-scale fast path)
        params = _quantize_decode_weights_int8(params, cfg)
    elif weight_quant is not None:
        raise ValueError(f"unknown weight_quant {weight_quant!r} "
                         "(supported: 'int8')")
    if batch_size is None:
        (bdim,) = jit_mod._symbolic_dims(1)
    else:
        bdim = int(batch_size)
    from jax import export as jexport
    args = (jax.ShapeDtypeStruct((bdim, int(prompt_len)), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.uint32),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.int32))
    p_specs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
               for k, v in params.items()}
    jf = jax.jit(serving_fn)
    try:
        # multi-platform like jit.save: a dev-box export must serve on TPU
        exported = jexport.export(jf, platforms=("cpu", "tpu"))(
            p_specs, {}, *args)
    except Exception:
        exported = jexport.export(jf)(p_specs, {}, *args)
    meta = {"kind": "gpt2_generator", "weight_quant": weight_quant,
            "kv_quant": kv_quant, "prompt_len": int(prompt_len),
            "max_new_tokens": int(max_new_tokens), "top_k": int(top_k),
            "top_p_enabled": bool(top_p_enabled),
            # None = batch-polymorphic (serving layers pick their own B)
            "batch_size": None if batch_size is None else int(batch_size),
            "inputs": ["ids[int32]", "seed[uint32]",
                       "temperature[f32]", "eos[int32]", "top_p[f32]",
                       "pad[int32] (-1 disables left-pad masking)"]}
    return jit_mod.write_artifact(path_prefix, exported, params, {}, meta)


def build_train_step(cfg: GPT2Config, remat=False, dtype="float32"):
    """Pure functional GPT-2 loss for pjit/fleet: returns
    (loss_fn(params, batch, key), init_params()). The module tree above is
    used once to materialize params; the pure fn re-binds them per call.
    """
    import jax
    import jax.numpy as jnp

    from ..core import rng as rng_mod

    model = GPT2(cfg)
    model.train()
    if dtype != "float32":
        model.to(dtype=dtype)

    def init_params():
        p, _ = model.functional_state()
        return p

    def loss_fn(params, batch, key):
        saved_p, saved_b = model.functional_state()
        rng_saved = (rng_mod._default_generator._key,
                     rng_mod._default_generator._count)
        rng_mod._default_generator._key = key
        rng_mod._default_generator._count = 0
        model.load_functional_state(params, None)
        try:
            from ..core.autograd import functional_trace
            input_ids, labels = batch["input_ids"], batch["labels"]
            with functional_trace():
                loss = model.loss(Tensor(input_ids), Tensor(labels))
            return loss._value
        finally:
            model.load_functional_state(saved_p, saved_b)
            (rng_mod._default_generator._key,
             rng_mod._default_generator._count) = rng_saved

    if remat:
        import jax
        loss_fn = jax.checkpoint(loss_fn)
    return loss_fn, init_params, model
