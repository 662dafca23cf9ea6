"""The kimi_linear family: what a serving cell needs from the program to
serve a Kimi-Linear configuration through the entry points a user calls
(`models.kimi_linear.KimiLinear`, `inference.PagedGenerationServer`), and
the plain reference it is checked against.  Keys of the configuration file
are the published config.json's; `layers` and `num_experts` are what this
chip holds (`reduced`), `published.num_experts` the router's width."""
from __future__ import annotations

# imported before anything is built: a checkout that cannot serve this
# family fails here, at once
from paddle_tpu.models.kimi_linear import KimiLinear, KimiLinearConfig

# The routers' weight recipe (`assumed.weights` of the configuration file):
# sequences, bias updates and the first step of `balance_routers`, whose
# step shrinks by BALANCE_DECAY an update (0.3 to 5e-4 of a score).
BALANCE_ROWS, BALANCE_STEPS = 8, 40
BALANCE_STEP, BALANCE_DECAY = 0.3, 0.85


def serve_kernels(cfg):
    """Pallas kernels a program holds, by program and kernel name: a KDA
    layer steps its state with `kda_decode`, the MLA layer walks its
    latents with `mla_decode`, an expert layer runs one `moe_gmm`.  The
    prefill forms of KDA and MLA are XLA (ROADMAP M6, M7)."""
    kinds = program_config(cfg).layer_kinds()
    kda = sum(1 for m, _f in kinds if m == "kda")
    mla = sum(1 for m, _f in kinds if m == "mla")
    moe = sum(1 for _m, f in kinds if f == "experts")
    return {"decode_step": {"kda_decode": kda, "mla_decode": mla,
                            "moe_gmm": moe},
            "packed_prefill": {"moe_gmm": moe}}


def program_config(cfg):
    lin = cfg["linear_attn_config"]
    sizes = cfg["assumed_sizes"]
    return KimiLinearConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        num_experts=cfg["published"]["num_experts"],
        num_experts_per_token=cfg["num_experts_per_token"],
        num_shared_experts=cfg["num_shared_experts"],
        moe_renormalize=cfg["moe_renormalize"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        rms_norm_eps=cfg["rms_norm_eps"],
        model_max_length=cfg["model_max_length"],
        kda_layers=tuple(lin["kda_layers"]),
        full_attn_layers=tuple(lin["full_attn_layers"]),
        kda_num_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        short_conv_kernel_size=lin["short_conv_kernel_size"],
        kda_gate_rank=sizes["kda_gate_rank"], kda_chunk=sizes["kda_chunk"],
        held_layers=cfg["layers"],
        held_experts=tuple(cfg["deployment"]["held_experts"]))


def shape(cfg):
    """The sizes flops_kimi_linear.py needs, under its own names."""
    c = program_config(cfg)
    kinds = c.layer_kinds()
    return {
        "layers": c.held_layers, "hidden": c.hidden_size,
        "vocab": c.vocab_size, "heads": c.num_attention_heads,
        "kda_layers": sum(1 for m, _f in kinds if m == "kda"),
        "mla_layers": sum(1 for m, _f in kinds if m == "mla"),
        "expert_layers": sum(1 for _m, f in kinds if f == "experts"),
        "kda_heads": c.kda_num_heads, "kda_dim": c.kda_head_dim,
        "kda_chunk": c.kda_chunk,
        "latent": c.kv_lora_rank + c.qk_rope_head_dim,
        "lora": c.kv_lora_rank, "expert_width": c.moe_intermediate_size,
        "held_experts": c.held_experts[1], "top_k": c.num_experts_per_token,
    }


def served_model(cfg, dtype):
    """The model with its weights from `paddle.seed` and its routers'
    correction biases balanced on seeded random tokens (`assumed.weights`
    of the configuration file says why); `model.router_balance` keeps the
    experts' largest load over the mean, before and after."""
    model = KimiLinear(program_config(cfg), dtype=dtype)
    model.eval()
    model.router_balance = balance_routers(
        model, cfg["assumed_sizes"]["balance_tokens"])
    return model


def balance_routers(model, tokens):
    """Set every expert layer's `router.bias` (the published
    `e_score_correction_bias`: added to the scores for the choice alone)
    so that random tokens spread evenly over the experts, as a trained
    router's bias does.  With random weights they do not: every KDA
    layer's q, k, v come out of a SiLU with a positive mean, so its
    output, and with it every row's normed hidden state, has a large
    component that all rows share, and a few experts take most of the
    tokens whatever the token.

    The released training rule, run to a fixed point on the model's own
    serving program: BALANCE_STEPS packed prefills of BALANCE_ROWS
    sequences (`tokens` in all) of seeded random ids; after each, an
    expert that got more than its share has its bias lowered and one that
    got less raised, by a step that shrinks geometrically.  Returns the
    experts' largest load over the mean, per expert layer, before and
    after."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.inference.kv_cache import PagedKVCache, blocks_for
    from paddle_tpu.nn.decode import PagedDecoder
    from paddle_tpu.sampling import SlotParamStore

    desc = model.decoder_description()
    layers = [i for i, l in enumerate(desc.layers) if l.ffn == "experts"]
    params = dict(model.functional_state()[0])
    rows, align = BALANCE_ROWS, desc.pack_multiple
    per_row = max(align, tokens // rows // align * align)
    block = max(16, align)
    cache = PagedKVCache.for_description(
        desc, block_size=block,
        num_blocks=rows * blocks_for(per_row, block) + 1,
        dtype=params[desc.final_norm].dtype, max_slots=rows)
    cache.ensure_many([(r, per_row) for r in range(rows)])
    decoder = PagedDecoder(desc, block)
    sp = SlotParamStore(rows, desc.vocab).warm_args(rows)
    g = np.random.default_rng([0, 26])
    toks = jnp.asarray(g.integers(1, desc.vocab, rows * per_row,
                                  dtype=np.int32))
    seg = jnp.asarray(np.repeat(np.arange(rows, dtype=np.int32), per_row))
    pos = jnp.asarray(np.tile(np.arange(per_row, dtype=np.int32), rows))
    tables = jnp.asarray(cache.table_array(list(range(rows))))
    sample = jnp.asarray(np.arange(rows, dtype=np.int32) * per_row)
    names = [f"layers.{i}.moe.router.bias" for i in layers]
    bias = np.stack([np.asarray(params[n], np.float32) for n in names])
    share = rows * per_row * desc.top_k / desc.experts
    skew = []
    for step in range(BALANCE_STEPS + 1):
        _t, _s, kc, state, _c, routed = decoder.packed_prefill(
            params, toks, seg, pos, tables, sample, cache.k_blocks, None,
            sp, state=cache.state)
        cache.swap_arrays(kc, None, state)
        load = np.stack([np.bincount(p.reshape(-1), minlength=desc.experts)
                         for p in np.asarray(routed["picks"])])
        if step in (0, BALANCE_STEPS):
            skew.append((load.max(-1) / share).tolist())
        if step < BALANCE_STEPS:
            bias -= BALANCE_STEP * BALANCE_DECAY ** step \
                * np.sign(load - share)
            for n, b in zip(names, bias):
                params[n] = jnp.asarray(b)
    model.load_functional_state({n: params[n] for n in names})
    return {"before": skew[0], "after": skew[1]}


def serving_path(_cfg):
    """Which form the decode-side ops take on this backend: the platform
    alone chooses (`ops.attention._on_tpu`), nothing falls back."""
    from paddle_tpu.ops import attention

    return "pallas" if attention._on_tpu() else "xla"


def reference(cfg):
    """(arch, hidden(params, ids, **kw) -> (x, found), head(params,
    rows) -> logits) of benchmark/reference/kimi_linear.py for this cut."""
    from reference import kimi_linear as ref

    a = ref.arch(cfg)
    return (a, lambda params, ids, **kw: ref.hidden(params, ids, a, **kw),
            lambda params, rows: ref.head(params, rows, a))
