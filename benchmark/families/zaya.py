"""The zaya family: what a serving cell needs from the program to serve a
ZAYA1 configuration through the entry points a user calls
(`models.zaya.Zaya`, `inference.PagedGenerationServer`), and the plain
reference it is checked against.  Keys of the configuration file are the
published config.json's; `layers` is what this chip holds (`reduced`)."""
from __future__ import annotations

# imported before anything is built: a checkout that cannot serve this
# family fails here, at once
from paddle_tpu.models.zaya import Zaya, ZayaConfig

# The routers' bias recipe (`assumed.router_bias` of the configuration
# file): sequences, bias updates and the first step of `balance_routers`,
# whose step shrinks by BALANCE_DECAY an update.  A softmax score lies near
# 1 / experts, so the first step is BALANCE_STEP of that.
BALANCE_ROWS, BALANCE_STEPS = 8, 40
BALANCE_STEP, BALANCE_DECAY = 0.5, 0.85


def program_config(cfg):
    rope = cfg["rope_parameters"]["hybrid"]
    return ZayaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], cca_time0=cfg["cca_time0"],
        cca_time1=cfg["cca_time1"],
        partial_rotary_factor=rope["partial_rotary_factor"],
        rope_theta=rope["rope_theta"], num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        router_hidden_size=cfg["router_hidden_size"],
        rms_norm_eps=cfg["rms_norm_eps"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        attention_bias=cfg["attention_bias"],
        max_position_embeddings=cfg["max_position_embeddings"],
        held_layers=cfg["layers"],
        held_experts=tuple(cfg["deployment"]["held_experts"]))


def serve_kernels(cfg):
    """Pallas kernels a program holds, by program and kernel name: every
    layer attends through the paged kernel of its program and runs one
    `moe_gmm`.  Rotary, the convolutions, the router and the head are
    XLA."""
    n = cfg["layers"]
    return {"decode_step": {"paged_attn_decode": n, "moe_gmm": n},
            "packed_prefill": {"paged_attn_prefill": n, "moe_gmm": n}}


def shape(cfg):
    """The sizes flops_zaya.py and the `.serve` metric readers need, under
    their names."""
    c = program_config(cfg)
    return {
        "layers": c.held_layers, "hidden": c.hidden_size,
        "vocab": c.vocab_size, "heads": c.num_attention_heads,
        "kv_heads": c.num_key_value_heads, "head_dim": c.head_dim,
        "expert_layers": c.held_layers,
        "expert_width": c.moe_intermediate_size,
        "held_experts": c.held_experts[1], "top_k": c.num_experts_per_tok,
    }


def served_model(cfg, dtype):
    """The model with its weights from `paddle.seed` and its routers'
    balancing biases set on seeded random tokens (`assumed.router_bias` of
    the configuration file says why); `model.router_balance` keeps the
    experts' largest load over the mean, before and after."""
    model = Zaya(program_config(cfg), dtype=dtype)
    model.eval()
    model.router_balance = balance_routers(
        model, cfg["assumed_sizes"]["balance_tokens"])
    return model


def balance_routers(model, tokens):
    """Set every layer's `router.bias` (added to the softmax scores for
    the choice alone) so that random tokens spread evenly over the
    experts, as a trained router's balancing bias does.  With random
    weights they do not: the router's logits have a component every row
    shares (a GELU's output has a positive mean, and the carried state
    adds the layers before), so a few experts take most tokens whatever
    the token.

    `families/kimi_linear.balance_routers`' rule on this model's own
    serving program: BALANCE_STEPS packed prefills of BALANCE_ROWS
    sequences (`tokens` in all) of seeded random ids; after each, an expert
    that got more than its share has its bias lowered and one that got
    less raised, by a step that shrinks geometrically.  Returns the
    experts' largest load over the mean, per layer, before and after."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.inference.kv_cache import PagedKVCache, blocks_for
    from paddle_tpu.nn.decode import PagedDecoder
    from paddle_tpu.sampling import SlotParamStore

    desc = model.decoder_description()
    layers = range(len(desc.layers))
    params = dict(model.functional_state()[0])
    rows, align = BALANCE_ROWS, 128    # the stream kernel's query tile
    per_row = max(align, tokens // rows // align * align)
    block = 128
    cache = PagedKVCache.for_description(
        desc, block_size=block,
        num_blocks=rows * blocks_for(per_row, block) + 1,
        dtype=params[desc.final_norm].dtype, max_slots=rows)
    cache.ensure_many([(r, per_row) for r in range(rows)])
    decoder = PagedDecoder(desc, block)
    sp = SlotParamStore(rows, desc.vocab).warm_args(rows)
    g = np.random.default_rng([0, 30])
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
        _t, _s, kc, vc, state, _c, routed = decoder.packed_prefill(
            params, toks, seg, pos, tables, sample, cache.k_blocks,
            cache.v_blocks, sp, state=cache.state)
        cache.swap_arrays(kc, vc, state)
        load = np.stack([np.bincount(p.reshape(-1), minlength=desc.experts)
                         for p in np.asarray(routed["picks"])])
        if step in (0, BALANCE_STEPS):
            skew.append((load.max(-1) / share).tolist())
        if step < BALANCE_STEPS:
            bias -= BALANCE_STEP / desc.experts * BALANCE_DECAY ** step \
                * np.sign(load - share)
            for n, b in zip(names, bias):
                params[n] = jnp.asarray(b)
    model.load_functional_state({n: params[n] for n in names})
    return {"before": skew[0], "after": skew[1]}


def serving_path(cfg):
    """Which form the paged attention ops take for this configuration's
    shapes on this backend (`ops.attention.paged_attention_path`: the
    platform and the kernels' shape gate alone choose, nothing falls
    back); the expert matmul follows the platform too."""
    from paddle_tpu.ops.attention import paged_attention_path

    return paged_attention_path(
        cfg["head_dim"], cfg["deployment"]["serve"]["block_size"],
        cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"])


def reference(cfg):
    """(arch, hidden(params, ids, **kw) -> (x, found), head(params,
    rows) -> logits) of benchmark/reference/zaya.py for this cut."""
    from reference import zaya as ref

    a = ref.arch(cfg)
    return (a, lambda params, ids, **kw: ref.hidden(params, ids, a, **kw),
            lambda params, rows: ref.head(params, rows, a))
