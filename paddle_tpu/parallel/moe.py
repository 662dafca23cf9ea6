"""Mixture-of-Experts with expert parallelism over the `ep` mesh axis.

Reference lineage: Paddle's distributed MoE work (incubate/distributed/models/
moe in later reference versions) — rebuilt TPU-first: top-k gating, capacity-
bounded dispatch as one einsum pair, experts sharded over `ep` so each device
holds E/ep experts; under jit/GSPMD the dispatch einsums lower to all-to-all
over ICI. Everything is static-shaped (capacity factor) — XLA-friendly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def top2_gating(logits, capacity, key=None, second_policy="all"):
    """Switch/GShard-style top-2 gating with static capacity.

    logits: [T, E]. Returns (combine [T, E, C], dispatch bool [T, E, C], aux).
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)

    g1_idx = jnp.argmax(probs, axis=-1)
    g1_prob = jnp.max(probs, axis=-1)
    probs_wo1 = probs * (1 - jax.nn.one_hot(g1_idx, e, dtype=probs.dtype))
    g2_idx = jnp.argmax(probs_wo1, axis=-1)
    g2_prob = jnp.max(probs_wo1, axis=-1)

    # load-balancing auxiliary loss (GShard eq.)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(g1_idx, e, dtype=probs.dtype), axis=0)
    aux = jnp.sum(me * ce) * e

    def positions(idx):
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) * onehot - 1  # position within expert
        return onehot, pos.max(axis=-1)

    oh1, pos1 = positions(g1_idx)
    # second choice positions come after all first choices
    count1 = jnp.sum(oh1, axis=0)
    oh2 = jax.nn.one_hot(g2_idx, e, dtype=jnp.int32)
    pos2 = (jnp.cumsum(oh2, axis=0) * oh2 - 1).max(axis=-1) + \
        jnp.take(count1, g2_idx)

    keep1 = pos1 < capacity
    keep2 = pos2 < capacity

    denom = jnp.maximum(g1_prob + g2_prob, 1e-9)
    w1 = jnp.where(keep1, g1_prob / denom, 0.0)
    w2 = jnp.where(keep2, g2_prob / denom, 0.0)

    def scatter(idx, pos, w, keep):
        # [T, E, C]
        e_oh = jax.nn.one_hot(idx, e, dtype=logits.dtype)
        c_oh = jax.nn.one_hot(jnp.where(keep, pos, 0), capacity,
                              dtype=logits.dtype)
        return w[:, None, None] * e_oh[:, :, None] * c_oh[:, None, :]

    combine = scatter(g1_idx, pos1, w1, keep1) + scatter(g2_idx, pos2, w2,
                                                         keep2)
    dispatch = combine > 0
    return combine, dispatch, aux


def moe_layer_apply(params, x, capacity_factor=1.25):
    """Pure MoE FFN apply.

    params: {"gate": [D, E], "w1": [E, D, H], "b1": [E, H],
             "w2": [E, H, D], "b2": [E, D]}
    x: [T, D] tokens. Returns ([T, D], aux_loss).
    Under jit with w1/w2 sharded P("ep", ...) the dispatch einsum becomes an
    all-to-all over ep.
    """
    t, d = x.shape
    e = params["gate"].shape[1]
    capacity = max(1, int(capacity_factor * t / e))
    logits = x @ params["gate"]
    combine, dispatch, aux = top2_gating(logits, capacity)
    # dispatch tokens: [E, C, D]
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)
    h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", expert_in, params["w1"])
                    + params["b1"][:, None, :])
    expert_out = jnp.einsum("ech,ehd->ecd", h, params["w2"]) \
        + params["b2"][:, None, :]
    out = jnp.einsum("tec,ecd->td", combine, expert_out)
    return out, aux


def init_moe_params(key, d_model, d_hidden, num_experts, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    s1 = (2.0 / d_model) ** 0.5
    s2 = (2.0 / d_hidden) ** 0.5
    return {
        "gate": jax.random.normal(k1, (d_model, num_experts), dtype) * s1,
        "w1": jax.random.normal(k2, (num_experts, d_model, d_hidden), dtype) * s1,
        "b1": jnp.zeros((num_experts, d_hidden), dtype),
        "w2": jax.random.normal(k3, (num_experts, d_hidden, d_model), dtype) * s2,
        "b2": jnp.zeros((num_experts, d_model), dtype),
    }


def moe_shardings(mesh, params, ep_axis="ep"):
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = {"gate": P(), "w1": P(ep_axis), "b1": P(ep_axis),
            "w2": P(ep_axis), "b2": P(ep_axis)}
    return {k: NamedSharding(mesh, spec[k]) for k in params}


# ---- dropless routed experts, for a layer that is told which it holds ----

def _row_tile(n_tokens, top_k, held):
    """Rows a tile of the grouped matmul: a power of two near the picks an
    expert held here gets, between 16 (one packed bf16 sublane group: a
    decode batch) and 128 (a prefill chunk), so that an expert's weights
    are streamed for one tile or two."""
    tm = 16
    while tm < 128 and tm * held < n_tokens * top_k:
        tm *= 2
    return tm


def routed_expert_ffn(x, valid, router_w, router_b, gate, up, down, *,
                      held_first, top_k, scaling, renormalize=True):
    """The routed half of an expert FFN on one chip of an expert-parallel
    layer, under a sigmoid router: s = sigmoid(x W_r) over ALL experts
    (router_w [D, n_experts]), the selection scores s + router_b
    (router_b [n_experts], for the choice alone); then `dispatch_experts`
    on them, whose arguments and results these are."""
    s = jax.nn.sigmoid(jnp.dot(x, router_w,
                               preferred_element_type=jnp.float32))
    return dispatch_experts(
        x, valid, s, s + router_b.astype(jnp.float32), gate, up, down,
        held_first=held_first, top_k=top_k, scaling=scaling,
        renormalize=renormalize)


def dispatch_experts(x, valid, scores, select, gate, up, down, *,
                     held_first, top_k, scaling=1.0, renormalize=False):
    """Route over ALL experts by a router's scores, compute the experts
    held here: one chip's share of an expert-parallel layer, whatever the
    router.

    x [N, D] tokens, valid [N] bool (padding and idle rows route nowhere);
    scores, select [N, n_experts] float32 from the caller's router: the
    top_k of `select` are chosen, and a chosen expert weighs its `scores`
    entry (over the chosen's sum when `renormalize`) times `scaling`;
    gate, up [held, D, F], down [held, F, D] the SwiGLU weights of experts
    held_first .. held_first + held - 1.
    Returns (y [N, D], counts int32 [4], picks int32 [N, k]):
    y is the weighted sum over the chosen experts HELD HERE (what the
    others would add is their chips'), counts = (tokens routed, picks held
    here, held experts with a pick, the most picks on one expert), picks
    the experts each token chose (for a check that compares the choice).

    Dropless: the picks held are sorted by expert into tiles of rows,
    each tile one expert's (an expert's rows padded up to a tile), and one
    grouped matmul takes them all (`moe_gmm` on the TPU; elsewhere the
    same tiles against their experts' weights, gathered)."""
    n, d = x.shape
    held = gate.shape[0]
    f32 = jnp.float32
    _, idx = jax.lax.top_k(select, top_k)                         # [N, k]
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if renormalize:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * scaling
    local = idx - held_first
    mine = valid[:, None] & (local >= 0) & (local < held)
    group = jnp.where(mine, local, held).reshape(-1)              # [N*k]
    picks = n * top_k
    tm = _row_tile(n, top_k, held)
    rows = -(-(picks + held * (tm - 1)) // tm) * tm               # static

    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)
    start = jnp.cumsum(sizes) - sizes         # of each group, among the sorted
    padded = -(-sizes[:held] // tm) * tm
    ends = jnp.cumsum(padded)                 # of each group's tiles, in rows
    g_sorted = group[order]
    rank = jnp.arange(picks) - start[g_sorted]
    dest_sorted = jnp.where(
        g_sorted < held,
        (ends - padded)[jnp.minimum(g_sorted, held - 1)] + rank, rows)
    row_token = jnp.zeros((rows,), jnp.int32).at[dest_sorted].set(
        (order // top_k).astype(jnp.int32), mode="drop")
    n_valid = ends[-1] // tm
    tiles = jnp.arange(rows // tm)
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, tiles * tm, side="right"), held - 1)
    tile_expert = jnp.where(tiles < n_valid, tile_expert,
                            tile_expert[jnp.maximum(n_valid - 1, 0)])
    x_rows = x[row_token]                     # pad rows: token 0, never read
    from ..ops import attention as _attention

    if _attention._on_tpu():
        from ..ops.pallas.moe_gmm import moe_gmm_kernel

        y_rows = moe_gmm_kernel(x_rows, gate, up, down, tile_expert,
                                n_valid[None], tm=tm)
    else:
        xt = x_rows.reshape(rows // tm, tm, d)
        hid = jax.nn.silu(jnp.einsum("tmd,tdf->tmf", xt, gate[tile_expert])) \
            * jnp.einsum("tmd,tdf->tmf", xt, up[tile_expert])
        y_rows = jnp.einsum("tmf,tfd->tmd", hid,
                            down[tile_expert]).reshape(rows, d)
    dest = jnp.zeros((picks,), jnp.int32).at[order].set(
        jnp.minimum(dest_sorted, rows - 1).astype(jnp.int32))
    y_pick = y_rows[dest].reshape(n, top_k, d).astype(f32)
    y = jnp.sum(jnp.where(mine[..., None], w[..., None] * y_pick, 0.0),
                axis=1)
    counts = jnp.stack([valid.sum(), mine.sum(),
                        (sizes[:held] > 0).sum(),
                        sizes[:held].max()]).astype(jnp.int32)
    return y.astype(x.dtype), counts, idx.astype(jnp.int32)
