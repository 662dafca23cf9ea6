"""Pipeline parallelism — GPipe and circular-interleaved schedules over
the `pp` mesh axis.

Reference: python/paddle/distributed/fleet/meta_optimizers/pipeline_optimizer.py
(graph-partitioned pipeline with send/recv ops over NCCL). TPU-first rework:
SPMD collective-permute pipelining — a lax.scan over ticks rotates
activations to the next stage with ppermute. Backward flows through the
scan + ppermute transpose automatically, so jax.grad of the pipelined
loss trains the pipeline without hand-written send/recv grads.

Two schedules, selectable via `strategy.pipeline_configs["schedule"]`:

* GPipe (`pipeline_apply`): every rank holds ONE stage. M + S - 1 ticks
  of one full stage-pass each; bubble fraction = (S-1)/(M+S-1).
* Circular interleaved (`pipeline_apply_interleaved`): every rank holds
  V non-adjacent layer chunks (global layer-group l*S + r sits in chunk
  slot l of rank r — the Megatron-interleaved placement). A tick is one
  CHUNK pass (1/V of a stage), and the static schedule
      tick(m, v) = (m//S)*V*S + (v//S)*S + (m%S) + (v%S)
  keeps the exact GPipe ring dataflow — each tick's ppermute output is
  consumed on the very next tick — while the fill/drain shrinks to
  chunk granularity: bubble fraction = (S-1)/(V*M+S-1). E.g. S=2, M=4:
  GPipe burns 20% by construction, interleaved V=2 burns 11% (the
  dryrun leg's tiny M=2 config: 33% -> 20%).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.lax import axis_size as _axis_size
from .mesh import pvary as _pvary


def pipeline_apply(stage_fn, stage_params, microbatches, axis_name="pp"):
    """Run homogeneous pipeline stages inside shard_map over `axis_name`.

    stage_fn: (params, x) -> y, the per-stage computation (same structure on
        every rank; each rank's shard of `stage_params` is ITS stage).
    stage_params: pytree whose leaves are this rank's stage params (already
        sharded: leading stacked dim split over pp outside, so in here each
        rank sees its own slice).
    microbatches: [M, mb, ...] — every rank sees the same microbatch stream
        (replicated over pp); only stage 0's compute on fresh input matters,
        later stages consume permuted activations.
    Returns [M, mb, ...] outputs of the LAST stage (valid on every rank —
        replicated by a final collect).
    """
    s = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    m = microbatches.shape[0]
    ticks = m + s - 1
    mb_shape = microbatches.shape[1:]

    fwd_perm = [(i, (i + 1) % s) for i in range(s)]

    def tick(carry, t):
        buf, outs = carry
        # stage 0 ingests microbatch t (if any); others use the rotated buffer
        mb_idx = jnp.clip(t, 0, m - 1)
        fresh = jax.lax.dynamic_index_in_dim(microbatches, mb_idx, 0,
                                             keepdims=False)
        x = jnp.where(idx == 0, fresh, buf)
        y = stage_fn(stage_params, x)
        # last stage's result for microbatch (t - (s-1)) is ready at tick t
        out_idx = t - (s - 1)
        is_valid = (out_idx >= 0)
        outs = jax.lax.cond(
            is_valid,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, y, jnp.clip(out_idx, 0, m - 1), 0),
            lambda o: o, outs)
        buf_next = jax.lax.ppermute(y, axis_name, fwd_perm)
        return (buf_next, outs), None

    buf0 = jnp.zeros(mb_shape, microbatches.dtype)
    buf0 = _pvary(buf0, axis_name)
    outs0 = jnp.zeros((m,) + mb_shape, microbatches.dtype)
    outs0 = _pvary(outs0, axis_name)
    mbs = _pvary(microbatches, axis_name) \
        if not _is_varying(microbatches) else microbatches
    (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(ticks))
    # outs holds last-stage results only on the last rank; broadcast via
    # masked psum (a one-hot "bcast from rank s-1")
    outs_masked = jnp.where(idx == s - 1, outs, jnp.zeros_like(outs))
    return jax.lax.psum(outs_masked, axis_name)


def _is_varying(x):
    return True  # inputs inside shard_map are treated varying; pvary is idempotent-safe


def pipeline_apply_interleaved(chunk_fn, chunk_params, microbatches,
                               axis_name="pp"):
    """Circular-interleaved schedule inside shard_map over `axis_name`.

    chunk_fn: (params, x) -> y, ONE chunk's computation (1/V of a stage).
    chunk_params: pytree whose leaves are [V, ...] — this rank's V chunk
        param sets; global layer-group order is chunk l of rank r ==
        group l*S + r (reshape a [V*S, ...] stack to [V, S, ...] and
        shard dim 1 on pp to get this placement).
    microbatches: [M, mb, ...] with M % S == 0, replicated over pp.
    Returns [M, mb, ...] outputs of the LAST group (replicated).

    Derivation of the schedule (see module docstring): microbatch m's
    group v runs on rank v%S at tick
        t = (m//S)*V*S + (v//S)*S + (m%S) + (v%S),
    so consecutive groups of one microbatch run on consecutive ranks at
    consecutive ticks (including the ring wrap S-1 -> 0 into the next
    chunk level), and each rank runs at most one chunk per tick. Inverse
    (what rank r does at tick t): u = t - r; m = (u//(V*S))*S + u%S;
    chunk slot l = (u % (V*S)) // S; idle iff u < 0 or m >= M.
    """
    s = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    m_total = microbatches.shape[0]
    if m_total % s:
        raise ValueError(
            f"interleaved schedule needs num_microbatches ({m_total}) "
            f"divisible by pp degree ({s})")
    v_chunks = jax.tree_util.tree_leaves(chunk_params)[0].shape[0]
    ticks = v_chunks * m_total + s - 1
    mb_shape = microbatches.shape[1:]

    fwd_perm = [(i, (i + 1) % s) for i in range(s)]

    def tick(carry, t):
        buf, outs = carry
        u = t - idx
        uc = jnp.maximum(u, 0)
        rem = uc % (v_chunks * s)
        chunk_l = rem // s
        mb_idx = (uc // (v_chunks * s)) * s + uc % s
        valid = (u >= 0) & (mb_idx < m_total)
        mb_c = jnp.clip(mb_idx, 0, m_total - 1)
        # group v == 0 (rank 0, chunk 0) ingests a fresh microbatch;
        # everything else consumes the ring buffer
        fresh = jax.lax.dynamic_index_in_dim(microbatches, mb_c, 0,
                                             keepdims=False)
        x = jnp.where((idx == 0) & (chunk_l == 0), fresh, buf)
        params_l = jax.tree_util.tree_map(
            lambda p: jax.lax.dynamic_index_in_dim(p, chunk_l, 0,
                                                   keepdims=False),
            chunk_params)
        y = chunk_fn(params_l, x)
        # the LAST group (rank S-1, chunk V-1) finishes microbatch mb_idx
        done = (idx == s - 1) & (chunk_l == v_chunks - 1) & valid
        outs = jax.lax.cond(
            done,
            lambda o: jax.lax.dynamic_update_index_in_dim(o, y, mb_c, 0),
            lambda o: o, outs)
        buf_next = jax.lax.ppermute(y, axis_name, fwd_perm)
        return (buf_next, outs), None

    buf0 = _pvary(jnp.zeros(mb_shape, microbatches.dtype), axis_name)
    outs0 = _pvary(jnp.zeros((m_total,) + mb_shape,
                                    microbatches.dtype), axis_name)
    (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(ticks))
    outs_masked = jnp.where(idx == s - 1, outs, jnp.zeros_like(outs))
    return jax.lax.psum(outs_masked, axis_name)


def make_pipeline_loss(stage_fn, loss_head, mesh, num_microbatches,
                       axis_name="pp", schedule="gpipe", num_virtual=1):
    """Build loss(params_stacked, batch) running the selected pipeline
    schedule under shard_map on `mesh`.

    stage_fn: (stage_params, x) -> y — one stage (gpipe) / one chunk
        (interleaved); same callable works for both: it sees a param
        tree whose leading stacked dim is whatever its slice holds.
    loss_head: (y_last, labels) -> scalar (computed replicated)
    params_stacked: pytree with leading dim = #stages (gpipe) or
        #groups = num_virtual * pp_degree (interleaved; groups in layer
        order — the reshape below produces the interleaved placement).
    schedule: "gpipe" | "interleaved" (strategy.pipeline_configs).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if schedule not in ("gpipe", "interleaved"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    interleaved = schedule == "interleaved" and num_virtual > 1

    def loss_fn(params_stacked, x, labels):
        s_pp = mesh.shape[axis_name]

        def inner(params_local, x, labels):
            m = num_microbatches
            mbs = x.reshape((m, x.shape[0] // m) + x.shape[1:])
            if interleaved:
                # params_local leaves: [V, 1, ...] — this rank's V chunks
                chunk_tree = jax.tree_util.tree_map(
                    lambda p: p[:, 0], params_local)
                outs = pipeline_apply_interleaved(
                    stage_fn, chunk_tree, mbs, axis_name)
            else:
                # params_local leaves: [1, ...] — this rank's stage
                params_stage = jax.tree_util.tree_map(
                    lambda p: p[0], params_local)
                outs = pipeline_apply(stage_fn, params_stage, mbs,
                                      axis_name)
            y = outs.reshape((x.shape[0],) + outs.shape[2:])
            ell = loss_head(y, labels)
            # identical on every pp rank; mean keeps it consistent
            return jax.lax.pmean(ell, axis_name)

        if interleaved:
            # [V*S, ...] in layer order -> [V, S, ...]; sharding dim 1 on
            # pp gives rank r chunks {l*S + r} — the interleaved placement
            params_in = jax.tree_util.tree_map(
                lambda p: p.reshape((num_virtual, s_pp) + p.shape[1:]),
                params_stacked)
            spec_p = jax.tree_util.tree_map(
                lambda p: P(None, axis_name), params_in)
        else:
            params_in = params_stacked
            spec_p = jax.tree_util.tree_map(
                lambda p: P(axis_name), params_stacked)
        return shard_map(
            inner, mesh=mesh,
            in_specs=(spec_p, P(), P()),
            out_specs=P(),
            check_vma=False)(params_in, x, labels)

    return loss_fn


def bubble_fraction(schedule, num_stages, num_microbatches, num_virtual=1):
    """Analytic steady-state idle fraction of each schedule (docstring
    derivation): gpipe (S-1)/(M+S-1); interleaved (S-1)/(V*M+S-1)."""
    s, m, v = num_stages, num_microbatches, num_virtual
    if schedule == "gpipe":
        return (s - 1) / (m + s - 1)
    if schedule == "interleaved":
        return (s - 1) / (v * m + s - 1)
    raise ValueError(f"unknown pipeline schedule {schedule!r}")
