"""Strategy lowering — the meta-optimizer equivalents.

Reference: python/paddle/distributed/fleet/meta_optimizers/*. Each reference
meta-optimizer is a graph rewrite; here each strategy flag picks an XLA-native
mechanism applied when building the hybrid train step:

  amp             -> bf16 compute policy on the step (amp_optimizer.py)
  recompute       -> jax.checkpoint around layer blocks (recompute_optimizer.py)
  gradient_merge  -> lax.scan micro-batch accumulation (gradient_merge_optimizer.py)
  sharding (ZeRO) -> params/opt-state sharded on dp axis (sharding_optimizer.py)
  localsgd        -> periodic param psum-average (localsgd_optimizer.py)
  lamb/lars       -> optimizer swap (lamb_optimizer.py / lars_optimizer.py)
  pipeline        -> pp mesh axis + microbatch schedule (pipeline_optimizer.py)
  fp16_allreduce  -> grads cast bf16 before psum (fp16_allreduce_optimizer.py)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ... import optimizer as opt_mod
from ...parallel.mesh import make_mesh, mesh_guard, set_mesh


def wrap_optimizer(fleet_obj, optimizer, strategy):
    """lamb/lars strategies swap the inner optimizer (ref: lamb_optimizer.py
    `_can_apply`: replaces Momentum/Adam); other flags are applied at
    train-step build time."""
    if strategy.lamb and not isinstance(optimizer, opt_mod.Lamb):
        optimizer = opt_mod.Lamb(
            learning_rate=optimizer._lr,
            lamb_weight_decay=strategy.lamb_configs.get("lamb_weight_decay", 0.01),
            parameters=optimizer._parameter_list,
            grad_clip=optimizer._grad_clip)
    elif strategy.lars and isinstance(optimizer, opt_mod.Momentum):
        optimizer = opt_mod.Lars(
            learning_rate=optimizer._lr,
            momentum=optimizer._momentum,
            lars_coeff=strategy.lars_configs.get("lars_coeff", 0.001),
            lars_weight_decay=strategy.lars_configs.get("lars_weight_decay",
                                                        0.0005),
            parameters=optimizer._parameter_list,
            grad_clip=optimizer._grad_clip)
    optimizer._fleet_strategy = strategy
    return optimizer


def _remat_policy(strategy):
    """Map recompute_configs to a jax.checkpoint policy — the TPU analogue
    of the reference's per-op checkpoints list (recompute_optimizer.py):
      granularity 'full'      -> recompute everything (default; max memory
                                 savings, most recompute FLOPs)
      granularity 'selective' -> save weight-matmul outputs, recompute
                                 batched (attention-score) dots and
                                 elementwise — the Megatron selective
                                 recompute
      granularity 'dots'      -> save every dot output, recompute only
                                 elementwise chains
    """
    gran = (strategy.recompute_configs or {}).get("granularity", "full")
    import jax.ad_checkpoint as adc
    table = {
        "full": None,  # jax.checkpoint default: recompute everything
        "selective": adc.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "dots": adc.checkpoint_policies.dots_saveable,
    }
    if gran not in table:
        raise ValueError(
            f"recompute_configs.granularity must be one of {list(table)}, "
            f"got {gran!r}")
    return table[gran]


def apply_strategy(strategy, loss_fn):
    """Wrap a pure loss_fn(params, batch, key) per strategy flags."""
    fn = loss_fn
    if strategy.recompute:
        fn = jax.checkpoint(fn, policy=_remat_policy(strategy))
    if strategy.amp:
        inner = fn

        def amp_fn(params, batch, key):
            cast = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.bfloat16)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
            return inner(cast, batch, key)
        fn = amp_fn
    return fn


def build_hybrid_train_step(strategy, loss_fn, optimizer, mesh=None,
                            stage_fn=None, loss_head=None):
    """Build the full pjit'ed train step per strategy.

    loss_fn: pure (params, batch, key) -> scalar loss.
    Returns (step_fn, mesh): step_fn(params, opt_state, batch, key) ->
    (loss, new_params, new_opt_state); all collectives XLA-inserted.

    strategy.pipeline (pp_degree > 1) additionally needs `stage_fn`
    ((stage_params, x) -> y, the homogeneous per-stage computation) and
    `loss_head` ((y, labels) -> scalar); the loss is then built by
    parallel/pipeline.py's GPipe schedule over the pp axis and `loss_fn`
    may be None.
    localsgd / dgc build an explicit-dp step (shard_map over dp) because
    both need per-worker gradients before the collective.
    """
    hybrid = strategy.hybrid_configs
    if mesh is None:
        mesh = make_mesh(dp=None if hybrid.get("dp_degree", -1) in (-1, None)
                         else hybrid["dp_degree"],
                         mp=hybrid.get("mp_degree", 1),
                         pp=hybrid.get("pp_degree", 1),
                         sp=hybrid.get("sp_degree", 1))
        set_mesh(mesh)

    if strategy.pipeline and mesh.shape.get("pp", 1) > 1:
        # ref: pipeline_optimizer.py — graph-partitioned GPipe. Here the
        # stage computation is user-supplied and the schedule comes from
        # parallel/pipeline.py (ppermute microbatch rotation).
        if stage_fn is None or loss_head is None:
            raise ValueError(
                "strategy.pipeline with pp_degree>1 needs stage_fn and "
                "loss_head (the reference partitions the program graph by "
                "device annotation; the TPU rebuild takes the per-stage fn)")
        from ...parallel.pipeline import make_pipeline_loss
        m = strategy.pipeline_configs.get("accumulate_steps", 1)
        # schedule: "gpipe" (default) or "interleaved" (circular, each
        # rank holds `num_virtual` non-adjacent chunks; bubble shrinks
        # from (S-1)/(M+S-1) to (S-1)/(V*M+S-1))
        sched = strategy.pipeline_configs.get("schedule", "gpipe")
        v = strategy.pipeline_configs.get("num_virtual", 1)
        pl_loss = make_pipeline_loss(stage_fn, loss_head, mesh, m, "pp",
                                     schedule=sched, num_virtual=v)

        def loss_fn(params, batch, key):  # noqa: F811
            labels = batch.get("labels", batch.get("y"))
            return pl_loss(params, batch["x"], labels)

    if strategy.localsgd or strategy.dgc \
            or getattr(strategy, "int8_allreduce", False):
        return _build_explicit_dp_step(strategy, loss_fn, optimizer, mesh)

    wrapped_loss = apply_strategy(strategy, loss_fn)
    k_steps = strategy.gradient_merge_configs.get("k_steps", 1) \
        if strategy.gradient_merge else 1

    def step(params, opt_state, batch, key):
        # tracing under the mesh lets the attention op shard_map its
        # Pallas kernel over (dp, mp): GSPMD cannot partition a kernel
        with mesh_guard(mesh):
            return _step(params, opt_state, batch, key)

    def _step(params, opt_state, batch, key):
        if k_steps > 1:
            # micro-batch accumulation via scan (gradient_merge)
            def micro(accum, mb):
                l, g = jax.value_and_grad(wrapped_loss)(params, mb, key)
                return (accum[0] + l,
                        jax.tree_util.tree_map(jnp.add, accum[1], g)), None
            micro_batches = jax.tree_util.tree_map(
                lambda x: x.reshape((k_steps, x.shape[0] // k_steps)
                                    + x.shape[1:]), batch)
            zero_g = jax.tree_util.tree_map(jnp.zeros_like, params)
            (loss, grads), _ = jax.lax.scan(micro, (0.0, zero_g), micro_batches)
            if strategy.gradient_merge_configs.get("avg", True):
                loss = loss / k_steps
                grads = jax.tree_util.tree_map(lambda g: g / k_steps, grads)
        else:
            loss, grads = jax.value_and_grad(wrapped_loss)(params, batch, key)
        if strategy.fp16_allreduce:
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.bfloat16).astype(g.dtype), grads)
        if optimizer._grad_clip is not None and hasattr(optimizer._grad_clip,
                                                        "clip_tree"):
            grads = optimizer._grad_clip.clip_tree(grads)
        new_params, new_state = optimizer.functional_update(params, grads,
                                                            opt_state)
        return loss, new_params, new_state

    # ZeRO shardings (ref: sharding_optimizer.py stages):
    #   stage 1: optimizer state sharded over dp, params/grads replicated
    #   stage 2: + gradient reduce-scatter — with dp-sharded slots XLA's
    #            SPMD partitioner emits the reduce-scatter into the update
    #            itself, so stages 1/2 share the slot-sharding lowering
    #   stage 3: + parameters sharded over dp
    def _zero_spec(v):
        # shard the largest dim that divides dp degree
        dp = mesh.shape["dp"]
        for i, s in enumerate(v.shape):
            if s % dp == 0 and s >= dp:
                return P(*([None] * i + ["dp"] + [None] * (v.ndim - i - 1)))
        return P()

    zero_stage = strategy.sharding_configs.get("stage", 2) \
        if strategy.sharding else 0
    if zero_stage >= 3:
        param_sharding_fn = lambda v: NamedSharding(mesh, _zero_spec(v))  # noqa: E731
    elif strategy.pipeline and mesh.shape.get("pp", 1) > 1:
        pp = mesh.shape["pp"]
        param_sharding_fn = lambda v: NamedSharding(  # noqa: E731
            mesh, P("pp", *([None] * (v.ndim - 1)))
            if v.ndim and v.shape[0] == pp else P())
    else:
        param_sharding_fn = lambda v: NamedSharding(mesh, P())  # noqa: E731
    slot_sharding_fn = (lambda v: NamedSharding(mesh, _zero_spec(v))) \
        if zero_stage >= 1 else None

    def compile_for(params, batch, opt_state=None):
        p_sh = jax.tree_util.tree_map(param_sharding_fn, params)
        b_sh = jax.tree_util.tree_map(
            lambda x: NamedSharding(mesh, P("dp", *([None] * (x.ndim - 1)))),
            batch)
        s_sh = None
        if opt_state is not None:  # slots: the ZeRO spec, else replicated
            s_sh = jax.tree_util.tree_map(
                slot_sharding_fn
                or (lambda v: NamedSharding(mesh, P())), opt_state)
        # pin outputs to the input contract, so the step can be fed its
        # own outputs — otherwise XLA picks output shardings (on four
        # chips it split out_proj.weight over mp after a shard_mapped
        # attention) and the next call re-compiles or, compiled ahead of
        # time, refuses its arguments
        out_sh = None if s_sh is None else (None, p_sh, s_sh)
        return jax.jit(step,
                       in_shardings=(p_sh, s_sh, b_sh, None),
                       out_shardings=out_sh,
                       donate_argnums=(0, 1))

    step.compile_for = compile_for
    step.mesh = mesh
    return step, mesh


def _build_explicit_dp_step(strategy, loss_fn, optimizer, mesh):
    """localsgd / dgc lowering — both need each dp worker's own gradient
    before the collective, so the step body runs under shard_map over dp.

    localsgd (ref: localsgd_optimizer.py): params carry a leading dp axis
    (one divergent copy per worker); workers update locally from LOCAL
    grads and every k_steps psum-average the copies.
    dgc (ref: dgc_optimizer.py): error-feedback top-k sparsification — the
    allreduce moves only the top (1-sparsity) gradient entries; the residual
    stays in a per-worker error buffer folded into the next step.
    """
    from jax import shard_map

    wrapped_loss = apply_strategy(strategy, loss_fn)
    dp = mesh.shape["dp"]
    use_localsgd = strategy.localsgd
    use_dgc = strategy.dgc
    k_steps = strategy.localsgd_configs.get("k_steps", 1)
    sparsity = strategy.dgc_configs.get("sparsity", [0.999])[-1] \
        if use_dgc else 0.0

    # per-worker (divergent) state carries a leading dp axis, sharded P("dp")
    # into shard_map so each worker owns one slice of size 1:
    #   localsgd -> params + optimizer slots diverge between averaging steps
    #   dgc      -> the error-feedback residual is inherently per-worker
    stack_pi = use_localsgd        # params + inner slots
    stack_err = use_dgc

    def _stack(tree):
        return jax.tree_util.tree_map(
            lambda v: jnp.broadcast_to(v[None], (dp,) + v.shape), tree)

    def _local(tree):   # [1, ...] worker slice -> [...]
        return jax.tree_util.tree_map(lambda v: v[0], tree)

    def _relocal(tree):  # [...] -> [1, ...] for the P("dp") out concat
        return jax.tree_util.tree_map(lambda v: v[None], tree)

    def _compress(g, e):
        # error feedback: add residual, keep top-k magnitude entries
        g = g + e
        flat = g.reshape(-1)
        kk = max(1, int(flat.size * (1.0 - sparsity)))
        thresh = jax.lax.top_k(jnp.abs(flat), kk)[0][-1]
        g_send = jnp.where(jnp.abs(g) >= thresh, g, 0.0)
        return g_send, g - g_send

    def local_step(params, inner_state, err, step_ct, batch, key):
        p_local = _local(params) if stack_pi else params
        s_local = _local(inner_state) if stack_pi else inner_state
        e_local = _local(err) if stack_err else err
        loss, grads = jax.value_and_grad(wrapped_loss)(p_local, batch, key)
        if use_dgc:
            flat_g, tdef = jax.tree_util.tree_flatten(grads)
            flat_e = jax.tree_util.tree_leaves(e_local)
            pairs = [_compress(g, e) for g, e in zip(flat_g, flat_e)]
            grads = jax.tree_util.tree_unflatten(tdef, [p[0] for p in pairs])
            e_local = jax.tree_util.tree_unflatten(tdef,
                                                   [p[1] for p in pairs])
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.psum(g, "dp") / dp, grads)
        elif getattr(strategy, "int8_allreduce", False) \
                and not use_localsgd:
            # (localsgd defines its OWN communication schedule — the
            # periodic param average — so int8_allreduce must not
            # reintroduce per-step grad sync under it)
            # EQuARX-pattern compressed gradient sync: int8 blockwise
            # reduce-scatter + all-gather in place of the f32 psum —
            # BUCKETED (r5): small leaves ride the compressed path and
            # each bucket is an independent collective the scheduler can
            # overlap with the rest of the backward (reference reducer)
            from ..collective import bucketed_quantized_all_reduce
            grads = jax.tree_util.tree_map(
                lambda g: g / dp,
                bucketed_quantized_all_reduce(grads, "dp"))
        elif not use_localsgd:
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, "dp"), grads)
        # localsgd: NO grad sync — the collective is the periodic param avg
        new_p, new_s = optimizer.functional_update(p_local, grads, s_local)
        if use_localsgd:
            do_avg = (step_ct % k_steps) == (k_steps - 1)
            new_p = jax.lax.cond(
                do_avg,
                lambda p: jax.tree_util.tree_map(
                    lambda v: jax.lax.pmean(v, "dp"), p),
                lambda p: p, new_p)
        if stack_pi:
            new_p, new_s = _relocal(new_p), _relocal(new_s)
        if stack_err:
            e_local = _relocal(e_local)
        return jax.lax.pmean(loss, "dp"), new_p, new_s, e_local

    def step(params, opt_state, batch, key):
        inner = opt_state["inner"]
        err = opt_state["dgc_err"]
        ct = opt_state["step"]
        rep = P()
        pi_spec = lambda tree: jax.tree_util.tree_map(  # noqa: E731
            lambda v: P("dp", *([None] * (v.ndim - 1))) if stack_pi else rep,
            tree)
        err_spec = jax.tree_util.tree_map(
            lambda v: P("dp", *([None] * (v.ndim - 1))) if stack_err else rep,
            err)
        b_spec = jax.tree_util.tree_map(
            lambda x: P("dp", *([None] * (x.ndim - 1))), batch)
        loss, new_p, new_s, new_err = shard_map(
            local_step, mesh=mesh,
            in_specs=(pi_spec(params), pi_spec(inner), err_spec, rep,
                      b_spec, rep),
            out_specs=(rep, pi_spec(params), pi_spec(inner), err_spec),
            check_vma=False)(params, inner, err, ct, batch, key)
        return loss, new_p, {"inner": new_s, "dgc_err": new_err,
                             "step": ct + 1}

    def init_opt_state(params):
        """Build (params_for_step, opt_state): step counter + dgc error
        buffers; localsgd stacks params/slots to one copy per dp worker."""
        inner = optimizer.functional_init(params)
        if use_dgc:  # per-worker residuals: [dp, ...] per param leaf
            err = jax.tree_util.tree_map(
                lambda v: jnp.zeros((dp,) + v.shape, v.dtype), params)
        else:        # unused placeholder, keeps the opt_state pytree static
            err = jax.tree_util.tree_map(
                lambda v: jnp.zeros((), v.dtype), params)
        p = params
        if stack_pi:
            p, inner = _stack(params), _stack(inner)
        return p, {"inner": inner, "dgc_err": err,
                   "step": jnp.zeros((), jnp.int32)}

    def compile_for(params, batch, opt_state=None):
        p_sh = jax.tree_util.tree_map(
            lambda v: NamedSharding(
                mesh, P("dp", *([None] * (v.ndim - 1))) if stack_pi else P()),
            params)
        b_sh = jax.tree_util.tree_map(
            lambda x: NamedSharding(mesh, P("dp", *([None] * (x.ndim - 1)))),
            batch)
        return jax.jit(step, in_shardings=(p_sh, None, b_sh, None),
                       out_shardings=None, donate_argnums=(0, 1))

    step.compile_for = compile_for
    step.init_opt_state = init_opt_state
    step.mesh = mesh
    return step, mesh


def applied_mechanisms(strategy):
    """Which strategy flags are active and the XLA mechanism each lowers
    to (ref: fleet_base._get_applied_meta_list naming the meta-optimizer
    classes; here the mechanisms are declarative, not graph passes)."""
    out = []
    if strategy is None:
        return out
    if getattr(strategy, "amp", False):
        out.append("AMPOptimizer->bf16_compute_policy")
    if getattr(strategy, "recompute", False):
        out.append("RecomputeOptimizer->jax.checkpoint")
    if getattr(strategy, "sharding", False):
        out.append("ShardingOptimizer->zero_param_sharding")
    if getattr(strategy, "gradient_merge", False):
        out.append("GradientMergeOptimizer->microbatch_scan")
    if getattr(strategy, "pipeline", False):
        out.append("PipelineOptimizer->pp_mesh_axis_gpipe")
    if getattr(strategy, "localsgd", False):
        out.append("LocalSGDOptimizer->periodic_psum_average")
    if getattr(strategy, "dgc", False):
        out.append("DGCMomentumOptimizer->topk_grad_compression")
    if getattr(strategy, "int8_allreduce", False):
        out.append("Int8AllReduce->quantized_reduce_scatter_all_gather")
    if getattr(strategy, "lamb", False):
        out.append("LambOptimizer->lamb_rule")
    if getattr(strategy, "lars", False):
        out.append("LarsOptimizer->lars_rule")
    return out
