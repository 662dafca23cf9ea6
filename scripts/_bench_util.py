"""Shared timing helpers for the perf scripts.

(a) Completion barriers fetch a reduced scalar via device_get, so the
timed region ends when the device does; (b) kernels are timed as `inner`
carry-dependent iterations inside ONE jitted lax.scan (the carry
dependence defeats CSE/hoisting, and one dispatch is amortized over
`inner` iterations)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def sync(out):
    leaves = jax.tree_util.tree_leaves(out)
    float(jax.device_get(jnp.sum(leaves[0]).astype(jnp.float32)))


def gpt2_amp_setup():
    """Shared GPT-2-small AMP harness for the perf sections: returns
    (cfg, params0, amp_loss, make_data) with the bf16-compute /
    f32-master recipe of the benchmark's train cells, so every sweep
    measures one configuration."""
    import numpy as np

    from paddle_tpu.models.gpt2 import GPT2Config, build_train_step

    cfg = GPT2Config()
    cfg.dropout = 0.0
    loss_fn, init_params, _ = build_train_step(cfg, remat=False)
    params0 = init_params()

    def _to_bf16(x):
        return x.astype(jnp.bfloat16) \
            if jnp.issubdtype(x.dtype, jnp.floating) else x

    def amp_loss(p32, data, key):
        pb = jax.tree_util.tree_map(_to_bf16, p32)
        return loss_fn(pb, data, key).astype(jnp.float32)

    rng = np.random.RandomState(0)

    def make_data(batch, seq=1024):
        return {
            "input_ids": jnp.asarray(rng.randint(
                0, cfg.vocab_size, (batch, seq)).astype(np.int32)),
            "labels": jnp.asarray(rng.randint(
                0, cfg.vocab_size, (batch, seq)).astype(np.int32)),
        }

    return cfg, params0, amp_loss, make_data


def scan_time_args(step, carry0, args, inner=20, reps=3):
    """scan_time with large operands threaded as EXPLICIT jit arguments.
    Closure-captured arrays lower as literal constants in the HLO
    (model-sized programs, slow compiles) — pass them here instead.
    step: (carry, args) -> carry."""

    @jax.jit
    def many(c0, a):
        c, _ = jax.lax.scan(lambda c, _: (step(c, a), None), c0,
                            None, length=inner)
        return c

    sync(many(carry0, args))  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        sync(many(carry0, args))
        best = min(best, (time.perf_counter() - t0) / inner)
    return best


def scan_time(step_of_carry, carry0, inner=20, reps=3):
    """Best per-iteration wall time of `inner` chained iterations in one
    dispatch. step_of_carry: carry -> carry (make the compute depend on
    the carry, e.g. x + carry * 1e-30)."""

    @jax.jit
    def many(c0):
        c, _ = jax.lax.scan(lambda c, _: (step_of_carry(c), None), c0,
                            None, length=inner)
        return c

    sync(many(carry0))  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        sync(many(carry0))
        best = min(best, (time.perf_counter() - t0) / inner)
    return best
