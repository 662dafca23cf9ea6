"""4-D hybrid GPT-2: dp×pp×mp×sp ALL > 1 on one mesh (VERDICT r1 #2).

Needs 16 virtual devices; tests/conftest.py materializes 8 by default, so
this file spawns no mesh when fewer than 16 exist — __graft_entry__'s
dryrun bumps jax_num_cpu_devices to 16 when it controls the platform. To
still exercise the full composition in CI we run a subprocess with its own
device count.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

_SCRIPT = r"""
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
from paddle_tpu.models.gpt2_hybrid import (
    build_hybrid_gpt2_loss, hybrid_shardings, init_hybrid_gpt2_params,
    reference_loss)
from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu import optimizer as opt_mod

mesh = make_mesh(dp=2, mp=2, pp=2, sp=2)
assert all(mesh.shape[a] > 1 for a in ("dp", "pp", "mp", "sp"))
# vocab 129 is NOT divisible by mp=2: exercises Megatron vocab padding +
# masked softmax stats; d_head=32 and S_local=128 pass _flash_ok so the
# ring runs the Pallas flash kernels (interpret-mode on CPU)
VOCAB = 129
import functools
params = init_hybrid_gpt2_params(
    jax.random.key(0), vocab_size=VOCAB, hidden=128, num_heads=4,
    num_layers=4, pp=2, max_position=256, mp=2)
assert params["wte"].shape[0] == 130  # padded to a multiple of mp
rng = np.random.RandomState(0)
batch = {"input_ids": jnp.asarray(rng.randint(0, VOCAB, (8, 256), np.int32)),
         "labels": jnp.asarray(rng.randint(0, VOCAB, (8, 256), np.int32))}

loss_fn = build_hybrid_gpt2_loss(mesh, num_microbatches=2, vocab_size=VOCAB)
ref_fn = functools.partial(reference_loss, vocab_size=VOCAB)
ref = float(jax.jit(ref_fn)(params, batch))
hyb = float(jax.jit(loss_fn)(params, batch))
assert abs(ref - hyb) < 1e-3 * max(1.0, abs(ref)), (ref, hyb)
from paddle_tpu.parallel.ring_attention import last_impl_used
assert last_impl_used() == "flash", last_impl_used()
print("PARITY_OK", ref, hyb)
print("RING_IMPL", last_impl_used())

# full train step with ZeRO slot sharding over dp
optimizer = opt_mod.AdamW(learning_rate=1e-3, weight_decay=0.0)
opt_state = optimizer.functional_init(params)
p_sh, os_sh = hybrid_shardings(mesh, params, opt_state)
wte_m = opt_state["slots"]["wte"]

def step(params, opt_state, batch):
    loss, grads = jax.value_and_grad(loss_fn)(params, batch)
    new_p, new_s = optimizer.functional_update(params, grads, opt_state)
    return loss, new_p, new_s

jitted = jax.jit(step, in_shardings=(p_sh, os_sh, None),
                 out_shardings=(None, p_sh, os_sh))
params = jax.device_put(params, p_sh)
opt_state = jax.device_put(opt_state, os_sh)
l0 = None
for i in range(4):
    loss, params, opt_state = jitted(params, opt_state, batch)
    if l0 is None:
        l0 = float(loss)
# wte is vocab-parallel now: its slots follow the mp sharding; ZeRO-over-dp
# applies to the remaining big replicated leaves (wpe)
slot = list(opt_state["slots"]["wte"].values())[0]
assert "mp" in str(slot.sharding.spec), slot.sharding
wpe_slot = list(opt_state["slots"]["wpe"].values())[0]
assert "dp" in str(wpe_slot.sharding.spec), wpe_slot.sharding
assert float(loss) < l0, (l0, float(loss))
print("TRAIN_OK", l0, float(loss))

# grads parity: hybrid grads == reference grads on the embedding
g_h = jax.grad(loss_fn)(jax.device_get(params), batch)
g_r = jax.grad(ref_fn)(jax.device_get(params), batch)
d = float(jnp.max(jnp.abs(g_h["wte"] - g_r["wte"])))
scale = float(jnp.max(jnp.abs(g_r["wte"]))) + 1e-9
assert d / scale < 5e-3, (d, scale)
print("GRAD_OK", d, scale)

# zigzag sp inside the SAME 4D composition: the batch and positions go to
# zigzag layout; mean CE is permutation-invariant so the loss must match
# the reference on the unpermuted batch, and wte grads likewise
from paddle_tpu.parallel.ring_attention import zigzag_order
zz_loss = build_hybrid_gpt2_loss(mesh, num_microbatches=2,
                                 ring_impl="zigzag", vocab_size=VOCAB)
perm = np.asarray(zigzag_order(mesh.shape["sp"], 256))
zz_batch = {"input_ids": batch["input_ids"][:, perm],
            "labels": batch["labels"][:, perm]}
host_params = jax.device_get(params)
zz = float(jax.jit(zz_loss)(host_params, zz_batch))
ref2 = float(jax.jit(ref_fn)(host_params, batch))
assert abs(zz - ref2) < 1e-3 * max(1.0, abs(ref2)), (zz, ref2)
# reuse g_r/scale: same params (host_params is the tensor g_r used), so
# no need to recompute the reference backward
g_z = jax.grad(zz_loss)(host_params, zz_batch)
dz = float(jnp.max(jnp.abs(g_z["wte"] - g_r["wte"])))
assert dz / scale < 5e-3, (dz, scale)
print("ZIGZAG_OK", zz, ref2)

# circular-interleaved pipeline schedule inside the SAME 4D composition
# (VERDICT r4 next #5): num_layers=4, pp=2 -> V=2 chunks/rank; exact
# parity vs the meshless reference AND the GPipe loss, fwd + wte grads
il_loss = build_hybrid_gpt2_loss(mesh, num_microbatches=2,
                                 vocab_size=VOCAB,
                                 pp_schedule="interleaved", num_virtual=2)
il = float(jax.jit(il_loss)(host_params, batch))
assert abs(il - ref2) < 1e-3 * max(1.0, abs(ref2)), (il, ref2)
g_i = jax.grad(il_loss)(host_params, batch)
di = float(jnp.max(jnp.abs(g_i["wte"] - g_r["wte"])))
assert di / scale < 5e-3, (di, scale)
# block-param grads must match too (the interleaved regroup reshapes
# them; a placement bug would show here, not in wte)
db = float(jnp.max(jnp.abs(g_i["blk.w1"] - g_r["blk.w1"])))
sb = float(jnp.max(jnp.abs(g_r["blk.w1"]))) + 1e-9
assert db / sb < 5e-3, (db, sb)
print("INTERLEAVED_OK", il, ref2)
"""


def test_4d_hybrid_parity_and_training():
    env = dict(os.environ)
    # 16 virtual devices via XLA flag: it must be in the environment
    # before the subprocess imports jax
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "PARITY_OK" in r.stdout, r.stdout + "\n" + r.stderr[-4000:]
    assert "RING_IMPL flash" in r.stdout, r.stdout + "\n" + r.stderr[-4000:]
    assert "TRAIN_OK" in r.stdout, r.stdout + "\n" + r.stderr[-4000:]
    assert "GRAD_OK" in r.stdout, r.stdout + "\n" + r.stderr[-4000:]
    assert "ZIGZAG_OK" in r.stdout, r.stdout + "\n" + r.stderr[-4000:]
    assert "INTERLEAVED_OK" in r.stdout, r.stdout + "\n" + r.stderr[-4000:]
