"""Recsys training with PS-lite: a huge sparse embedding table lives in
host RAM (the TPU-native parameter server), the dense tower trains on
device; readers feed slot-format data.

Run: JAX_PLATFORMS=cpu python examples/recsys_ps.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax
    # a CPU example: pin the platform before any backend initialises, so
    # it never takes the chip from the process that should hold it
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.distributed.ps import PSEmbedding

    paddle.seed(0)
    emb = PSEmbedding(100_000, 16, learning_rate=0.5)  # host-resident
    tower = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 1))
    opt = paddle.optimizer.Adam(learning_rate=0.01,
                                parameters=tower.parameters())

    rng = np.random.RandomState(0)
    ids = rng.randint(0, 100_000, (256,))
    y = (ids % 2).astype(np.float32)[:, None]

    first = last = None
    for step in range(40):
        e = emb(Tensor(jnp.asarray(ids.astype(np.int32))))
        out = tower(e)
        loss = ((out - Tensor(jnp.asarray(y))) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        emb.apply_gradients()  # push sparse grads back to the host table
        last = float(loss.numpy())
        first = first if first is not None else last
        if step % 10 == 0:
            print(f"step {step}: loss {last:.4f}")
    assert last < first * 0.5, (first, last)
    print("OK: sparse table learned through the pull/push cycle")


if __name__ == "__main__":
    main()
