"""Long-context attention: the three sequence-parallel modes side by side.

Shards S=8192 over an 8-device mesh and runs causal attention through
  ring    — ppermute K/V rotation, O(S_local^2 * n) blockwise work
  ulysses — one all-to-all round, heads sharded instead of sequence
  zigzag  — ring in zigzag layout: every rank does equal causal work
            per step (plain causal ring bills all ranks for the last
            rank's full workload)
checking all three against full attention.

CPU timings are indicative only (the modes exist for ICI-connected TPU
meshes); the parity numbers are the point.

Run: python examples/long_context.py   (forces an 8-device CPU mesh)
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax
    try:
        jax.config.update("jax_num_cpu_devices", 8)
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.parallel.ring_attention import (
        ring_attention_sharded, zigzag_ring_attention_sharded)
    from paddle_tpu.parallel.ulysses import ulysses_attention

    n = min(8, jax.device_count())
    mesh = Mesh(np.array(jax.devices()[:n]), ("sp",))
    B, H, S, D = 1, 8, 1024 * n, 64
    rs = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype(np.float32) * 0.1)
               for _ in range(3))

    def full_reference(q, k, v):
        sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (D ** -0.5)
        sc = jnp.where(jnp.tril(jnp.ones((S, S), bool)), sc, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(sc, -1), v)

    ref = full_reference(q, k, v)
    spec = P(None, None, "sp", None)

    def run(label, fn):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        dt = time.perf_counter() - t0
        err = float(jnp.abs(out - ref).max())
        print(f"{label:8s} S={S}  max err vs full attention: {err:.2e}  "
              f"({dt:.2f}s incl. compile)")
        assert err < 5e-4, (label, err)

    run("ring", lambda: ring_attention_sharded(
        q, k, v, mesh, causal=True, impl="chunked"))
    run("ulysses", lambda: shard_map(
        lambda a, b, c: ulysses_attention(a, b, c, axis_name="sp",
                                          causal=True),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
        check_vma=False)(q, k, v))
    run("zigzag", lambda: zigzag_ring_attention_sharded(q, k, v, mesh))
    print(f"OK: three sequence-parallel modes agree at S={S} "
          f"across {n} devices")


if __name__ == "__main__":
    main()
