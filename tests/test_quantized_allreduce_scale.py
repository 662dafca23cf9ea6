"""Quantized all-reduce at scale (VERDICT r4 next #8): 8-process ring,
byte-savings instrumentation, and the bucketed-overlap schedule.

The 8-proc leg proves the collective across REAL process boundaries at
the ring size the reference's DCN path runs at; the HLO tests pin the
two properties that make the compression worth having: int8 (not f32)
on the wire, and per-bucket collectives the scheduler can overlap with
backward compute instead of one barrier at the end.
"""
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


_WORKER8 = textwrap.dedent("""
    import os, sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.distributed.launch import initialize_from_env
    nproc, pid = initialize_from_env()
    assert nproc == 8 and jax.process_count() == 8, jax.process_count()
    assert jax.local_device_count() == 1

    import numpy as np
    import jax.numpy as jnp
    from jax.experimental import multihost_utils
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax import shard_map
    from paddle_tpu.distributed.collective import (
        bucketed_quantized_all_reduce, quantized_all_reduce)

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    rs = np.random.RandomState(pid)
    gl = jnp.asarray(rs.randn(1, 8192).astype(np.float32))
    garr = jax.make_array_from_single_device_arrays(
        (8, 8192), NamedSharding(mesh, P("dp", None)),
        [jax.device_put(gl, jax.local_devices()[0])])
    qout = jax.jit(
        shard_map(lambda x: quantized_all_reduce(x[0], "dp")[None],
                  mesh=mesh, in_specs=P("dp", None),
                  out_specs=P("dp", None), check_vma=False),
        out_shardings=NamedSharding(mesh, P("dp", None)))(garr)
    mine = np.asarray(
        multihost_utils.process_allgather(qout, tiled=True))[pid]
    exact = sum(np.random.RandomState(i).randn(1, 8192)
                for i in range(8))[0]
    qrel = float(np.abs(mine - exact).max() / np.abs(exact).max())
    assert qrel < 2e-2, qrel

    # bucketed variant across the same 8 real processes: a dict tree
    # with a small leaf that per-leaf compression would psum in f32
    tree = {"w": jnp.asarray(rs.randn(64, 64).astype(np.float32)),
            "b": jnp.asarray(rs.randn(17).astype(np.float32))}
    gtree = {k: jax.make_array_from_single_device_arrays(
        (8,) + v.shape, NamedSharding(
            mesh, P("dp", *([None] * v.ndim))),
        [jax.device_put(v[None], jax.local_devices()[0])])
        for k, v in tree.items()}
    tree_specs = jax.tree_util.tree_map(
        lambda v: P("dp", *([None] * (v.ndim - 1))), gtree)
    bout = jax.jit(
        shard_map(
            lambda t: jax.tree_util.tree_map(
                lambda v: v[None],
                bucketed_quantized_all_reduce(
                    jax.tree_util.tree_map(lambda v: v[0], t), "dp")),
            mesh=mesh,
            in_specs=(tree_specs,),
            out_specs=tree_specs,
            check_vma=False))(gtree)
    bmine = {k: np.asarray(multihost_utils.process_allgather(
        v, tiled=True))[pid] for k, v in bout.items()}
    # exacts: each rank drew 8192 then w then b from its seeded rng
    exw = np.zeros((64, 64)); exb = np.zeros((17,))
    for i in range(8):
        r = np.random.RandomState(i)
        r.randn(1, 8192)  # the first draw above
        exw += r.randn(64, 64)
        exb += r.randn(17)
    relw = float(np.abs(bmine["w"] - exw).max() / np.abs(exw).max())
    relb = float(np.abs(bmine["b"] - exb).max() / np.abs(exb).max())
    assert relw < 2e-2 and relb < 2e-2, (relw, relb)

    out_dir = os.environ["TEST_OUT_DIR"]
    with open(os.path.join(out_dir, f"ok_{pid}.txt"), "w") as f:
        f.write("ok")
    print("WORKER_OK", pid, qrel, relw, relb)
""")


@pytest.mark.timeout(600)
@pytest.mark.skip(reason="the pinned jaxlib's CPU backend has no "
                  "multi-process collectives (XlaRuntimeError: "
                  "'Multiprocess computations aren't implemented on the "
                  "CPU backend') — real multi-host/chip only; the "
                  "quantized-ring math is covered in-process by "
                  "TestQuantizedAllReduce on the forced-host mesh")
def test_eight_process_quantized_ring(tmp_path):
    port = _free_port()
    procs = []
    for pid in range(8):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # one CPU device per process
        env.update({
            "JAX_PLATFORMS": "cpu",
            "PADDLE_COORDINATOR": f"127.0.0.1:{port}",
            "PADDLE_TRAINERS_NUM": "8",
            "PADDLE_TRAINER_ID": str(pid),
            "TEST_OUT_DIR": str(tmp_path),
        })
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _WORKER8], env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, out + "\n" + err[-3000:]
        assert "WORKER_OK" in out, out + "\n" + err[-3000:]
    for pid in range(8):
        assert (tmp_path / f"ok_{pid}.txt").exists()


class TestByteSavings:
    def test_wire_bytes_quarter_of_f32(self):
        from paddle_tpu.distributed.collective import \
            quantized_allreduce_wire_bytes
        for size in (1 << 16, 1 << 20, 124_000_000):
            for n in (2, 8, 64):
                c, f = quantized_allreduce_wire_bytes(size, n)
                assert c / f < 0.27, (size, n, c / f)

    def test_int8_on_the_wire_in_hlo(self):
        """The compiled collective must move s8 codes, not f32 — the
        byte savings exist on the wire only if the all_to_all/all_gather
        operands are int8 in the HLO."""
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        from paddle_tpu.distributed.collective import quantized_all_reduce

        mesh = Mesh(np.array(jax.devices()[:8]), ("dp",))
        fn = jax.jit(shard_map(
            lambda x: quantized_all_reduce(x, "dp"),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
        txt = fn.lower(jnp.zeros((1 << 16,), jnp.float32)) \
            .compile().as_text()
        a2a = [ln for ln in txt.splitlines() if "all-to-all" in ln]
        assert a2a, "no all-to-all in compiled HLO"
        assert any("s8" in ln for ln in a2a), a2a[:4]
        # the f32 fallback path must NOT appear for a big tensor: no
        # all-reduce over f32[65536]
        assert not any("all-reduce" in ln and "f32[65536]" in ln
                       for ln in txt.splitlines())


class TestBucketedOverlap:
    def _mlp_loss(self, widths):
        import jax.numpy as jnp

        def loss(params, x, y):
            h = x
            for w in params:
                h = jnp.tanh(h @ w)
            return jnp.mean((h - y) ** 2)
        return loss

    def test_bucketed_emits_independent_collectives(self):
        """Bucketed sync must compile to one collective PER BUCKET (the
        unit the scheduler can overlap), not one barrier collective —
        and the flat variant to exactly one. The schedule itself is
        inspectable in the HLO op order: with buckets, backward dots
        appear BETWEEN collective ops; flat sync puts every dot before
        its single collective."""
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        from paddle_tpu.distributed.collective import (
            bucketed_quantized_all_reduce, quantized_all_reduce)

        mesh = Mesh(np.array(jax.devices()[:8]), ("dp",))
        d = 256
        widths = [d] * 4
        loss = self._mlp_loss(widths)
        params = [jnp.asarray(np.random.RandomState(i).randn(d, d)
                              .astype(np.float32) * 0.1) for i in range(4)]
        x = jnp.zeros((8, d), jnp.float32)
        y = jnp.zeros((8, d), jnp.float32)

        def bucketed(params, x, y):
            g = jax.grad(loss)(params, x, y)
            # bucket_bytes = one layer's grad -> one bucket per layer
            return bucketed_quantized_all_reduce(
                g, "dp", bucket_bytes=d * d * 4)

        def flat(params, x, y):
            g = jax.grad(loss)(params, x, y)
            cat = jnp.concatenate([v.reshape(-1) for v in g])
            return quantized_all_reduce(cat, "dp")

        def compile_text(f):
            return jax.jit(shard_map(
                f, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
                check_vma=False)).lower(params, x, y).compile().as_text()

        txt_b = compile_text(bucketed)
        txt_f = compile_text(flat)

        def a2a_ops(txt):
            # op applications only (tuple-element consumers don't count)
            return [i for i, ln in enumerate(txt.splitlines())
                    if "all-to-all(" in ln and "s8" in ln]

        # 4 buckets -> 4 independent code all-to-alls; flat -> 1
        assert len(a2a_ops(txt_b)) >= 4, len(a2a_ops(txt_b))
        assert len(a2a_ops(txt_f)) <= 2, len(a2a_ops(txt_f))


class TestBucketScaleIsolation:
    def test_tiny_leaf_keeps_precision_next_to_big_weights(self):
        """A 17-element O(1e-4) bias bucketed beside O(1) weight grads
        must NOT share a quantization block (shared abs-max scale would
        turn the bias grad into pure noise) — leaves are block-padded."""
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        from paddle_tpu.distributed.collective import \
            bucketed_quantized_all_reduce

        mesh = Mesh(np.array(jax.devices()[:8]), ("dp",))
        rs = np.random.RandomState(0)
        tree = {"w": jnp.asarray(rs.randn(64, 64).astype(np.float32)),
                "b": jnp.asarray(rs.randn(17).astype(np.float32) * 1e-4)}

        out = jax.jit(shard_map(
            lambda t: bucketed_quantized_all_reduce(t, "dp"),
            mesh=mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: P(), tree),),
            out_specs=jax.tree_util.tree_map(lambda _: P(), tree),
            check_vma=False))(tree)
        # replicated inputs: the sum is 8 * x; the tiny leaf must hold
        # its RELATIVE precision, impossible under a shared O(1) scale
        for k in ("w", "b"):
            rel = float(jnp.max(jnp.abs(out[k] - 8 * tree[k]))
                        / jnp.max(jnp.abs(8 * tree[k])))
            assert rel < 2e-2, (k, rel)
