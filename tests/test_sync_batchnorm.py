"""SyncBatchNorm must use CROSS-REPLICA statistics inside an explicit
shard_map region — each shard normalizing by its local batch stats is the
bug this layer exists to prevent (ref: sync_batch_norm_op)."""
import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.parallel.mesh import mesh_guard


def test_sync_bn_matches_global_batch_stats():
    rs = np.random.RandomState(0)
    # deliberately different distributions per shard so local != global
    x = np.concatenate([rs.randn(4, 3, 4, 4).astype(np.float32) + i * 2.0
                        for i in range(8)], axis=0)  # [32, 3, 4, 4]

    bn = paddle.nn.SyncBatchNorm(3)
    bn.train()
    w = bn.weight._value
    b = bn.bias._value

    mesh = Mesh(np.array(jax.devices()[:8]), ("dp",))

    def shard_fn(xs):
        bn_local = paddle.nn.SyncBatchNorm(3)
        bn_local.train()
        bn_local.weight._value = w
        bn_local.bias._value = b
        return bn_local(paddle.Tensor(xs))._value

    with mesh_guard(mesh):
        out = jax.jit(shard_map(shard_fn, mesh=mesh,
                                in_specs=P("dp"), out_specs=P("dp"),
                                check_vma=False))(jnp.asarray(x))

    # reference: plain BN over the FULL batch on one device
    ref_bn = paddle.nn.BatchNorm2D(3)
    ref_bn.train()
    ref_bn.weight._value = w
    ref_bn.bias._value = b
    ref = ref_bn(paddle.to_tensor(x)).numpy()
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


def test_sync_bn_eager_equals_batchnorm():
    rs = np.random.RandomState(1)
    x = rs.randn(8, 5).astype(np.float32)
    sbn = paddle.nn.SyncBatchNorm(5)
    bn = paddle.nn.BatchNorm1D(5)
    for layer in (sbn, bn):
        layer.train()
    sbn.weight._value = bn.weight._value
    sbn.bias._value = bn.bias._value
    a = sbn(paddle.to_tensor(x)).numpy()
    b = bn(paddle.to_tensor(x)).numpy()
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    # running stats updated toward the batch stats
    assert not np.allclose(sbn._mean.numpy(), 0.0)


def test_eager_gradients_flow():
    # SyncBatchNorm is a registered op: eager backward must reach both the
    # affine params and the input (the hand-rolled version regressed this)
    rs = np.random.RandomState(3)
    sbn = paddle.nn.SyncBatchNorm(4)
    sbn.train()
    x = paddle.to_tensor(rs.randn(6, 4).astype(np.float32),
                         stop_gradient=False)
    loss = (sbn(x) ** 2).sum()
    loss.backward()
    assert sbn.weight.grad is not None
    assert np.abs(sbn.weight.grad.numpy()).sum() > 0
    assert x.grad is not None


def test_running_stats_match_batchnorm_unbiased():
    rs = np.random.RandomState(4)
    x = rs.randn(8, 3).astype(np.float32) * 2 + 5
    sbn = paddle.nn.SyncBatchNorm(3)
    bn = paddle.nn.BatchNorm1D(3)
    sbn.train(), bn.train()
    sbn(paddle.to_tensor(x))
    bn(paddle.to_tensor(x))
    np.testing.assert_allclose(sbn._variance.numpy(),
                               bn._variance.numpy(), rtol=1e-5)
    np.testing.assert_allclose(sbn._mean.numpy(), bn._mean.numpy(),
                               rtol=1e-5)


def test_non_dp_axes_not_synced():
    # binding only 'mp' (channel-sharded contexts): stats must stay LOCAL
    # — summing disjoint channels' moments would corrupt them
    rs = np.random.RandomState(5)
    x = np.stack([rs.randn(4, 2).astype(np.float32) + 10 * i
                  for i in range(8)])  # [8, 4, 2] very different shards
    mesh = Mesh(np.array(jax.devices()[:8]), ("mp",))

    def shard_fn(xs):
        sbn = paddle.nn.SyncBatchNorm(2)
        sbn.train()
        return sbn(paddle.Tensor(xs[0]))._value[None]

    out = jax.jit(shard_map(shard_fn, mesh=mesh, in_specs=P("mp"),
                            out_specs=P("mp"), check_vma=False))(
        jnp.asarray(x))
    # each shard normalized by its OWN stats -> every shard has mean ~0
    per_shard_means = np.asarray(out).mean(axis=(1, 2))
    np.testing.assert_allclose(per_shard_means, 0.0, atol=1e-5)


def test_convert_sync_batchnorm_still_works():
    net = paddle.nn.Sequential(paddle.nn.Conv2D(3, 4, 3),
                               paddle.nn.BatchNorm2D(4))
    out = paddle.nn.SyncBatchNorm.convert_sync_batchnorm(net)
    assert isinstance(out[1], paddle.nn.SyncBatchNorm)
