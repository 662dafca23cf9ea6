"""Reference module-PATH parity (r4): real 1.x/2.0 user code imports
specific submodules (`from paddle.fluid.param_attr import ParamAttr`,
`import paddle.device`, `from paddle.optimizer.adam import Adam`), not
just the package roots the __all__/attribute audit covers. These tests
pin the paths found missing by the round-4 module-tree diff against
/root/reference/python/paddle."""
import numpy as np
import pytest

import paddle_tpu as paddle


class TestModulePaths:
    def test_user_facing_module_paths_import(self):
        import importlib
        for mod in [
            "device",
            "amp.grad_scaler",
            "optimizer.adam", "optimizer.adamw", "optimizer.sgd",
            "optimizer.momentum", "optimizer.rmsprop", "optimizer.lamb",
            "optimizer.adagrad", "optimizer.adadelta", "optimizer.adamax",
            "nn.decode",
            "static.input",
            "utils.install_check",
            "reader.decorator",
            "tensor.attribute", "tensor.logic", "tensor.stat",
            "tensor.tensor", "tensor.to_string",
            "fluid.param_attr", "fluid.data_feeder", "fluid.lod_tensor",
            "fluid.input", "fluid.reader", "fluid.layer_helper",
            "fluid.layer_helper_base",
            "distributed.utils", "distributed.cloud_utils",
            "onnx.export",
            "hapi.progressbar", "hapi.dynamic_flops",
            "distributed.fleet.utils", "distributed.fleet.utils.fs",
            "nn.layer.distance", "nn.layer.extension", "nn.layer.vision",
            "nn.utils.weight_norm_hook", "nn.functional.transformer",
            "distributed.fleet.cloud_utils",
            "distributed.fleet.launch_utils", "distributed.fleet.launch",
            "fluid.dataloader", "fluid.dataloader.dataset",
            "fluid.dataloader.sampler", "fluid.dataloader.batch_sampler",
            "fluid.transpiler", "fluid.transpiler.distribute_transpiler",
            "text.datasets.imdb", "text.datasets.wmt16",
            "fluid.layers.utils",
        ]:
            importlib.import_module(f"paddle_tpu.{mod}")

    def test_classic_from_imports(self):
        from paddle_tpu.amp.grad_scaler import GradScaler  # noqa: F401
        from paddle_tpu.device import get_device
        from paddle_tpu.fluid.param_attr import ParamAttr  # noqa: F401
        from paddle_tpu.optimizer.adam import Adam  # noqa: F401
        from paddle_tpu.tensor.stat import mean  # noqa: F401
        assert isinstance(get_device(), str)

    def test_explicit_place_must_exist(self, monkeypatch):
        """A device the caller names explicitly is an error when this host
        lacks the kind, or the index is past its last device — never
        another device handed back quietly (the default place may still
        choose by what exists)."""
        import pytest

        from paddle_tpu.core import place

        before = place._expected_place()
        with pytest.raises(RuntimeError, match="no tpu device"):
            place.set_device("tpu")  # this test host is CPU-only
        with pytest.raises(RuntimeError, match="no tpu device"):
            place.TPUPlace(0).jax_device()

        class FakeChip:
            platform = "tpu"

        chips = [FakeChip(), FakeChip()]
        monkeypatch.setattr(place.jax, "devices", lambda: chips)
        assert place.TPUPlace(1).jax_device() is chips[1]
        with pytest.raises(ValueError, match="past the last"):
            place.TPUPlace(2).jax_device()
        with pytest.raises(ValueError, match="past the last"):
            place.set_device("tpu:3")
        assert place._expected_place() is before  # a refused device sticks nowhere

    def test_nest_utils(self):
        from paddle_tpu.fluid.layers.utils import flatten, map_structure, \
            pack_sequence_as

        s = {"a": [1, 2], "b": (3,)}
        fl = flatten(s)
        assert fl == [1, 2, 3]
        assert pack_sequence_as(s, [x * 2 for x in fl]) == \
            {"a": [2, 4], "b": (6,)}
        assert map_structure(lambda x: x + 1, s)["b"] == (4,)

    def test_dtype_predicates(self):
        t = paddle.to_tensor(np.ones(3, np.float32))
        assert bool(paddle.is_floating_point(t))
        assert not bool(paddle.is_integer(t))
        assert not bool(paddle.is_complex(t))
        i = paddle.to_tensor(np.ones(3, np.int32))
        assert bool(paddle.is_integer(i))


class TestPyReader:
    def test_batch_generator_feeds_static_executor(self):
        paddle.enable_static()
        try:
            import paddle_tpu.static as static
            from paddle_tpu.fluid.reader import PyReader

            main, startup = static.Program(), static.Program()
            with static.program_guard(main, startup):
                x = static.data("x", [-1, 4], "float32")
                y = static.data("y", [-1, 1], "float32")
                pred = static.nn.fc(x, 1)
                loss = paddle.mean((pred - y) ** 2)
                paddle.optimizer.SGD(0.1).minimize(loss)

            reader = PyReader(feed_list=[x, y], capacity=8)
            rng = np.random.RandomState(0)

            def gen():
                for _ in range(4):
                    xb = rng.rand(8, 4).astype(np.float32)
                    yield xb, xb.sum(1, keepdims=True).astype(np.float32)

            reader.decorate_batch_generator(gen)
            exe = static.Executor()
            exe.run(startup)
            losses = [float(np.asarray(exe.run(main, feed=d,
                                               fetch_list=[loss])[0]))
                      for d in reader()]
            assert len(losses) == 4 and losses[-1] < losses[0]
        finally:
            paddle.disable_static()

    def test_sample_generators(self):
        from paddle_tpu.fluid.reader import PyReader

        r = PyReader(return_list=True)
        r.decorate_sample_generator(
            lambda: iter([(np.ones(2), np.zeros(1))] * 5), batch_size=2,
            drop_last=True)
        batches = list(r())
        assert len(batches) == 2 and batches[0][0].shape == (2, 2)

        r2 = PyReader(return_list=True)
        r2.decorate_sample_list_generator(
            lambda: iter([[(np.ones(2),), (np.ones(2),)]]))
        assert list(r2())[0][0].shape == (2, 2)

    def test_non_iterable_raises_with_guidance(self):
        from paddle_tpu.fluid.reader import PyReader

        r = PyReader(iterable=False)
        with pytest.raises(NotImplementedError, match="iterable=True"):
            r.start()


class TestLayerHelper:
    def test_eager_custom_layer(self):
        from paddle_tpu.fluid.layer_helper import LayerHelper

        h = LayerHelper("my_fc", act="relu")
        w = h.create_parameter(shape=[4, 3], dtype="float32")
        x = paddle.to_tensor(-np.ones((2, 4), np.float32))
        out = h.append_activation(h.append_op(
            type="matmul", inputs={"X": [x], "Y": [w]},
            outputs={"Out": [None]}))
        assert out.shape == [2, 3]

    def test_static_custom_layer(self):
        paddle.enable_static()
        try:
            import paddle_tpu.static as static
            from paddle_tpu.fluid.layer_helper import LayerHelper

            main, startup = static.Program(), static.Program()
            with static.program_guard(main, startup):
                x = static.data("x", [-1, 4], "float32")
                h = LayerHelper("fc2")
                w = h.create_parameter(shape=[4, 3], dtype="float32")
                out = h.append_op(type="matmul",
                                  inputs={"X": [x], "Y": [w]},
                                  outputs={"Out": [None]})
            exe = static.Executor()
            exe.run(startup)
            r, = exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                         fetch_list=[out])
            assert np.asarray(r).shape == (2, 3)
        finally:
            paddle.disable_static()

    def test_unknown_op_raises_with_guidance(self):
        from paddle_tpu.fluid.layer_helper import LayerHelper

        with pytest.raises(NotImplementedError, match="paddle_tpu.ops"):
            LayerHelper("x").append_op(type="definitely_not_an_op")


class TestClusterUtils:
    def test_get_cluster_tree(self):
        from paddle_tpu.distributed.utils import find_free_ports, \
            get_cluster

        c, pod = get_cluster(["10.0.0.1", "10.0.0.2"], "10.0.0.2",
                             ["10.0.0.1:6170", "10.0.0.2:6170"], [0])
        assert c.trainers_nranks() == 2
        assert pod.addr == "10.0.0.2"
        assert c.trainers_endpoints() == ["10.0.0.1:6170", "10.0.0.2:6170"]
        assert len(find_free_ports(3)) == 3

    def test_cloud_cluster_from_env(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TRAINER_ENDPOINTS",
                           "10.0.0.1:6170,10.0.0.2:6170")
        monkeypatch.setenv("POD_IP", "10.0.0.1")
        from paddle_tpu.distributed.cloud_utils import get_cloud_cluster

        c, pod = get_cloud_cluster()
        assert c.trainers_nranks() == 2 and pod.addr == "10.0.0.1"
