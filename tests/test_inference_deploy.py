"""Inference deployment format (VERDICT r2 next #3, carried from r1):
jit.save serializes the traced forward as StableHLO (jax.export) +
params npz; jit.load / create_predictor(Config) rebuild a runnable
Predictor in a FRESH PROCESS with no model-class import.

Ref: python/paddle/fluid/io.py:1198 save_inference_model,
paddle/fluid/inference/api/analysis_predictor.cc.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.static import InputSpec


class _Net(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(4, 16)
        self.fc2 = nn.Linear(16, 3)

    def forward(self, x):
        return self.fc2(paddle.nn.functional.relu(self.fc1(x)))


def _save_net(tmp_path):
    paddle.seed(11)
    net = _Net()
    net.eval()
    prefix = str(tmp_path / "deploy" / "inference")
    import os
    os.makedirs(str(tmp_path / "deploy"), exist_ok=True)
    paddle.jit.save(net, prefix,
                    input_spec=[InputSpec([None, 4], "float32")])
    x = np.random.RandomState(0).randn(5, 4).astype(np.float32)
    ref = np.asarray(net(Tensor(jnp.asarray(x))).numpy())
    return prefix, x, ref


class TestJitSaveLoad:
    def test_artifacts_exist_and_model_is_stablehlo(self, tmp_path):
        prefix, x, ref = _save_net(tmp_path)
        import os
        assert os.path.exists(prefix + ".pdmodel")
        assert os.path.exists(prefix + ".pdiparams")
        with open(prefix + ".pdmodel", "rb") as f:
            assert f.read(8) == b"PTPUEXP1"
        # params archive is plain npz, no pickles
        with open(prefix + ".pdiparams", "rb") as f:
            npz = np.load(f, allow_pickle=False)
            assert any(k.startswith("p:") for k in npz.files)

    def test_load_runs_without_model_class(self, tmp_path):
        prefix, x, ref = _save_net(tmp_path)
        loaded = paddle.jit.load(prefix)
        out = np.asarray(loaded(Tensor(jnp.asarray(x))).numpy())
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
        # batch-polymorphic: a different batch size runs too
        x2 = np.random.RandomState(1).randn(9, 4).astype(np.float32)
        out2 = loaded(Tensor(jnp.asarray(x2)))
        assert tuple(out2.shape) == (9, 3)

    def test_multiple_dynamic_dims_share_one_scope(self, tmp_path):
        """code-review r3: per-dim symbolic scopes broke any model with
        2+ dynamic dims (jax.export rejects scope mixing)."""
        paddle.seed(5)
        net = _Net()
        net.eval()
        prefix = str(tmp_path / "dyn2")
        paddle.jit.save(net, prefix,
                        input_spec=[InputSpec([None, None, 4], "float32")])
        loaded = paddle.jit.load(prefix)
        for b, s in ((2, 3), (5, 7)):
            x = np.random.rand(b, s, 4).astype(np.float32)
            out = loaded(Tensor(jnp.asarray(x)))
            assert tuple(out.shape) == (b, s, 3)

    def test_save_load_with_buffers_batchnorm(self, tmp_path):
        """BN running stats are buffers: they must ship in the artifact and
        drive the eval-mode normalization after load."""
        paddle.seed(6)
        net = nn.Sequential(nn.Linear(4, 8), nn.BatchNorm1D(8),
                            nn.Linear(8, 2))
        rng = np.random.RandomState(3)
        net.train()
        for _ in range(4):  # move the running stats off their init
            net(Tensor(jnp.asarray(
                (rng.randn(16, 4) * 3 + 1).astype(np.float32))))
        net.eval()
        prefix = str(tmp_path / "bn")
        paddle.jit.save(net, prefix,
                        input_spec=[InputSpec([None, 4], "float32")])
        x = rng.randn(5, 4).astype(np.float32)
        ref = np.asarray(net(Tensor(jnp.asarray(x))).numpy())
        loaded = paddle.jit.load(prefix)
        out = np.asarray(loaded(Tensor(jnp.asarray(x))).numpy())
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_save_requires_input_spec(self, tmp_path):
        with pytest.raises(ValueError, match="input_spec"):
            paddle.jit.save(_Net(), str(tmp_path / "m"))

    def test_cross_process_predictor_no_model_import(self, tmp_path):
        """The deployment contract: a fresh process with ONLY the artifact
        files must rebuild and run the model — no test module, no
        paddle_tpu.models import."""
        prefix, x, ref = _save_net(tmp_path)
        np.save(str(tmp_path / "x.npy"), x)
        script = textwrap.dedent(f"""
            import jax; jax.config.update("jax_platforms", "cpu")
            import sys
            import numpy as np
            from paddle_tpu.inference import Config, create_predictor
            cfg = Config({str(prefix)!r} + ".pdmodel",
                         {str(prefix)!r} + ".pdiparams")
            pred = create_predictor(cfg)
            x = np.load({str(tmp_path / "x.npy")!r})
            out = pred.run([x])
            # the model class lives in the test module: must not be loaded
            assert not any("test_inference_deploy" in m for m in sys.modules), \\
                "model-class module leaked into the fresh process"
            assert "paddle_tpu.models" not in sys.modules
            np.save({str(tmp_path / "out.npy")!r}, np.asarray(out.numpy()))
            print("CROSS_PROCESS_OK")
        """)
        env = {"PYTHONPATH": ".", "PATH": "/usr/bin:/bin",
               "JAX_PLATFORMS": "cpu",
               "HOME": os.environ.get("HOME", "/tmp")}
        r = subprocess.run([sys.executable, "-c", script], text=True,
                           capture_output=True, timeout=240, env=env,
                           cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert "CROSS_PROCESS_OK" in r.stdout, (r.stdout, r.stderr[-2000:])
        out = np.load(str(tmp_path / "out.npy"))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_handle_based_predictor_flow(self, tmp_path):
        """The reference's zero-copy handle flow: copy_from_cpu -> run() ->
        copy_to_cpu."""
        prefix, x, ref = _save_net(tmp_path)
        from paddle_tpu.inference import Config, create_predictor
        pred = create_predictor(Config(prefix + ".pdmodel",
                                       prefix + ".pdiparams"))
        names = pred.get_input_names()
        h = pred.get_input_handle(names[0])
        h.copy_from_cpu(x)
        assert pred.run()
        out = pred.get_output_handle(pred.get_output_names()[0]).copy_to_cpu()
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


class TestHapiDeploy:
    def test_model_save_training_false_is_deployable(self, tmp_path):
        """hapi Model.save(training=False) emits the StableHLO artifact;
        a Predictor rebuilds it without the network class."""
        paddle.seed(8)
        net = _Net()
        model = paddle.Model(net, inputs=[InputSpec([None, 4], "float32")])
        prefix = str(tmp_path / "hapi_deploy")
        model.save(prefix, training=False)
        x = np.random.RandomState(4).randn(3, 4).astype(np.float32)
        net.eval()
        ref = np.asarray(net(Tensor(jnp.asarray(x))).numpy())
        from paddle_tpu.inference import Config, create_predictor
        pred = create_predictor(Config(prefix + ".pdmodel",
                                       prefix + ".pdiparams"))
        out = pred.run([x])
        np.testing.assert_allclose(np.asarray(out.numpy()), ref,
                                   rtol=1e-5, atol=1e-5)

    def test_model_save_training_false_requires_inputs(self, tmp_path):
        model = paddle.Model(_Net())
        with pytest.raises(ValueError, match="inputs"):
            model.save(str(tmp_path / "x"), training=False)


class TestFlagshipDeploy:
    def test_gpt2_tiny_save_load_parity(self, tmp_path):
        """The flagship transformer (embeddings + attention + tied logits)
        must survive the StableHLO round-trip — the full deployment story,
        not just MLPs."""
        from paddle_tpu.models.gpt2 import GPT2, GPT2Config
        paddle.seed(13)
        model = GPT2(GPT2Config.tiny())
        model.eval()
        prefix = str(tmp_path / "gpt2")
        # batch-polymorphic: transformer reshapes on the symbolic batch dim
        paddle.jit.save(model, prefix,
                        input_spec=[InputSpec([None, 64], "int64")])
        ids = np.random.RandomState(6).randint(0, 1024, (2, 64)) \
            .astype(np.int64)
        ref = np.asarray(model(Tensor(jnp.asarray(ids))).numpy())
        loaded = paddle.jit.load(prefix)
        out = np.asarray(loaded(Tensor(jnp.asarray(ids))).numpy())
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
        out5 = loaded(Tensor(jnp.asarray(
            np.tile(ids, (3, 1))[:5])))  # a different batch size runs
        assert tuple(out5.shape)[0] == 5


class TestQuantizedDeploy:
    def test_save_quantized_model_roundtrip(self, tmp_path):
        """slim.save_quantized_model rides the same artifact path: the int8
        weights are baked into the StableHLO module as constants."""
        from paddle_tpu.slim import ImperativeQuantAware
        paddle.seed(3)
        net = _Net()
        qat = ImperativeQuantAware()
        qat.quantize(net)
        x = np.random.RandomState(2).randn(6, 4).astype(np.float32)
        net(Tensor(jnp.asarray(x)))  # collect activation ranges
        prefix = str(tmp_path / "quant")
        qat.save_quantized_model(net, prefix,
                                 input_spec=[InputSpec([None, 4],
                                                       "float32")])
        ref = np.asarray(net(Tensor(jnp.asarray(x))).numpy())
        loaded = paddle.jit.load(prefix)
        out = np.asarray(loaded(Tensor(jnp.asarray(x))).numpy())
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


class TestInferenceAuxSurface:
    def test_enums_helpers_and_pool(self, tmp_path):
        """r4: DataType/PlaceType/PrecisionType, get_version,
        get_num_bytes_of_data_type, PredictorPool (ref:
        paddle/inference/__init__.py export list)."""
        from paddle_tpu import inference as infer
        assert infer.get_num_bytes_of_data_type("float32") == 4
        assert infer.get_num_bytes_of_data_type("bfloat16") == 2
        assert infer.get_num_bytes_of_data_type("int8") == 1
        assert "paddle_tpu" in infer.get_version()
        assert infer.PrecisionType.Int8 == 2
        assert infer.DataType.FLOAT32 == "float32"
        prefix, x, ref = _save_net(tmp_path)
        pool = infer.PredictorPool(
            infer.Config(prefix + ".pdmodel", prefix + ".pdiparams"), 2)
        assert len(pool) == 2
        for i in range(2):
            p = pool.retrive(i)  # reference spelling
            h = p.get_input_handle(p.get_input_names()[0])
            h.copy_from_cpu(x)
            p.run()
            out = p.get_output_handle(p.get_output_names()[0]).copy_to_cpu()
            np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
        with pytest.raises(ValueError):
            infer.PredictorPool(
                infer.Config(prefix + ".pdmodel", prefix + ".pdiparams"), 0)


def test_bf16_artifact_roundtrip(tmp_path):
    """jit.save/load of a BF16 model — the recommended serving dtype.
    npz writes extension dtypes as raw '|V2' void; the artifact stores a
    bit-preserving view + dtype sidecar and views back on load (this was
    broken before r4: Exported.call rejected the void arrays)."""
    import ml_dtypes

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import jit

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    net.eval()
    net.to(dtype="bfloat16")
    prefix = str(tmp_path / "m_bf16")
    jit.save(net, prefix,
             input_spec=[paddle.static.InputSpec([-1, 4], "bfloat16")])
    served = jit.load(prefix)
    x = np.ones((2, 4), np.float32).astype(ml_dtypes.bfloat16)
    out = np.asarray(served(x)._value if hasattr(served(x), "_value")
                     else served(x))
    ref = np.asarray(net(paddle.to_tensor(x)).numpy())
    assert out.astype(np.float32) == pytest.approx(
        ref.astype(np.float32), abs=1e-2)


def test_loaded_artifact_weights_are_device_committed(tmp_path):
    """r5 serving find: jit.load must commit the npz weights to device
    ONCE — host numpy params make jit re-transfer them on EVERY call
    (measured 8x on the exported decode artifact, round 5)."""
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn

    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    net.eval()
    prefix = str(tmp_path / "m")
    paddle.jit.save(net, prefix, input_spec=[
        paddle.static.InputSpec([None, 4], "float32")])
    loaded = paddle.jit.load(prefix)
    leaves = jax.tree_util.tree_leaves(loaded._params)
    assert leaves, "no params in artifact"
    for v in leaves:
        assert isinstance(v, jax.Array), type(v)
