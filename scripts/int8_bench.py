"""Int8 vs bf16 inference throughput on the real chip (VERDICT r3 next #5).

Times the slim int8 inference path (quantize -> int8 dot -> rescale, the
`_QuantedBase` int8 mode) against the same MLP in bf16 and f32, on
MXU-bound shapes (4096-wide Linears). v5e executes int8 dots at 2x the
bf16 MAC rate, so a well-lowered int8 path should land near or above the
bf16 time despite the quantize/rescale overhead; a large regression means
the rescale epilogue is not fusing.

Run on-chip; on CPU it smoke-tests only.
"""
from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp

    from scripts._bench_util import scan_time_args

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.slim import PostTrainingQuantization

    on_tpu = jax.default_backend() not in ("cpu",)
    d, depth, batch = (4096, 4, 512) if on_tpu else (256, 2, 32)
    inner = 20 if on_tpu else 2

    paddle.seed(0)

    class MLP(nn.Layer):
        def __init__(self):
            super().__init__()
            self.layers = nn.LayerList(
                [nn.Linear(d, d) for _ in range(depth)])

        def forward(self, x):
            for lin in self.layers:
                x = paddle.nn.functional.relu(lin(x))
            return x

    rng = np.random.RandomState(0)
    x = rng.randn(batch, d).astype(np.float32)

    def timed_forward(model, xv):
        # weights travel as explicit jit args (closure arrays lower as HLO
        # literals: 268MB of f32 Linears inside the program). The frozen
        # int8 codes
        # (_wq, ~67MB) are plain attributes and still ride the closure,
        # comfortably under the cap.
        p, b = model.functional_state()

        def step(carry, pb):
            out = model.functional_call(
                pb[0], pb[1], Tensor(xv + carry * 1e-30))._value
            return jnp.sum(out).astype(jnp.float32)
        return scan_time_args(step, jnp.float32(0.0), (p, b), inner=inner)

    flops = 2.0 * batch * d * d * depth  # MACs*2 per forward

    results = {}
    # f32 reference
    m32 = MLP()
    m32.eval()
    results["f32"] = timed_forward(m32, jnp.asarray(x))
    # bf16: serving precision
    mbf = MLP()
    mbf.eval()
    mbf.to(dtype="bfloat16")
    results["bf16"] = timed_forward(mbf, jnp.asarray(x, jnp.bfloat16))
    # int8: PTQ-converted
    mint = MLP()
    mint.eval()
    ptq = PostTrainingQuantization(model=mint, algo="abs_max",
                                   weight_quantize_type="abs_max")
    ptq.quantize(data_loader=[(x[:32],)])
    results["int8"] = timed_forward(mint, jnp.asarray(x))

    for kind, dt in results.items():
        tfs = flops / dt / 1e12
        print(f"{kind}: {dt*1e3:.3f} ms/fwd  {tfs:.1f} TF/s  "
              f"backend={jax.default_backend()}")
    print(f"int8/bf16 speed ratio: "
          f"{results['bf16'] / results['int8']:.3f} "
          f"(>1 means int8 faster)")


if __name__ == "__main__":
    main()
