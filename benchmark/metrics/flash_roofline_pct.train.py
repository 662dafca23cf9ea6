"""Kernels: the least time the chip could take for the step's attention,
forward and backward (the larger of FLOPs over peak and bytes over peak,
from flops.py), over the kernels' device time per traced step."""
import flops
import trace_reduce


def read(obs):
    if obs["peaks"] is None or obs["trace"] is None:
        return None
    sh = obs["shape"]
    f = sh["layers"] * flops.attention_flops_train(
        obs["batch"], sh["heads"], obs["seq"], sh["head_dim"], obs["causal"])
    b = sh["layers"] * flops.attention_bytes_train(
        obs["batch"], sh["heads"], obs["seq"], sh["head_dim"])
    least, which = flops.least_time_s(f, b, obs["peaks"])
    per_step = trace_reduce.kernel_time_s(obs["trace"]) / obs["traced_steps"]
    obs["log"](f"[roofline] flash kernels {per_step * 1e3:.3f} ms a step; "
               f"least {least * 1e3:.3f} ms, bound by {which} "
               f"({f / 1e12:.3f} TFLOP, {b / 1e9:.3f} GB a step)")
    return 100 * least / per_step
