"""Expert FFN: device time of the `moe_gmm` kernels over device busy time
in the traced slice."""
import trace_reduce


def read(obs):
    if obs["trace"] is None or not obs["busy_s"]:
        return None
    kernel_s = trace_reduce.time_by(
        obs["trace"], only=trace_reduce.is_kernel).get("moe_gmm")
    if not kernel_s:
        return None
    return 100 * kernel_s / obs["busy_s"]
