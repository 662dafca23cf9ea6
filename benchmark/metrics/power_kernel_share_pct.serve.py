"""Recurrent mixers: device time of the `power_decode` kernel over device
busy time in the traced slice.  A program without the kernel gives
nothing."""
import trace_reduce


def read(obs):
    if obs["trace"] is None or not obs["busy_s"]:
        return None
    kernel_s = trace_reduce.time_by(
        obs["trace"], only=trace_reduce.is_kernel).get("power_decode")
    if not kernel_s:
        return None
    return 100 * kernel_s / obs["busy_s"]
