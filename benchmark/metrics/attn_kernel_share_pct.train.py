"""Attention ops: device time of tpu_custom_call events over device busy
time in the traced steps.  In these programs every custom call is an
attention kernel (3 per layer in a train step)."""
import trace_reduce


def read(obs):
    if obs["trace"] is None:
        return None
    return 100 * trace_reduce.kernel_share(obs["trace"])
