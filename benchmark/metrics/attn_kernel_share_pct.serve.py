"""Attention ops: device time of tpu_custom_call events over device busy
time in the traced slice.  In the serving programs every custom call is
the paged stream kernel (1 per layer per attention program)."""
import trace_reduce


def read(obs):
    if obs["trace"] is None:
        return None
    return 100 * trace_reduce.kernel_share(obs["trace"])
