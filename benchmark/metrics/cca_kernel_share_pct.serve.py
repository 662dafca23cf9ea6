"""Attention ops: device time of the `paged_attn_decode` and
`paged_attn_prefill` kernels over device busy time in the traced slice
(`attn_kernel_share_pct.serve` counts every custom call, which in a
program with experts counts `moe_gmm` too)."""
import trace_reduce


def read(obs):
    if obs["trace"] is None or not obs["busy_s"]:
        return None
    by = trace_reduce.time_by(obs["trace"], only=trace_reduce.is_kernel)
    kernel_s = by.get("paged_attn_decode", 0.0) \
        + by.get("paged_attn_prefill", 0.0)
    if not kernel_s:
        return None
    return 100 * kernel_s / obs["busy_s"]
