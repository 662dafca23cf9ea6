"""Kernels: the least time the chip could take for the slice's attention
over the paged kernels' device time in it.  The work: the KV bytes every
decode token the client saw in the slice must read (its context x 2 x
layers x heads x head size x 2 B) and its FLOPs, plus the causal prefill
attention of every prompt whose first token arrived in the slice."""
import flops
import trace_reduce


def read(obs):
    if obs["peaks"] is None or obs["trace"] is None:
        return None
    sh = obs["shape"]
    dims = (sh["layers"], sh["heads"], sh["head_dim"])
    f = sum(flops.paged_decode_flops(c, *dims)
            for c in obs["decode_contexts"]) \
        + sum(flops.prefill_attention_flops(p, *dims)
              for p in obs["prefill_prompts"])
    b = sum(flops.paged_decode_bytes(c, *dims)
            for c in obs["decode_contexts"]) \
        + sum(flops.prefill_attention_bytes(p, *dims)
              for p in obs["prefill_prompts"])
    least, which = flops.least_time_s(f, b, obs["peaks"])
    kernel_s = trace_reduce.kernel_time_s(obs["trace"])
    if not kernel_s:
        return None
    obs["log"](f"[roofline] paged kernels {kernel_s * 1e3:.1f} ms in the "
               f"slice; least {least * 1e3:.1f} ms, bound by {which} "
               f"({f / 1e12:.3f} TFLOP, {b / 1e9:.2f} GB)")
    return 100 * least / kernel_s
