"""Kernels: the least time the chip could take for the slice's paged
attention under grouped heads over the device time of `paged_attn_decode`
and `paged_attn_prefill` in it.  The work: the K/V rows every decode token
the client saw in the slice must read (its context x the pool's K/V heads)
and its FLOPs (by the query heads), plus the causal prefill attention of
every prompt whose first token arrived in the slice.  A program whose shape
names no K/V heads, or a trace without the kernels, gives nothing."""
import flops
import flops_zaya as fz
import trace_reduce


def read(obs):
    sh = obs["shape"]
    if obs["peaks"] is None or obs["trace"] is None or "kv_heads" not in sh:
        return None
    by = trace_reduce.time_by(obs["trace"], only=trace_reduce.is_kernel)
    kernel_s = by.get("paged_attn_decode", 0.0) \
        + by.get("paged_attn_prefill", 0.0)
    if not kernel_s:
        return None
    q = (sh["layers"], sh["heads"], sh["head_dim"])
    kv = (sh["layers"], sh["kv_heads"], sh["head_dim"])
    f = sum(fz.cca_decode_flops(c, *q) for c in obs["decode_contexts"]) \
        + sum(fz.cca_prefill_flops(p, *q) for p in obs["prefill_prompts"])
    b = sum(fz.cca_decode_bytes(c, *kv) for c in obs["decode_contexts"]) \
        + sum(fz.cca_prefill_bytes(p, sh["layers"], sh["heads"],
                                   sh["kv_heads"], sh["head_dim"])
              for p in obs["prefill_prompts"])
    least, which = flops.least_time_s(f, b, obs["peaks"])
    obs["log"](f"[roofline] paged attention {kernel_s * 1e3:.1f} ms in the "
               f"slice (decode "
               f"{by.get('paged_attn_decode', 0.0) * 1e3:.1f}, prefill "
               f"{by.get('paged_attn_prefill', 0.0) * 1e3:.1f}); least "
               f"{least * 1e3:.1f} ms, bound by {which} "
               f"({f / 1e12:.3f} TFLOP, {b / 1e9:.2f} GB of K/V)")
    return 100 * least / kernel_s
