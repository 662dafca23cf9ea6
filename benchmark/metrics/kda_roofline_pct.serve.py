"""Kernels: the least time the chip could take for the slice's KDA decode
work over the `kda_decode` kernel's device time in it: each decode
token's float32 state, read and written once in every KDA layer.  (The
chunked prefill is XLA in this program: no kernel, nothing counted.)"""
import flops
import flops_kimi_linear as fk
import trace_reduce


def read(obs):
    if obs["peaks"] is None or obs["trace"] is None:
        return None
    by = trace_reduce.time_by(obs["trace"], only=trace_reduce.is_kernel)
    kernel_s = by.get("kda_decode")
    if not kernel_s:
        return None
    sh = obs["shape"]
    dims = (sh["kda_layers"], sh["kda_heads"], sh["kda_dim"])
    n = len(obs["decode_contexts"])
    f, b = fk.kda_decode_flops(n, *dims), fk.kda_decode_bytes(n, *dims)
    least, which = flops.least_time_s(f, b, obs["peaks"])
    obs["log"](f"[roofline] kda_decode {kernel_s * 1e3:.1f} ms in the "
               f"slice for {n} decode tokens; least {least * 1e3:.1f} ms, "
               f"bound by {which} ({b / 1e9:.2f} GB)")
    return 100 * least / kernel_s
