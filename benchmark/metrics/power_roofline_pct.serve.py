"""Kernels: the least time the chip could take for the slice's power-
retention decode work over the `power_decode` kernel's device time in it:
each decode token's float32 state S and normaliser z, read and written
once in every layer, counted at the symmetric map's own size D = d (d + 1)
/ 2 whatever tile the program keeps (`flops_brumby.py`: a program at tiles
of 32 can read 80.6 at most).  (The chunked prefill is XLA in this
program: no kernel, nothing counted.)  A program without the kernel gives
nothing."""
import flops
import flops_brumby as fb
import trace_reduce


def read(obs):
    if obs["peaks"] is None or obs["trace"] is None:
        return None
    by = trace_reduce.time_by(obs["trace"], only=trace_reduce.is_kernel)
    kernel_s = by.get("power_decode")
    sh = obs["shape"]
    if not kernel_s or "power_layers" not in sh:
        return None
    n = len(obs["decode_contexts"])
    f = fb.power_decode_flops(n, sh["power_layers"], sh["heads"],
                              sh["kv_heads"], sh["head_dim"])
    b = fb.power_decode_bytes(n, sh["power_layers"], sh["kv_heads"],
                              sh["head_dim"])
    least, which = flops.least_time_s(f, b, obs["peaks"])
    obs["log"](f"[roofline] power_decode {kernel_s * 1e3:.1f} ms in the "
               f"slice for {n} decode tokens; least {least * 1e3:.1f} ms, "
               f"bound by {which} ({b / 1e9:.2f} GB at D "
               f"{fb.power_state_dim(sh['head_dim'])}; the program keeps "
               f"D {sh['power_state_dim']})")
    return 100 * least / kernel_s
