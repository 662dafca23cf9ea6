"""Kernels: the least time the chip could take for the traced slice's
expert matmuls over the `moe_gmm` kernels' device time in it.  The work is
the program's own count (stats()["experts"]["dispatches"], the entries
stamped inside the slice): the weights of every expert that got a pick,
streamed once, and the FLOPs of the picks held here."""
import flops
import flops_kimi_linear as fk
import trace_reduce


def read(obs):
    if obs["peaks"] is None or obs["trace"] is None:
        return None
    log = (obs["stats"].get("experts") or {}).get("dispatches")
    kernel_s = trace_reduce.time_by(
        obs["trace"], only=trace_reduce.is_kernel).get("moe_gmm")
    if not log or not kernel_s:
        return None
    t0, t1 = obs["slice_clock"]
    mine = [e for e in log if t0 <= e[0] < t1]
    sh = obs["shape"]
    touched, picks = sum(e[3] for e in mine), sum(e[2] for e in mine)
    b = fk.moe_gmm_bytes(touched, picks, sh["hidden"], sh["expert_width"])
    f = fk.moe_gmm_flops(picks, sh["hidden"], sh["expert_width"])
    least, which = flops.least_time_s(f, b, obs["peaks"])
    obs["log"](f"[roofline] moe_gmm {kernel_s * 1e3:.1f} ms in the slice "
               f"over {len(mine)} dispatches; least {least * 1e3:.1f} ms, "
               f"bound by {which} ({f / 1e12:.3f} TFLOP, {b / 1e9:.2f} GB: "
               f"{touched} experts streamed, {picks} picks)")
    return 100 * least / kernel_s
