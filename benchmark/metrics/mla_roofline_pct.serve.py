"""Kernels: the least time the chip could take for the slice's latent
decode attention over the `mla_decode` kernel's device time in it: one
latent row per position of each decode token's context, read once for
all heads.  (The latent prefill is XLA in this program: no kernel,
nothing counted.)"""
import flops
import flops_kimi_linear as fk
import trace_reduce


def read(obs):
    if obs["peaks"] is None or obs["trace"] is None:
        return None
    by = trace_reduce.time_by(obs["trace"], only=trace_reduce.is_kernel)
    kernel_s = by.get("mla_decode")
    if not kernel_s:
        return None
    sh = obs["shape"]
    ctx = sum(obs["decode_contexts"])
    f = fk.mla_decode_flops(ctx, sh["mla_layers"], sh["heads"],
                            sh["latent"], sh["lora"])
    b = fk.mla_decode_bytes(ctx, sh["mla_layers"], sh["latent"])
    least, which = flops.least_time_s(f, b, obs["peaks"])
    obs["log"](f"[roofline] mla_decode {kernel_s * 1e3:.1f} ms in the "
               f"slice; least {least * 1e3:.1f} ms, bound by {which} "
               f"({b / 1e9:.2f} GB of latents, {f / 1e12:.3f} TFLOP)")
    return 100 * least / kernel_s
