"""Trainer: model FLOP/s utilization by the 6N rule (BENCH_r05's
convention): tokens/s x 6 x parameters over the chip's bf16 peak.  The
attention-inclusive figure (PaLM appendix B) goes on an earlier line."""
import flops


def read(obs):
    if obs["peaks"] is None:
        return None
    rate, peak = obs["train_tokens_per_s"], obs["peaks"]["bf16_flops_per_s"]
    six_n = flops.train_flops_per_token_6n(obs["n_params"])
    sh = obs["shape"]
    extra = flops.train_attention_extra_flops_per_token(
        sh["layers"], obs["seq"], sh["hidden"], obs["causal"])
    obs["log"](f"[mfu] 6N: {100 * rate * six_n / peak:.2f}%; with attention "
               f"({extra / six_n:.3f} x 6N more): "
               f"{100 * rate * (six_n + extra) / peak:.2f}%")
    return 100 * rate * six_n / peak
