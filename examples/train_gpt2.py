"""Train a tiny GPT-2 on synthetic data, save a checkpoint, export for
deployment, and reload it with the Predictor — the full user journey.

Run: JAX_PLATFORMS=cpu python examples/train_gpt2.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax
    # a CPU example: pin the platform before any backend initialises, so
    # it never takes the chip from the process that should hold it
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.gpt2 import GPT2, GPT2Config
    from paddle_tpu.static import InputSpec

    paddle.seed(0)
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                 parameters=model.parameters())

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (8, 64)).astype(np.int32)
    first = last = None
    for step in range(10):
        loss = model.loss(Tensor(jnp.asarray(ids)), Tensor(jnp.asarray(ids)))
        loss.backward()
        opt.step()
        opt.clear_grad()
        last = float(loss.numpy())
        first = first if first is not None else last
        if step % 3 == 0:
            print(f"step {step}: loss {last:.4f}")
    assert last < first, (first, last)

    # checkpoint (resume training later)
    paddle.save({"model": model.state_dict(), "opt": opt.state_dict()},
                "/tmp/gpt2_ckpt")

    # deployment artifact: StableHLO + params, no Python class needed
    model.eval()
    paddle.jit.save(model, "/tmp/gpt2_deploy",
                    input_spec=[InputSpec([None, 64], "int64")])
    from paddle_tpu.inference import Config, create_predictor
    pred = create_predictor(Config("/tmp/gpt2_deploy.pdmodel",
                                   "/tmp/gpt2_deploy.pdiparams"))
    logits = pred.run([ids.astype(np.int64)])
    print("deployed predictor logits:", tuple(logits.shape))

    # text generation: KV-cache decode with sampling; left-padded batches
    # of unequal prompts decode row-independently
    pad = 0
    prompts = np.array([[3, 5, 7, 9], [pad, pad, 11, 13]], np.int64)
    out = model.generate(prompts, max_new_tokens=8, temperature=0.8,
                         top_k=40, seed=1, pad_token_id=pad)
    print("generated:", out.numpy()[1].tolist())

    # weight-only int8 serving (W8A16): halves the per-token weight
    # stream — 1.7-2.5x tokens/s at small batch on-chip (PERF.md); the
    # greedy path matches bf16 on this config, and the same flag exports
    # an int8 decode artifact via models.gpt2.export_generator
    out8 = model.generate(prompts, max_new_tokens=8, weight_quant="int8",
                          pad_token_id=pad)
    print("w8a16 generated:", out8.numpy()[1].tolist())
    print("OK: trained, checkpointed, exported, served, generated (+w8a16)")


if __name__ == "__main__":
    main()
