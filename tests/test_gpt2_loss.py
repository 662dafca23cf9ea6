"""GPT-2's training loss against a float64 NumPy log-softmax
cross-entropy of the model's own logits: `GPT2.loss` eagerly and
`build_train_step`'s pure loss under jit, with every position labelled
and with positions ignored (-100, as a masked objective labels them)."""
import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.models.gpt2 import GPT2Config, build_train_step


def _reference(logits, labels):
    z = np.asarray(logits, np.float64).reshape(-1, logits.shape[-1])
    lab = np.asarray(labels).reshape(-1)
    z = z - z.max(-1, keepdims=True)
    logp = z - np.log(np.exp(z).sum(-1, keepdims=True))
    keep = lab != -100
    return -logp[np.arange(lab.size)[keep], lab[keep]].mean()


@pytest.mark.parametrize("ignored", [False, True],
                         ids=["all_labels", "ignored_positions"])
def test_loss_matches_float64_log_softmax(ignored):
    paddle.seed(0)
    rs = np.random.RandomState(1)
    cfg = GPT2Config.tiny()
    cfg.dropout = 0.0
    loss_fn, init_params, model = build_train_step(cfg)
    ids = rs.randint(0, cfg.vocab_size, (2, 16)).astype(np.int32)
    labels = rs.randint(0, cfg.vocab_size, (2, 16)).astype(np.int32)
    if ignored:
        labels[rs.rand(2, 16) < 0.4] = -100
        assert 0 < (labels == -100).sum() < labels.size
    want = _reference(model(paddle.to_tensor(ids)).numpy(), labels)
    eager = float(model.loss(paddle.to_tensor(ids),
                             paddle.to_tensor(labels)).numpy())
    jitted = float(jax.jit(loss_fn)(
        init_params(), {"input_ids": ids, "labels": labels},
        jax.random.key(0)))
    assert abs(eager - want) < 1e-5 * want, (eager, want)
    assert abs(jitted - want) < 1e-5 * want, (jitted, want)
