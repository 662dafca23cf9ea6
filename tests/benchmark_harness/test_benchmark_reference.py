"""Each plain reference against the program's own float32 forward at the
tiny preset on the CPU: loss, and for training the gradients of every
parameter.  Both sides compute in float32; they order their reductions
differently (the program's fused QKV and flattened head matmul against
the reference's plain einsum order), which moves a float32 result by a few
ulps per reduction: 1e-5 relative on the loss, and on each gradient 1e-4 of
that gradient's largest entry (measured: 2e-7 and 3e-6).  A reference
that left out a layer, a bias, the causal mask or the GELU flavour misses
these by orders of magnitude."""
import json
import os

import numpy as np
import pytest

from bench_paths import BENCH
import bench_data
from run import load_plugin, overlay

LOSS_RTOL = 1e-5
GRAD_TOL = 1e-4


def tiny(config_name):
    with open(os.path.join(BENCH, "configs", config_name + ".json")) as f:
        cfg = json.load(f)
    return overlay(cfg, cfg["rehearse"])


def batch_for(family, cfg, n=4, seq=48):
    data = bench_data.SeededSequences(3, n, seq, cfg["vocab_size"],
                                      family.OBJECTIVE)
    return bench_data.COLLATE[family.OBJECTIVE]([data[i] for i in range(n)])


@pytest.mark.parametrize("config_name", ["gpt2_medium", "bert_large"])
def test_loss_and_gradients(config_name):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle

    cfg = tiny(config_name)
    family = load_plugin("families", cfg["family"])
    paddle.seed(5)
    loss_fn, init_params = family.train_program(cfg)
    params = {k: jnp.asarray(v) for k, v in init_params().items()}
    # biases and LayerNorm offsets start at 0: move them, so a reference
    # that forgot one would show
    g = bench_data.rng(9)
    params = {k: v + 0.02 * jnp.asarray(g.standard_normal(v.shape),
                                        jnp.float32)
              if v.ndim == 1 else v for k, v in params.items()}
    batch = {k: jnp.asarray(v) for k, v in batch_for(family, cfg).items()}
    key = jax.random.key(0)
    ref = family.reference_loss(cfg)
    with jax.default_matmul_precision("highest"):
        l_prog, g_prog = jax.value_and_grad(loss_fn)(params, batch, key)
    l_ref, g_ref = jax.value_and_grad(ref)(params, batch)
    assert abs(float(l_prog) - float(l_ref)) <= LOSS_RTOL * float(l_ref)
    assert set(g_prog) == set(g_ref) == set(params)
    trained = 0
    largest = max(float(np.abs(np.asarray(v)).max()) for v in g_ref.values())
    for name in params:
        a, b = np.asarray(g_prog[name]), np.asarray(g_ref[name])
        # a key bias moves no softmax, so its gradient is rounding noise
        # around 0: the floor keeps the test from comparing noise to noise
        scale = max(float(np.abs(b).max()), 1e-3 * largest)
        assert np.abs(a - b).max() <= GRAD_TOL * scale, name
        trained += bool(np.abs(b).max() > 0)
    # only BERT's pooler and NSP head (4 arrays) get no gradient
    assert len(params) - trained == (4 if cfg["family"] == "bert" else 0)


def test_gpt2_logits_match_the_served_model():
    """The serving check's reference: logits of the model's ordinary
    cache-free forward, float32, same weights."""
    import jax.numpy as jnp

    import paddle_tpu as paddle

    cfg = tiny("gpt2_medium")
    family = load_plugin("families", "gpt2")
    paddle.seed(6)
    model = family.served_model(cfg, "float32")
    params, buffers = model.functional_state()
    ids = jnp.asarray(bench_data.rng(1).integers(
        1, cfg["vocab_size"], (2, 40), dtype=np.int32))
    own = model.functional_call(params, buffers, ids)
    own = np.asarray(getattr(own, "_value", own))
    ref = np.asarray(family.reference_logits(cfg)(dict(params), ids))
    assert np.abs(own - ref).max() <= 1e-4 * np.abs(ref).max()


def test_mlm_batches_mask_a_fixed_count():
    data = bench_data.SeededSequences(1, 8, 512, 30522, "mlm")
    b = bench_data.collate_mlm([data[i] for i in range(8)])
    masked = b["labels"] != bench_data.MLM_IGNORE
    assert (masked.sum(1) == 77).all()            # round(0.15 * 512)
    assert (b["input_ids"][masked] == 103).all()
    again = bench_data.collate_mlm([data[i] for i in range(8)])
    assert (again["input_ids"] == b["input_ids"]).all()


def test_lm_batches_shift_by_one_and_follow_the_seed():
    a = bench_data.SeededSequences(2 ** 31 + 7, 4, 16, 1000, "lm")
    b = bench_data.collate_lm([a[i] for i in range(4)])
    assert b["input_ids"].shape == b["labels"].shape == (4, 16)
    assert (b["input_ids"][:, 1:] == b["labels"][:, :-1]).all()
    other = bench_data.SeededSequences(2 ** 31 + 8, 4, 16, 1000, "lm")
    assert (other[0] != a[0]).any()


def test_every_seed_offers_the_same_sizes_in_another_order():
    with open(os.path.join(BENCH, "traffic", "closed32.json")) as f:
        spec = json.load(f)
    grid = bench_data.request_sizes(spec)
    sizes = [cell for row in grid for cell in row]
    assert len(grid) == 8 and len(sizes) == len(set(sizes)) == 64
    p = np.array([s[0] for s in sizes])
    o = np.array([s[1] for s in sizes])
    assert 16 <= p.min() and p.max() <= 768 and 16 <= o.min() \
        and o.max() <= 256
    assert 320 <= p.mean() <= 345 and 105 <= o.mean() <= 120
    assert (p == 768).sum() == 9    # the clipped tail: 14% of prompts
    assert abs(np.corrcoef(p, o)[0, 1]) < 0.2
    seen = []
    for seed in (1, 2 ** 31 + 5):
        stream = bench_data.RequestStream(spec, 50257, seed)
        seen.append([(len(t), n) for t, n in
                     (next(stream) for _ in range(128))])
    assert seen[0] != seen[1]
    for got in seen:   # each pass is the whole multiset
        assert sorted(got[:64]) == sorted(got[64:]) == sorted(sizes)
        # and any aligned block of 8 is the mix in small: one prompt of
        # every length group, one output of every length group
        groups = {cell: (a, (a + b) % 8) for a, row in enumerate(grid)
                  for b, cell in enumerate(row)}
        for i in range(0, 128, 8):
            block = [groups[c] for c in got[i:i + 8]]
            assert sorted(a for a, _ in block) == list(range(8))
            assert sorted(b for _, b in block) == list(range(8))
