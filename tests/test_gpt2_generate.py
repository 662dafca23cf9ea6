"""GPT2.generate — KV-cache autoregressive decoding (serving path).
Greedy decode must match the naive recompute-the-whole-prefix loop token
for token; eos handling pads with eos after the first hit."""
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models.gpt2 import GPT2, GPT2Config


def _naive_greedy(model, ids, n):
    out = ids.copy()
    for _ in range(n):
        logits = model(paddle.to_tensor(out)).numpy()
        nxt = logits[:, -1].argmax(-1).astype(np.int64)
        out = np.concatenate([out, nxt[:, None]], axis=1)
    return out


def test_greedy_matches_naive_loop():
    paddle.seed(0)
    cfg = GPT2Config.tiny()
    cfg.dropout = 0.0
    model = GPT2(cfg)
    model.eval()
    rs = np.random.RandomState(0)
    ids = rs.randint(0, cfg.vocab_size, (2, 7)).astype(np.int64)

    fast = model.generate(ids, max_new_tokens=6).numpy()
    slow = _naive_greedy(model, ids, 6)
    np.testing.assert_array_equal(fast, slow)


def test_single_token_and_eos():
    paddle.seed(1)
    cfg = GPT2Config.tiny()
    cfg.dropout = 0.0
    model = GPT2(cfg)
    model.eval()
    ids = np.array([[5, 9, 2]], np.int64)

    one = model.generate(ids, max_new_tokens=1).numpy()
    assert one.shape == (1, 4)
    np.testing.assert_array_equal(one, _naive_greedy(model, ids, 1))

    # force the first generated token to be "eos": the rest must be eos
    eos = int(one[0, -1])
    full = model.generate(ids, max_new_tokens=5, eos_token_id=eos).numpy()
    assert (full[0, 3:] == eos).all()


def test_untied_head_and_bounds():
    paddle.seed(3)
    cfg = GPT2Config.tiny()
    cfg.dropout = 0.0
    cfg.tie_embeddings = False  # decode must use lm_head, not wte.T
    model = GPT2(cfg)
    model.eval()
    ids = np.array([[3, 1, 4]], np.int64)
    np.testing.assert_array_equal(model.generate(ids, 4).numpy(),
                                  _naive_greedy(model, ids, 4))

    # max_new_tokens=0 returns the prompt unchanged
    np.testing.assert_array_equal(model.generate(ids, 0).numpy(), ids)

    # exceeding the positional table raises instead of silently clamping
    import pytest as _pytest
    long_ids = np.zeros((1, cfg.max_position - 2), np.int64)
    with _pytest.raises(ValueError):
        model.generate(long_ids, 5)


def test_sampling_is_reproducible_and_plausible():
    paddle.seed(2)
    cfg = GPT2Config.tiny()
    cfg.dropout = 0.0
    model = GPT2(cfg)
    model.eval()
    ids = np.array([[1, 2, 3, 4]], np.int64)
    a = model.generate(ids, max_new_tokens=8, temperature=0.8,
                       seed=7).numpy()
    b = model.generate(ids, max_new_tokens=8, temperature=0.8,
                       seed=7).numpy()
    np.testing.assert_array_equal(a, b)  # same seed -> same sample
    assert a.shape == (1, 12)
    assert (a[:, :4] == ids).all()


def test_left_padded_batch_matches_per_row():
    # variable-length prompts, left-padded into one batch: each row must
    # decode exactly as it would alone (pads masked from attention,
    # positions not consumed by pads)
    paddle.seed(10)
    cfg = GPT2Config.tiny()
    cfg.dropout = 0.0
    model = GPT2(cfg)
    model.eval()
    pad = 0
    p1 = np.array([5, 9, 2, 7], np.int64)        # length 4
    p2 = np.array([11, 3], np.int64)             # length 2
    batch = np.stack([p1, np.concatenate([[pad, pad], p2])])
    out = model.generate(batch, 5, pad_token_id=pad).numpy()
    r1 = model.generate(p1[None], 5).numpy()[0]
    r2 = model.generate(p2[None], 5).numpy()[0]
    np.testing.assert_array_equal(out[0, 4:], r1[4:])
    np.testing.assert_array_equal(out[1, 4:], r2[2:])

    # right padding is rejected loudly
    bad = np.stack([p1, np.concatenate([p2, [pad, pad]])])
    import pytest as _pytest
    with _pytest.raises(ValueError, match="LEFT-padded"):
        model.generate(bad, 3, pad_token_id=pad)


def test_top_k_top_p_filtering():
    paddle.seed(6)
    cfg = GPT2Config.tiny()
    cfg.dropout = 0.0
    model = GPT2(cfg)
    model.eval()
    ids = np.array([[2, 4, 6]], np.int64)
    # top_k=1 sampling degenerates to greedy regardless of temperature
    greedy = model.generate(ids, 5).numpy()
    k1 = model.generate(ids, 5, temperature=1.5, top_k=1, seed=3).numpy()
    np.testing.assert_array_equal(k1, greedy)
    # tiny top_p likewise collapses to the argmax token
    p_small = model.generate(ids, 5, temperature=1.5, top_p=1e-6,
                             seed=4).numpy()
    np.testing.assert_array_equal(p_small, greedy)
    # permissive settings still produce valid tokens
    free = model.generate(ids, 5, temperature=1.0, top_k=50,
                          top_p=0.9, seed=5).numpy()
    assert free.shape == (1, 8)
    assert (free >= 0).all() and (free < cfg.vocab_size).all()


def test_no_recompile_across_seed_temp_eos():
    from paddle_tpu.models import gpt2 as gpt2_mod
    paddle.seed(4)
    cfg = GPT2Config.tiny()
    cfg.dropout = 0.0
    model = GPT2(cfg)
    model.eval()
    ids = np.array([[1, 2, 3]], np.int64)
    # another test file on this xdist worker may already have built the
    # same program: count misses from an empty cache
    gpt2_mod._generate_impl.cache_clear()
    before = gpt2_mod._generate_impl.cache_info().misses
    model.generate(ids, 4, temperature=0.7, seed=1)
    model.generate(ids, 4, temperature=1.3, seed=2, eos_token_id=5)
    model.generate(ids, 4, temperature=0.0, seed=3)
    after = gpt2_mod._generate_impl.cache_info().misses
    # seed/temperature/eos are traced: one compiled program serves all
    assert after - before == 1


class TestWeightOnlyInt8Decode:
    def test_w8a16_matches_bf16_greedy(self):
        import paddle_tpu as paddle
        from paddle_tpu.models.gpt2 import GPT2, GPT2Config

        paddle.seed(0)
        m = GPT2(GPT2Config.tiny())
        m.eval()
        ids = np.random.RandomState(3).randint(5, 200, (2, 10)).astype(
            np.int32)
        a = m.generate(ids, 12).numpy()
        b = m.generate(ids, 12, weight_quant="int8").numpy()
        # per-channel int8 weights: greedy paths agree on the tiny config
        assert (a == b).mean() > 0.9
        assert (b[:, :10] == ids).all()

    def test_quantize_weights_public_packing(self):
        """`quantize_weights()` (the quantized-serving satellite that
        replaced the lazy `_w8_cache`) is the ONE shared W8A16
        implementation: it packs every big 2-D decode weight into
        ::w8c/::w8s pairs, reflects in-place weight edits on the next
        call (no hidden cache to go stale), and round-trips within the
        per-channel int8 bound."""
        import paddle_tpu as paddle
        from paddle_tpu.models.gpt2 import GPT2, GPT2Config

        paddle.seed(1)
        m = GPT2(GPT2Config.tiny())
        m.eval()
        packed = m.quantize_weights()
        assert not hasattr(m, "_w8_cache")  # the lazy cache is gone
        for name in ("wte.weight", "h.0.qkv_proj.weight",
                     "h.1.fc2.weight"):
            assert name not in packed
            codes = packed[name + "::w8c"]
            scales = packed[name + "::w8s"]
            assert str(codes.dtype) == "int8"
            assert codes.shape[:len(scales.shape)] != () and \
                np.abs(np.asarray(codes)).max() <= 127
        # round-trip bound: |w - codes*scale| <= scale/2 per channel
        w = dict(m.named_parameters())["h.0.fc1.weight"].numpy()
        codes = np.asarray(packed["h.0.fc1.weight::w8c"], np.float32)
        scales = np.asarray(packed["h.0.fc1.weight::w8s"], np.float32)
        deq = codes * scales[None, :]
        assert np.abs(deq - w).max() <= scales.max() * 0.51
        # no stale cache: an in-place weight edit shows up next call
        p = dict(m.named_parameters())["h.0.fc1.weight"]
        p.set_value(np.asarray(p.numpy()) * 0 + 1)
        packed2 = m.quantize_weights()
        assert not np.array_equal(
            np.asarray(packed2["h.0.fc1.weight::w8c"]),
            np.asarray(packed["h.0.fc1.weight::w8c"]))

    def test_unknown_weight_quant_raises(self):
        import pytest
        import paddle_tpu as paddle
        from paddle_tpu.models.gpt2 import GPT2, GPT2Config

        m = GPT2(GPT2Config.tiny())
        m.eval()
        with pytest.raises(ValueError, match="int8"):
            m.generate(np.zeros((1, 8), np.int32), 2, weight_quant="int4")


class TestInt8KVCache:
    def test_kv8_greedy_parity(self):
        import paddle_tpu as paddle
        from paddle_tpu.models.gpt2 import GPT2, GPT2Config

        paddle.seed(0)
        m = GPT2(GPT2Config.tiny())
        m.eval()
        ids = np.random.RandomState(5).randint(5, 200, (2, 12)).astype(
            np.int32)
        a = m.generate(ids, 16).numpy()
        b = m.generate(ids, 16, kv_quant="int8").numpy()
        assert (a == b).mean() > 0.9
        # stacks with weight-only int8
        c = m.generate(ids, 16, kv_quant="int8",
                       weight_quant="int8").numpy()
        assert (c[:, :12] == ids).all()

    def test_kv8_left_padded(self):
        import paddle_tpu as paddle
        from paddle_tpu.models.gpt2 import GPT2, GPT2Config

        paddle.seed(1)
        m = GPT2(GPT2Config.tiny())
        m.eval()
        p = np.array([[0, 0, 7, 9], [3, 5, 7, 9]], np.int32)
        o = m.generate(p, 6, kv_quant="int8", pad_token_id=0).numpy()
        assert o.shape == (2, 10) and (o[:, :4] == p).all()

    def test_unknown_kv_quant_raises(self):
        import pytest

        import paddle_tpu as paddle
        from paddle_tpu.models.gpt2 import GPT2, GPT2Config

        m = GPT2(GPT2Config.tiny())
        m.eval()
        with pytest.raises(ValueError, match="int8"):
            m.generate(np.zeros((1, 8), np.int32), 2, kv_quant="fp4")
