"""Packed ragged prefill (ISSUE 3 tentpole): the attention op (XLA
gather fallback + Pallas kernel in interpret mode), and the packed
prefill program's logits parity against the sequential B=1 bucketed
prefill — including a prompt split across 3+ chunks, whose partial K/V
state lives in the paged cache between dispatches."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt2 import GPT2, GPT2Config


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(21)
    cfg = GPT2Config.tiny()
    cfg.dropout = 0.0
    model = GPT2(cfg)
    model.eval()
    return model, cfg


def _dense_segment_reference(q, k_blocks, v_blocks, tables, seg, pos):
    """Straight-line numpy reference: token t attends its own segment's
    cache positions [0, pos[t]] gathered block by block."""
    T, H, Dh = q.shape
    _, BS, _, _ = k_blocks.shape
    out = np.zeros_like(q)
    for t in range(T):
        if pos[t] < 0:
            continue
        tb = tables[seg[t]]
        ctx = pos[t] + 1
        ks = np.concatenate([k_blocks[b] for b in tb])[:ctx]  # [ctx, H, Dh]
        vs = np.concatenate([v_blocks[b] for b in tb])[:ctx]
        for h in range(H):
            s = ks[:, h] @ q[t, h] * (Dh ** -0.5)
            w = np.exp(s - s.max())
            w /= w.sum()
            out[t, h] = w @ vs[:, h]
    return out


class TestRaggedPrefillAttention:
    def _case(self, seed=0):
        rs = np.random.RandomState(seed)
        n, bs, h, dh, m = 7, 4, 4, 8, 3
        kb = rs.randn(n, bs, h, dh).astype(np.float32)
        vb = rs.randn(n, bs, h, dh).astype(np.float32)
        tables = np.array([[1, 2, 3], [4, 5, 0]], np.int32)
        # packed stream: seg0 tokens at positions 5..10 (a chunk whose
        # prefix 0..4 is already cached), seg1 at 0..3, then pad
        seg = np.array([0] * 6 + [1] * 4 + [0] * 2, np.int32)
        pos = np.array(list(range(5, 11)) + list(range(4)) + [-1, -1],
                       np.int32)
        q = rs.randn(len(seg), h, dh).astype(np.float32)
        return q, kb, vb, tables, seg, pos

    def test_xla_fallback_matches_dense_reference(self):
        import jax.numpy as jnp

        from paddle_tpu.ops.attention import ragged_prefill_attention

        q, kb, vb, tables, seg, pos = self._case()
        out = np.asarray(ragged_prefill_attention(
            jnp.asarray(q), jnp.asarray(kb), jnp.asarray(vb),
            jnp.asarray(tables), jnp.asarray(seg), jnp.asarray(pos)))
        ref = _dense_segment_reference(q, kb, vb, tables, seg, pos)
        valid = pos >= 0
        np.testing.assert_allclose(out[valid], ref[valid], atol=2e-6)

    def test_pad_tokens_produce_finite_output(self):
        """Packing pads (pos = -1) mask every key; their output must be
        finite garbage, never NaN (it flows through later layers)."""
        import jax.numpy as jnp

        from paddle_tpu.ops.attention import ragged_prefill_attention

        q, kb, vb, tables, seg, pos = self._case(1)
        out = np.asarray(ragged_prefill_attention(
            jnp.asarray(q), jnp.asarray(kb), jnp.asarray(vb),
            jnp.asarray(tables), jnp.asarray(seg), jnp.asarray(pos)))
        assert np.isfinite(out).all()

    def test_pallas_kernel_matches_xla_fallback(self):
        """Segment-aligned packing, kernel in interpret mode on CPU:
        tile-aligned segments, a pad tile, mixed causal horizons."""
        import jax.numpy as jnp

        from paddle_tpu.ops.attention import ragged_prefill_attention
        from paddle_tpu.ops.pallas.unified_attention import (
            unified_ragged_attention_kernel as
            ragged_prefill_attention_kernel)

        rs = np.random.RandomState(2)
        n, bs, h, dh, m, qt = 9, 8, 4, 8, 3, 8
        kb = rs.randn(n, bs, h, dh).astype(np.float32)
        vb = rs.randn(n, bs, h, dh).astype(np.float32)
        tables = np.array([[1, 2, 3], [4, 5, 6], [7, 8, 0]], np.int32)
        # 4 tiles of qt=8: seg0 chunk at positions 8..15 (cached
        # prefix), seg1 fresh 0..7, seg2 partial chunk 0..4 + pads,
        # then one all-pad tile
        seg = np.array([0] * 8 + [1] * 8 + [2] * 8 + [0] * 8, np.int32)
        pos = np.array(list(range(8, 16)) + list(range(8))
                       + list(range(5)) + [-1] * 3 + [-1] * 8, np.int32)
        q = rs.randn(len(seg), h, dh).astype(np.float32)
        ref = np.asarray(ragged_prefill_attention(
            jnp.asarray(q), jnp.asarray(kb), jnp.asarray(vb),
            jnp.asarray(tables), jnp.asarray(seg), jnp.asarray(pos)))
        out = np.asarray(ragged_prefill_attention_kernel(
            jnp.asarray(q), jnp.asarray(kb), jnp.asarray(vb),
            jnp.asarray(tables), jnp.asarray(seg[::qt]),
            jnp.asarray(pos[::qt]), q_tile=qt, interpret=True))
        valid = pos >= 0
        np.testing.assert_allclose(out[valid], ref[valid], atol=2e-6)


class TestPackedPrefillProgram:
    """packed_prefill vs the sequential B=1 bucketed prefill — the
    ISSUE 3 parity bar: same tokens greedily, logits allclose."""

    def _decoder_and_cache(self, cfg, bs=4, nblocks=32):
        from paddle_tpu.inference.kv_cache import PagedKVCache
        from paddle_tpu.nn.decode import PagedDecoder

        dec = PagedDecoder.for_config(cfg, bs, return_logits=True)
        cache = PagedKVCache(cfg.num_layers, cfg.num_heads,
                             cfg.hidden_size // cfg.num_heads,
                             block_size=bs, num_blocks=nblocks)
        return dec, cache

    def _ref_prefill(self, model, dec, cfg, prompt, bs=4):
        """Sequential B=1 bucketed prefill logits for one prompt."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.inference.kv_cache import PagedKVCache

        params, _ = model.functional_state()
        cache = PagedKVCache(cfg.num_layers, cfg.num_heads,
                             cfg.hidden_size // cfg.num_heads,
                             block_size=bs, num_blocks=32)
        n = len(prompt)
        cache.allocate(0, n)
        bucket = 8
        while bucket < n:
            bucket *= 2
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :n] = prompt
        tables = jnp.asarray(cache.table_array([0], 8))
        from paddle_tpu.sampling import greedy_args

        tok, _stop, kc, vc, _cnt, logits = dec.prefill(
            params, jnp.asarray(ids), jnp.asarray([n]), tables,
            cache.k_blocks, cache.v_blocks, greedy_args(1))
        return int(np.asarray(tok)[0]), np.asarray(logits)[0]

    def test_packed_matches_sequential_prefill(self, tiny_model):
        """Two mixed-length prompts packed into ONE dispatch must give
        each prompt the same greedy token and logits as its own B=1
        bucketed prefill."""
        import jax
        import jax.numpy as jnp

        model, cfg = tiny_model
        dec, cache = self._decoder_and_cache(cfg)
        params, _ = model.functional_state()
        rs = np.random.RandomState(3)
        prompts = [rs.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in (5, 9)]
        cache.ensure_many([(0, 5), (1, 9)])
        align = 8  # seg0 region [0, 8), seg1 region [8, 24)
        T = 24
        toks = np.zeros((T,), np.int32)
        seg = np.zeros((T,), np.int32)
        pos = np.full((T,), -1, np.int32)
        toks[:5], seg[:5], pos[:5] = prompts[0], 0, np.arange(5)
        toks[align:align + 9] = prompts[1]
        seg[align:align + 9] = 1
        pos[align:align + 9] = np.arange(9)
        sample_idx = np.array([4, align + 8], np.int32)
        tables = jnp.asarray(cache.table_array([0, 1], 8))
        from paddle_tpu.sampling import greedy_args

        tok, _stop, kc, vc, _cnt, logits = dec.packed_prefill(
            params, jnp.asarray(toks), jnp.asarray(seg),
            jnp.asarray(pos), tables, jnp.asarray(sample_idx),
            cache.k_blocks, cache.v_blocks, greedy_args(2))
        tok = np.asarray(tok)
        logits = np.asarray(logits)
        for row, prompt in enumerate(prompts):
            ref_tok, ref_logits = self._ref_prefill(model, dec, cfg,
                                                    prompt)
            assert int(tok[row]) == ref_tok
            np.testing.assert_allclose(logits[row], ref_logits,
                                       atol=1e-4, rtol=1e-4)

    def test_chunked_matches_oneshot_prefill(self, tiny_model):
        """A 13-token prompt fed in 3 chunks (5+5+3, partial K/V state
        carried in the paged cache) must end with the same greedy token
        and logits as the one-shot sequential prefill."""
        import jax
        import jax.numpy as jnp

        model, cfg = tiny_model
        dec, cache = self._decoder_and_cache(cfg)
        params, _ = model.functional_state()
        rs = np.random.RandomState(4)
        prompt = rs.randint(1, cfg.vocab_size, (13,)).astype(np.int32)
        tok = logits = None
        for start in (0, 5, 10):
            n = min(5, 13 - start)
            cache.ensure_many([(0, start + n)])
            T = 8
            toks = np.zeros((T,), np.int32)
            seg = np.zeros((T,), np.int32)
            pos = np.full((T,), -1, np.int32)
            toks[:n] = prompt[start:start + n]
            pos[:n] = np.arange(start, start + n)
            sample_idx = np.array([n - 1], np.int32)
            tables = jnp.asarray(cache.table_array([0], 8))
            from paddle_tpu.sampling import greedy_args

            tok, _stop, kc, vc, _cnt, logits = dec.packed_prefill(
                params, jnp.asarray(toks), jnp.asarray(seg),
                jnp.asarray(pos), tables, jnp.asarray(sample_idx),
                cache.k_blocks, cache.v_blocks, greedy_args(1))
            cache.swap_arrays(kc, vc)
        ref_tok, ref_logits = self._ref_prefill(model, dec, cfg, prompt)
        assert int(np.asarray(tok)[0]) == ref_tok
        np.testing.assert_allclose(np.asarray(logits)[0], ref_logits,
                                   atol=1e-4, rtol=1e-4)
