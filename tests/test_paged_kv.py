"""Paged KV cache (inference/kv_cache.py) + paged decode engine:
block-pool alloc/free/reuse invariants, paged-vs-dense decode parity on
mixed-length batches, pad-token-in-prompt correctness, and the Pallas
ragged paged-attention kernel vs the XLA gather path (interpret mode)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.kv_cache import (BlockPoolExhausted, PagedKVCache,
                                           blocks_for)
from paddle_tpu.models.gpt2 import GPT2, GPT2Config


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(7)
    cfg = GPT2Config.tiny()
    cfg.dropout = 0.0
    model = GPT2(cfg)
    model.eval()
    return model, cfg


class TestBlockPool:
    def _cache(self, num_blocks=8, block_size=4):
        return PagedKVCache(2, 4, 8, block_size=block_size,
                            num_blocks=num_blocks)

    def test_alloc_sizes_and_capacity(self):
        c = self._cache()
        assert c.capacity_tokens == 7 * 4  # block 0 is reserved trash
        t = c.allocate("a", 9)             # 9 tokens -> 3 blocks of 4
        assert len(t) == blocks_for(9, 4) == 3
        assert 0 not in t                  # trash block never handed out
        assert c.free_block_count == 4

    def test_append_crosses_block_boundary(self):
        c = self._cache()
        c.allocate("a", 4)                 # exactly one full block
        assert len(c.block_table("a")) == 1
        c.append("a")                      # token 5 needs a second block
        assert len(c.block_table("a")) == 2
        assert c.seq_len("a") == 5
        c.append("a", 3)                   # tokens 6..8 fit block 2
        assert len(c.block_table("a")) == 2

    def test_free_returns_blocks_and_reuse(self):
        c = self._cache()
        t_a = c.allocate("a", 12)
        c.allocate("b", 8)
        assert c.free_block_count == 2
        assert c.free("a") == 3
        assert c.free_block_count == 5
        # freed blocks are reusable — and a full-pool alloc succeeds
        t_c = c.allocate("c", 20)          # 5 blocks
        assert set(t_a) <= set(t_c)
        assert c.free_block_count == 0

    def test_exhaustion_raises_without_side_effects(self):
        c = self._cache()
        c.allocate("a", 20)                # 5 of 7 blocks
        with pytest.raises(BlockPoolExhausted):
            c.allocate("b", 12)            # needs 3, only 2 left
        assert "b" not in c._tables
        assert c.free_block_count == 2
        c.allocate("b", 8)                 # 2 blocks still fine

    def test_double_alloc_and_unknown_free(self):
        c = self._cache()
        c.allocate("a", 4)
        with pytest.raises(ValueError):
            c.allocate("a", 4)
        with pytest.raises(KeyError):
            c.free("zzz")

    def test_has_seq(self):
        c = self._cache()
        assert not c.has_seq("a")
        c.allocate("a", 4)
        assert c.has_seq("a")
        c.free("a")
        assert not c.has_seq("a")

    def test_unknown_seq_errors_are_descriptive(self):
        """Satellite: free/seq_len/block_table/ensure on an unknown
        sequence must raise a KeyError NAMING the sequence, not a bare
        KeyError from the internal dict."""
        c = self._cache()
        c.allocate("a", 4)
        for fn in (c.free, c.seq_len, c.block_table,
                   lambda s: c.ensure(s, 8), lambda s: c.append(s)):
            with pytest.raises(KeyError, match="unknown sequence 'ghost'"):
                fn("ghost")
        # and the failed probes left the pool untouched
        assert c.has_seq("a") and c.free_block_count == 6

    def test_allocate_and_ensure_share_ensure_many_bookkeeping(self):
        """Satellite: the grow paths are collapsed onto ensure_many —
        allocate/ensure get its atomicity (reclaim-aware precheck, no
        side effects on failure) and identical accounting."""
        c = self._cache()
        t = c.allocate("a", 9)
        assert t == c.block_table("a") and len(t) == 3
        assert c.ensure("a", 10) == t          # same block, no growth
        assert c.seq_len("a") == 10
        with pytest.raises(BlockPoolExhausted, match="reclaimable"):
            c.allocate("b", 999)               # same error surface
        assert not c.has_seq("b")
        with pytest.raises(BlockPoolExhausted, match="reclaimable"):
            c.ensure("a", 999)
        assert c.seq_len("a") == 10            # unchanged on failure
        assert len(c.block_table("a")) == 3

    def test_ensure_many_creates_and_grows_atomically(self):
        c = self._cache()
        c.allocate("a", 3)
        # bulk: grow "a" to 6 (1 more block) and create "b" at 9 (3)
        c.ensure_many([("a", 6), ("b", 9)])
        assert c.seq_len("a") == 6 and len(c.block_table("a")) == 2
        assert c.seq_len("b") == 9 and len(c.block_table("b")) == 3
        assert c.free_block_count == 2
        # shrink request is a no-op (lengths never go backwards)
        c.ensure_many([("a", 2)])
        assert c.seq_len("a") == 6

    def test_ensure_many_exhaustion_has_no_side_effects(self):
        c = self._cache()
        c.allocate("a", 16)                # 4 of 7 blocks
        with pytest.raises(BlockPoolExhausted):
            # total demand 4 blocks ("b" 3 + "a" grow 1), only 3 free:
            # NEITHER sequence may change
            c.ensure_many([("b", 12), ("a", 20)])
        assert not c.has_seq("b")
        assert c.seq_len("a") == 16
        assert len(c.block_table("a")) == 4
        assert c.free_block_count == 3

    def test_stats_and_table_array(self):
        c = self._cache()
        c.allocate("a", 6)
        st = c.stats()
        assert st["used_blocks"] == 2 and st["held_tokens"] == 6
        assert st["block_fill"] == 6 / 8
        assert 0 < st["utilization"] < 1
        tab = c.table_array(["a", None], width=4)
        assert tab.shape == (2, 4)
        assert (tab[1] == 0).all()         # idle row -> all trash
        assert tab[0, 2:].tolist() == [0, 0]
        c.free("a")
        assert c.stats()["used_blocks"] == 0
        assert c.stats()["peak_used_blocks"] == 2


def _truncate_fuzz(steps, seed, kv_dtype=None):
    """Fixed-seed pool fuzz interleaving `truncate_seq` accept/rollback
    ops (round 11 satellite) with the PR 4 op mix — alloc / ensure /
    append / ensure_many / free / attach / publish / CoW. After EVERY
    op the prefix-cache fuzz's invariant checker asserts that
    free ∪ retained ∪ tables still PARTITION the pool, refcounts equal
    table membership, and token accounting stays exact (a truncated
    sequence's table covers exactly blocks_for(new_len) blocks).
    kv_dtype="int8" (quantized-serving satellite) runs the same mix on
    a QUANTIZED pool: the scale buffers are parallel block-indexed
    arrays, so every partition/free/retain/CoW/truncate invariant
    must hold bit-for-bit the same — the checker also verifies the
    codes/scales arrays stay shape-locked to the block pool."""
    from test_prefix_cache import check_invariants

    rs = np.random.RandomState(seed)
    c = PagedKVCache(1, 1, 2, block_size=4, num_blocks=14,
                     kv_dtype=kv_dtype)
    master = rs.randint(1, 50, size=48).astype(np.int32)
    live = {}          # seq -> prompt length (publishable tokens)
    next_seq = [0]
    truncates = [0]

    def op_admit():
        seq = next_seq[0]
        next_seq[0] += 1
        n = int(rs.randint(1, 24))
        toks = master[:n]
        try:
            cached = c.attach_prefix(seq, toks)
            if cached == 0:
                c.allocate(seq, n)
            else:
                c.prepare_write(seq, cached)
                c.ensure(seq, n)
        except BlockPoolExhausted:
            if c.has_seq(seq):
                c.free(seq)
            return
        live[seq] = n

    def op_speculate():
        """The serving-engine shape: grow a speculative tail past the
        live length (the verify write horizon), then accept a random
        prefix of it — truncate back to len + accepted."""
        if not live:
            return
        seq = list(live)[int(rs.randint(len(live)))]
        base = c.seq_len(seq)
        k = int(rs.randint(1, 6))
        try:
            c.ensure(seq, base + k)
        except BlockPoolExhausted:
            return
        accepted = int(rs.randint(0, k + 1))
        c.truncate_seq(seq, base + accepted)
        truncates[0] += 1

    def op_truncate():
        """Arbitrary rollback — including to zero and into a published
        / attached prefix region (bookkeeping-only here: a real writer
        would route the next write through prepare_write)."""
        if not live:
            return
        seq = list(live)[int(rs.randint(len(live)))]
        new_len = int(rs.randint(0, c.seq_len(seq) + 1))
        c.truncate_seq(seq, new_len)
        live[seq] = min(live[seq], new_len)
        truncates[0] += 1

    def op_grow():
        if not live:
            return
        seq = list(live)[int(rs.randint(len(live)))]
        try:
            c.append(seq, int(rs.randint(1, 6)))
        except BlockPoolExhausted:
            pass

    def op_bulk():
        if not live:
            return
        seqs = list(live)
        picks = {seqs[int(rs.randint(len(seqs)))]
                 for _ in range(min(3, len(seqs)))}
        try:
            c.ensure_many([(s, c.seq_len(s) + int(rs.randint(0, 5)))
                           for s in picks])
        except BlockPoolExhausted:
            pass

    def op_publish():
        if not live:
            return
        seq = list(live)[int(rs.randint(len(live)))]
        n = min(live[seq], c.seq_len(seq))
        if n:
            c.publish_prefix(seq, master[:n])

    def op_free():
        if not live:
            return
        seq = list(live)[int(rs.randint(len(live)))]
        c.free(seq)
        del live[seq]

    ops = [op_admit, op_admit, op_speculate, op_speculate, op_truncate,
           op_grow, op_bulk, op_publish, op_free]
    for _ in range(steps):
        ops[int(rs.randint(len(ops)))]()
        check_invariants(c)
    for seq in list(live):
        c.free(seq)
        check_invariants(c)
    assert c._ref == {}
    assert c.free_block_count + c.retained_block_count \
        == c.num_blocks - 1
    assert truncates[0] > steps // 20     # the mix actually truncated
    return c


class TestTruncateFuzz:
    def test_truncate_interleaved_invariants(self):
        """Tier-1 satellite: 250 mixed ops with truncate_seq
        accept/rollback interleaved keep the pool partition exact."""
        _truncate_fuzz(250, seed=4321)

    def test_truncate_interleaved_invariants_int8(self):
        """Tier-1 (quantized-serving satellite): the same interleaved
        mix on an int8 pool — scale buffers must partition / free /
        retain / CoW / truncate in lockstep with the blocks."""
        c = _truncate_fuzz(250, seed=4321, kv_dtype="int8")
        assert c.kv_dtype == "int8"
        assert c.scale_bytes > 0

    @pytest.mark.slow
    def test_truncate_interleaved_invariants_long(self):
        """The long fuzz loop (slow-marked per the round-11 CI
        satellite): same mix, 2000 ops, different seed."""
        _truncate_fuzz(2000, seed=97531)

    @pytest.mark.slow
    def test_truncate_interleaved_invariants_int8_long(self):
        """Long int8-pool fuzz (slow; quantized-serving satellite)."""
        _truncate_fuzz(2000, seed=97531, kv_dtype="int8")


class TestPagedDenseParity:
    def test_uniform_batch_greedy_matches_dense(self, tiny_model):
        model, cfg = tiny_model
        rs = np.random.RandomState(0)
        ids = rs.randint(1, cfg.vocab_size, (3, 9)).astype(np.int32)
        dense = model.generate(ids, 6).numpy()
        paged = model.generate(ids, 6, kv_cache="paged",
                               block_size=4).numpy()
        np.testing.assert_array_equal(dense, paged)

    def test_mixed_length_matches_dense_leftpad(self, tiny_model):
        """Dense decodes LEFT-padded rows (value masking); paged decodes
        RIGHT-padded rows with explicit lengths. Generated suffixes must
        agree token for token."""
        model, cfg = tiny_model
        rs = np.random.RandomState(1)
        s0, new = 8, 5
        lens = np.array([3, 8, 5], np.int32)
        rows = [rs.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
                for n in lens]
        left = np.zeros((3, s0), np.int32)
        right = np.zeros((3, s0), np.int32)
        for i, r in enumerate(rows):
            left[i, s0 - lens[i]:] = r
            right[i, :lens[i]] = r
        dense = model.generate(left, new, pad_token_id=0).numpy()
        paged = model.generate(right, new, kv_cache="paged",
                               prompt_lens=lens, block_size=4,
                               pad_token_id=0).numpy()
        for i in range(3):
            np.testing.assert_array_equal(
                dense[i, s0:], paged[i, lens[i]:lens[i] + new],
                err_msg=f"row {i} (len {lens[i]})")

    def test_logit_parity_mixed_lengths(self, tiny_model):
        """The paged engine's prefill/step logits must match the dense
        model forward at the same positions (f32 CPU: tight atol)."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.nn.decode import PagedDecoder

        model, cfg = tiny_model
        rs = np.random.RandomState(2)
        s0 = 7
        lens = np.array([4, 7], np.int32)
        ids = np.zeros((2, s0), np.int32)
        for i, n in enumerate(lens):
            ids[i, :n] = rs.randint(1, cfg.vocab_size, (n,))
        params, _ = model.functional_state()
        bs = 4
        m = blocks_for(s0 + 2, bs)
        cache = PagedKVCache(cfg.num_layers, cfg.num_heads,
                             cfg.hidden_size // cfg.num_heads,
                             block_size=bs, num_blocks=2 * m + 1)
        for b in range(2):
            cache.allocate(b, int(lens[b]) + 2)
        tables = jnp.asarray(cache.table_array([0, 1], m))
        from paddle_tpu.sampling import greedy_args

        dec = PagedDecoder.for_config(cfg, bs, return_logits=True)
        tok, _stop, kc, vc, _cnt, logits0 = dec.prefill(
            params, jnp.asarray(ids), jnp.asarray(lens), tables,
            cache.k_blocks, cache.v_blocks, greedy_args(2))
        # dense reference: full forward on each row's true prompt
        for b in range(2):
            ref = model(ids[b:b + 1, :lens[b]]).numpy()[0, -1]
            np.testing.assert_allclose(np.asarray(logits0)[b], ref,
                                       atol=1e-4, rtol=1e-4)
        # one decode step: logits must match forward on prompt + tok0
        nxt, _stop, kc, vc, _cnt, logits1 = dec.step(
            params, tok, jnp.asarray(lens), jnp.ones((2,), bool), tables,
            kc, vc, greedy_args(2))
        tok = np.asarray(tok)
        for b in range(2):
            full = np.concatenate([ids[b, :lens[b]], tok[b:b + 1]])
            ref = model(full[None]).numpy()[0, -1]
            np.testing.assert_allclose(np.asarray(logits1)[b], ref,
                                       atol=1e-4, rtol=1e-4)

    def test_prompt_containing_pad_token_decodes_correctly(self, tiny_model):
        """The dense server's documented corruption case: a full-length
        prompt that legitimately contains pad_token_id, batched with a
        padded row. The paged path masks by LENGTH, so the pad-valued
        positions must be attended like any other token."""
        model, cfg = tiny_model
        rs = np.random.RandomState(3)
        s0, new = 6, 4
        tricky = rs.randint(1, cfg.vocab_size, (s0,)).astype(np.int32)
        tricky[2] = 0  # == pad_token_id, mid-prompt
        short = rs.randint(1, cfg.vocab_size, (3,)).astype(np.int32)
        batch = np.zeros((2, s0), np.int32)
        batch[0] = tricky
        batch[1, :3] = short
        out = model.generate(batch, new, kv_cache="paged",
                             prompt_lens=np.array([s0, 3], np.int32),
                             block_size=4, pad_token_id=0).numpy()
        # reference: each prompt decoded ALONE (no padding anywhere)
        ref0 = model.generate(tricky[None], new).numpy()[0]
        ref1 = model.generate(short[None], new).numpy()[0]
        np.testing.assert_array_equal(out[0, :s0 + new], ref0)
        np.testing.assert_array_equal(out[1, 3:3 + new], ref1[3:])

    def test_temperature_sampling_runs(self, tiny_model):
        model, cfg = tiny_model
        rs = np.random.RandomState(4)
        ids = rs.randint(1, cfg.vocab_size, (2, 6)).astype(np.int32)
        out = model.generate(ids, 4, kv_cache="paged", temperature=0.8,
                             seed=3, block_size=4).numpy()
        assert out.shape == (2, 10)
        assert (out[:, :6] == ids).all()

    def test_paged_rejects_unsupported_knobs(self, tiny_model):
        model, cfg = tiny_model
        ids = np.ones((1, 4), np.int32)
        # top_k/top_p are SUPPORTED on the paged path since round 10
        # (per-slot sampling pipeline), kv_quant="int8" since the
        # quantized-serving round; unknown kv_quant values still raise
        out = model.generate(ids, 2, kv_cache="paged", top_k=5,
                             temperature=0.5, seed=1).numpy()
        assert out.shape == (1, 6)
        with pytest.raises(ValueError):
            model.generate(ids, 2, kv_cache="paged", kv_quant="int4")
        with pytest.raises(ValueError):
            model.generate(ids, 2, kv_cache="nope")
        with pytest.raises(ValueError):  # dense path must not silently
            model.generate(ids, 2, prompt_lens=[4])  # ignore prompt_lens


class TestPagedAttentionKernel:
    @pytest.mark.parametrize("lens", [[11, 5, 16], [0, 0, 13], [16, 16, 16],
                                      [1, 4, 5]],
                             ids=["ragged", "one_live_row", "every_row_full",
                                  "block_edges"])
    def test_pallas_kernel_matches_xla_gather(self, lens):
        """Ragged Pallas kernel (interpret mode on CPU) vs the XLA
        gather path: ragged lengths, a launch of one live row, of full
        rows, of a block's edges; a row that sees nothing is zeros."""
        import jax.numpy as jnp

        from paddle_tpu.ops.attention import paged_decode_attention
        from paddle_tpu.ops.pallas.unified_attention import (
            paged_decode_attention_kernel)

        rs = np.random.RandomState(0)
        b, h, dh, n, bs, m = 3, 4, 8, 9, 4, 4
        q = jnp.asarray(rs.randn(b, h, dh).astype(np.float32))
        kb = jnp.asarray(rs.randn(n, bs, h, dh).astype(np.float32))
        vb = jnp.asarray(rs.randn(n, bs, h, dh).astype(np.float32))
        tables = jnp.asarray(np.array([[1, 2, 3, 8], [4, 5, 1, 3],
                                       [6, 7, 8, 2]], np.int32))
        seen = np.array(lens) > 0
        lens = jnp.asarray(np.array(lens, np.int32))
        ref = paged_decode_attention(q, kb, vb, tables, lens)
        out = paged_decode_attention_kernel(q, kb, vb, tables, lens,
                                            interpret=True)
        np.testing.assert_allclose(np.asarray(out)[seen],
                                   np.asarray(ref)[seen], atol=2e-6)
        assert not np.asarray(out)[~seen].any()

    def test_xla_gather_ignores_trash_blocks(self):
        """Positions beyond ctx_len must not influence the output even if
        the trash block holds garbage."""
        import jax.numpy as jnp

        from paddle_tpu.ops.attention import paged_decode_attention

        rs = np.random.RandomState(1)
        q = jnp.asarray(rs.randn(1, 2, 4).astype(np.float32))
        kb = rs.randn(4, 4, 2, 4).astype(np.float32)
        vb = rs.randn(4, 4, 2, 4).astype(np.float32)
        tables = jnp.asarray(np.array([[1, 2]], np.int32))
        lens = jnp.asarray(np.array([6], np.int32))
        out1 = paged_decode_attention(jnp.asarray(q), jnp.asarray(kb),
                                      jnp.asarray(vb), tables, lens)
        kb2, vb2 = kb.copy(), vb.copy()
        kb2[0] = 99.0  # poison the trash block
        vb2[0] = -99.0
        kb2[2, 2:] = 7.0  # poison positions >= ctx_len in the tail block
        vb2[2, 2:] = -7.0
        out2 = paged_decode_attention(jnp.asarray(q), jnp.asarray(kb2),
                                      jnp.asarray(vb2), tables, lens)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                   atol=1e-6)
