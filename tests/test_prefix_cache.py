"""Prefix caching for the paged KV pool (round 9 tentpole):
content-addressed block index, attach-by-table-copy, copy-on-write on
shared tails, LRU retention/eviction — pool-level unit tests, a
fixed-seed invariant fuzz (satellite), decoder-level logit parity for
the cached-resume path, and the server-level cache-ON vs cache-OFF
parity suite (mid-block CoW + forced eviction pressure included)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.kv_cache import (BlockPoolExhausted, PagedKVCache,
                                           blocks_for)
from paddle_tpu.inference.kv_tier import HostKVTier
from paddle_tpu.models.gpt2 import GPT2, GPT2Config


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(13)
    cfg = GPT2Config.tiny()
    cfg.dropout = 0.0
    model = GPT2(cfg)
    model.eval()
    return model, cfg


def _cache(num_blocks=16, block_size=4):
    return PagedKVCache(1, 1, 2, block_size=block_size,
                        num_blocks=num_blocks)


def check_invariants(c):
    """The pool partition + refcount + token-accounting invariants the
    fuzz satellite asserts after every operation."""
    usable = set(range(1, c.num_blocks))
    free = set(c._free)
    assert len(free) == len(c._free), "free list holds duplicates"
    retained = set(c._retained)
    in_tables = set()
    refs = {}
    for seq, table in c._tables.items():
        assert len(table) == len(set(table)), \
            f"table of {seq!r} holds a block twice"
        # token accounting: the table covers exactly the live length
        assert len(table) == blocks_for(c._lens[seq], c.block_size)
        for b in table:
            in_tables.add(b)
            refs[b] = refs.get(b, 0) + 1
    # free ∪ retained ∪ tables partition the usable pool
    assert free | retained | in_tables == usable
    assert not free & retained
    assert not free & in_tables
    assert not retained & in_tables
    # refcounts equal table membership counts, and exist ONLY for
    # referenced blocks (zero exactly at release)
    assert refs == c._ref
    # trash block 0 is never allocated, retained or shared
    assert 0 not in free | retained | in_tables
    assert 0 not in c._block_entries
    # retained blocks are retained BECAUSE the index still names them
    for b in retained:
        assert c._block_entries.get(b)
    # index entries are mutually consistent with the reverse maps
    for h, (blk, fill, parent) in c._index.items():
        assert blk in in_tables | retained
        assert 0 < fill <= c.block_size
        assert h in c._block_entries[blk]
        assert c._child_fills[parent].get(fill, 0) >= 1
    # int8 pool (quantized-serving round): the scale buffers are
    # block-indexed parallels of the code arrays — same block axis,
    # same per-row layout — so every block operation above moved them
    # in lockstep by construction; verify the structure never drifts
    if c.kv_dtype == "int8":
        for kv in (c.k_blocks, c.v_blocks):
            assert str(kv.codes.dtype) == "int8"
            assert kv.codes.shape == (c.num_layers, c.num_blocks,
                                      c.block_size,
                                      c.num_heads * c.head_dim)
            assert kv.scales.shape == kv.codes.shape[:-1] + (
                c.num_heads,)
    # host tier (long-context round): the tier index is DISJOINT from
    # the device index (move semantics), stays within capacity, and
    # its token accounting is internally consistent — so tiering adds
    # a fourth, host-side ownership class without perturbing the
    # device partition above
    if c.tier is not None:
        t = c.tier
        assert not set(c._index) & set(t._entries), \
            "a chain hash lives in both the device and tier indexes"
        assert len(t) <= t.capacity_blocks
        fills_seen = {}
        for h, (fill, parent, kp, vp) in t._entries.items():
            assert 0 < fill <= c.block_size
            # tier payloads are the int8 codec: codes cover exactly
            # the entry's fill rows, scales ride in lockstep
            for pay in (kp, vp):
                assert str(pay.codes.dtype) == "int8"
                assert pay.codes.shape == (c.num_layers, fill,
                                           c.num_heads, c.head_dim)
                assert pay.scales.shape == pay.codes.shape[:-1]
            fs = fills_seen.setdefault(parent, {})
            fs[fill] = fs.get(fill, 0) + 1
        assert fills_seen == t._child_fills
        assert t.tokens_resident() == sum(
            ent[0] for ent in t._entries.values())


class TestPrefixPoolUnit:
    def test_publish_attach_full_chain(self):
        c = _cache()
        toks = np.arange(100, 112, dtype=np.int32)      # 3 full blocks
        c.allocate("a", 12)
        c.publish_prefix("a", toks)
        ta = c.block_table("a")
        c.free("a")
        assert c.retained_block_count == 3              # parked, not freed
        got = c.attach_prefix("b", toks)
        # the last token is never matched (prefill must sample token 0)
        assert got == 11
        assert c.block_table("b") == ta                 # table-entry copy
        assert c.seq_len("b") == 11
        assert c.retained_block_count == 0              # revived
        st = c.stats()["prefix_cache"]
        assert st["hits"] == 1 and st["hit_tokens"] == 11
        assert st["lookups"] == 1 and st["lookup_tokens"] == 11
        check_invariants(c)

    def test_attach_extension_prompt_hits_full_blocks(self):
        c = _cache()
        toks = np.arange(50, 58, dtype=np.int32)        # exactly 2 blocks
        c.allocate("a", 8)
        c.publish_prefix("a", toks)
        longer = np.concatenate([toks, np.arange(9, dtype=np.int32)])
        got = c.attach_prefix("b", longer)
        assert got == 8                                 # both full blocks
        assert c.block_table("b") == c.block_table("a")
        c.ensure("b", longer.size)                      # grows fresh tail
        assert c._ref[c.block_table("a")[0]] == 2       # shared live
        check_invariants(c)

    def test_no_match_returns_zero_without_creating_seq(self):
        c = _cache()
        c.allocate("a", 8)
        c.publish_prefix("a", np.arange(8, dtype=np.int32))
        got = c.attach_prefix("b", np.arange(900, 912, dtype=np.int32))
        assert got == 0
        assert not c.has_seq("b")
        st = c.stats()["prefix_cache"]
        assert st["lookups"] == 1 and st["hits"] == 0
        check_invariants(c)

    def test_partial_tail_attach_and_inplace_when_sole(self):
        """A published prompt ending mid-block is attachable including
        the partial tail; the sole referent writing AT the claimed fill
        needs no copy (the entry only describes rows below it)."""
        c = _cache()
        toks = np.arange(10, dtype=np.int32)            # 2 full + fill 2
        c.allocate("a", 10)
        c.publish_prefix("a", toks)
        tail_block = c.block_table("a")[2]
        c.free("a")
        longer = np.concatenate([toks, np.arange(70, 75,
                                                 dtype=np.int32)])
        got = c.attach_prefix("b", longer)
        assert got == 10                                # incl. partial
        assert c.block_table("b")[2] == tail_block
        assert not c.prepare_write("b", 10)             # row 2 >= fill 2
        assert c.block_table("b")[2] == tail_block      # no copy
        assert c.stats()["prefix_cache"]["cow_copies"] == 0
        check_invariants(c)

    def test_cow_when_shared_live(self):
        """Writing into a block another live sequence still references
        must copy it; the original table and device content survive."""
        import jax.numpy as jnp

        c = _cache()
        toks = np.arange(10, dtype=np.int32)
        c.allocate("a", 10)
        c.publish_prefix("a", toks)
        tail = c.block_table("a")[2]
        # poison the tail block's device rows so the copy is observable
        c.k_blocks = c.k_blocks.at[:, tail].set(7.5)
        c.v_blocks = c.v_blocks.at[:, tail].set(-2.5)
        longer = np.concatenate([toks, np.arange(70, 76,
                                                 dtype=np.int32)])
        assert c.attach_prefix("b", longer) == 10
        assert c._ref[tail] == 2                        # shared live
        assert c.prepare_write("b", 10) is True         # CoW
        new = c.block_table("b")[2]
        assert new != tail
        assert c.block_table("a")[2] == tail            # owner untouched
        assert c._ref[tail] == 1 and c._ref[new] == 1
        np.testing.assert_array_equal(
            np.asarray(c.k_blocks[:, new]), np.asarray(jnp.full_like(
                c.k_blocks[:, new], 7.5)))
        np.testing.assert_array_equal(
            np.asarray(c.v_blocks[:, new]), np.asarray(jnp.full_like(
                c.v_blocks[:, new], -2.5)))
        assert c.stats()["prefix_cache"]["cow_copies"] == 1
        check_invariants(c)

    def test_cow_when_claiming_below_entry_fill(self):
        """An exact resubmission is capped one token short, so it
        claims FEWER rows of the tail entry than the entry's fill —
        writing there must copy (preserving the entry), even with no
        other referent."""
        c = _cache()
        toks = np.arange(300, 314, dtype=np.int32)      # 3 full + fill 2
        c.allocate("a", 14)
        c.publish_prefix("a", toks)
        tail = c.block_table("a")[3]
        c.free("a")
        got = c.attach_prefix("b", toks)                # same prompt
        assert got == 13                                # capped
        assert c.block_table("b")[3] == tail
        assert c.prepare_write("b", 13) is True         # row 1 < fill 2
        assert c.block_table("b")[3] != tail
        assert len(c._block_entries[tail]) == 1         # entry survives
        assert tail in c._retained                      # parked again
        check_invariants(c)

    def test_retention_lru_order_and_eviction(self):
        c = _cache(num_blocks=8)                        # 7 usable
        a = np.arange(0, 8, dtype=np.int32)
        b = np.arange(50, 58, dtype=np.int32)
        c.allocate("a", 8)
        c.publish_prefix("a", a)
        c.allocate("b", 8)
        c.publish_prefix("b", b)
        a_blocks = c.block_table("a")
        c.free("a")                                     # LRU: a first
        c.free("b")
        assert c.retained_block_count == 4
        assert c.free_block_count == 3
        # demand 5 blocks: reclaims "a"'s two (least recent) first
        c.allocate("c", 20)
        st = c.stats()["prefix_cache"]
        assert st["evictions"] == 2
        assert set(a_blocks) <= set(c.block_table("c"))
        # "a" is gone from the index, "b" still attachable
        assert c.attach_prefix("x", a) == 0
        assert c.attach_prefix("y", b) == 7
        check_invariants(c)

    def test_ensure_many_reclaims_before_raising(self):
        c = _cache(num_blocks=8)
        c.allocate("a", 8)
        c.publish_prefix("a", np.arange(8, dtype=np.int32))
        c.free("a")                                     # 2 retained
        c.ensure_many([("b", 24), ("c", 4)])            # needs all 7
        assert c.stats()["prefix_cache"]["evictions"] == 2
        # and a truly impossible demand still fails atomically
        with pytest.raises(BlockPoolExhausted, match="reclaimable"):
            c.ensure_many([("d", 8)])
        assert not c.has_seq("d")
        check_invariants(c)

    def test_publish_requires_live_tokens_and_known_seq(self):
        c = _cache()
        c.allocate("a", 4)
        with pytest.raises(ValueError, match="only 4 are live"):
            c.publish_prefix("a", np.arange(8, dtype=np.int32))
        with pytest.raises(KeyError, match="unknown sequence"):
            c.publish_prefix("zzz", np.arange(4, dtype=np.int32))
        with pytest.raises(KeyError, match="unknown sequence"):
            c.prepare_write("zzz", 0)


class TestPoolInvariantsFuzz:
    def test_randomized_op_sequence_keeps_invariants(self):
        """Satellite: a fixed-seed fuzz over
        alloc/ensure/append/ensure_many/free/attach/publish/CoW
        sequences; after EVERY op the free/retained/table partition,
        refcounts, token accounting and the trash-block rule must
        hold (check_invariants)."""
        rs = np.random.RandomState(1234)
        c = _cache(num_blocks=14, block_size=4)
        master = rs.randint(1, 50, size=48).astype(np.int32)
        live = {}          # seq -> its prompt tokens
        next_seq = [0]

        def new_tokens():
            # prefixes of a master string (deep sharing) + random tails
            n = int(rs.randint(1, 30))
            t = master[:n].copy()
            if rs.rand() < 0.4:
                t = np.concatenate([t, rs.randint(
                    1, 50, size=int(rs.randint(1, 7))).astype(np.int32)])
            return t

        def op_admit():
            seq = next_seq[0]
            next_seq[0] += 1
            toks = new_tokens()
            try:
                cached = c.attach_prefix(seq, toks)
                if cached == 0:
                    c.allocate(seq, toks.size)
                else:
                    c.prepare_write(seq, cached)
                    c.ensure(seq, toks.size)
            except BlockPoolExhausted:
                if c.has_seq(seq):  # attach landed, growth failed
                    c.free(seq)
                return
            live[seq] = toks

        def op_grow():
            if not live:
                return
            seq = list(live)[int(rs.randint(len(live)))]
            try:
                if rs.rand() < 0.5:
                    c.append(seq, int(rs.randint(1, 6)))
                else:
                    c.ensure(seq, c.seq_len(seq) + int(rs.randint(0, 6)))
            except BlockPoolExhausted:
                pass

        def op_bulk():
            if not live:
                return
            seqs = list(live)
            picks = [seqs[int(rs.randint(len(seqs)))]
                     for _ in range(min(3, len(seqs)))]
            try:
                c.ensure_many([(s, c.seq_len(s) + int(rs.randint(0, 5)))
                               for s in set(picks)])
            except BlockPoolExhausted:
                pass

        def op_publish():
            if not live:
                return
            seq = list(live)[int(rs.randint(len(live)))]
            c.publish_prefix(seq, live[seq])

        def op_write():
            if not live:
                return
            seq = list(live)[int(rs.randint(len(live)))]
            pos = int(rs.randint(0, c.seq_len(seq) + 1))
            try:
                c.prepare_write(seq, pos)
            except BlockPoolExhausted:
                pass

        def op_free():
            if not live:
                return
            seq = list(live)[int(rs.randint(len(live)))]
            if rs.rand() < 0.5:
                c.publish_prefix(seq, live[seq])
            c.free(seq)
            del live[seq]

        ops = [op_admit, op_admit, op_grow, op_bulk, op_publish,
               op_write, op_free, op_free]
        for step in range(400):
            ops[int(rs.randint(len(ops)))]()
            check_invariants(c)
        for seq in list(live):                     # full drain releases
            c.free(seq)                            # every refcount
            check_invariants(c)
        assert c._ref == {}
        assert c.free_block_count + c.retained_block_count \
            == c.num_blocks - 1
        st = c.stats()["prefix_cache"]
        assert st["hits"] > 20          # the fuzz actually shared
        assert st["cow_copies"] > 0     # ... and actually CoW'd
        assert st["evictions"] > 0      # ... and hit pool pressure


class TestTierInterleavingFuzz:
    """Long-context-round satellite: the host-tier choreography —
    watermark/explicit demotion, prefetch-on-match promotion, tier
    capacity eviction, and the int8 tier codec — interleaved with the
    regular alloc/publish/CoW/truncate/swap-out mix. After EVERY op
    the device partition must hold unchanged AND the tier index must
    stay disjoint from the device index with coherent token
    accounting (the extended check_invariants)."""

    def _fuzz(self, n_ops, seed, kv_dtype=None):
        rs = np.random.RandomState(seed)
        c = PagedKVCache(1, 1, 2, block_size=4, num_blocks=12,
                         kv_dtype=kv_dtype,
                         tier=HostKVTier(capacity_blocks=6,
                                         watermark=0.25))
        master = rs.randint(1, 50, size=40).astype(np.int32)
        live = {}
        next_seq = [0]

        def new_tokens():
            n = int(rs.randint(1, 26))
            t = master[:n].copy()
            if rs.rand() < 0.4:
                t = np.concatenate([t, rs.randint(
                    1, 50, size=int(rs.randint(1, 7))).astype(np.int32)])
            return t

        def op_admit():
            seq = next_seq[0]
            next_seq[0] += 1
            toks = new_tokens()
            try:
                cached = c.attach_prefix(seq, toks)  # may promote
                if cached == 0:
                    c.allocate(seq, toks.size)
                else:
                    c.prepare_write(seq, cached)
                    c.ensure(seq, toks.size)
            except BlockPoolExhausted:
                if c.has_seq(seq):
                    c.free(seq)
                return
            live[seq] = toks

        def op_probe():
            # read-ish probe that PROMOTES a tiered chain tail
            c.match_prefix_len(new_tokens())

        def op_demote():
            c.demote_cold(int(rs.randint(1, 4)))

        def op_publish():
            if not live:
                return
            seq = list(live)[int(rs.randint(len(live)))]
            c.publish_prefix(seq, live[seq])

        def op_write():
            if not live:
                return
            seq = list(live)[int(rs.randint(len(live)))]
            try:
                c.prepare_write(seq, int(rs.randint(0,
                                                    c.seq_len(seq) + 1)))
            except BlockPoolExhausted:
                pass

        def op_truncate():
            if not live:
                return
            seq = list(live)[int(rs.randint(len(live)))]
            c.truncate_seq(seq, int(rs.randint(0, c.seq_len(seq) + 1)))
            # keep live[] honest for later publishes
            live[seq] = live[seq][:c.seq_len(seq)]
            if live[seq].size == 0:
                c.free(seq)
                del live[seq]

        def op_swap_out():
            if not live:
                return
            seq = list(live)[int(rs.randint(len(live)))]
            c.swap_out_seq(seq, live[seq])
            del live[seq]

        def op_free():
            if not live:
                return
            seq = list(live)[int(rs.randint(len(live)))]
            if rs.rand() < 0.5:
                c.publish_prefix(seq, live[seq])
            c.free(seq)
            del live[seq]

        ops = [op_admit, op_admit, op_probe, op_demote, op_publish,
               op_write, op_truncate, op_swap_out, op_free]
        for _ in range(n_ops):
            ops[int(rs.randint(len(ops)))]()
            check_invariants(c)
        for seq in list(live):
            c.free(seq)
            check_invariants(c)
        assert c._ref == {}
        assert c.free_block_count + c.retained_block_count \
            == c.num_blocks - 1
        st = c.stats()["tier"]
        assert st["enabled"]
        assert st["demotions"] > 5       # the fuzz actually tiered
        assert st["promotions"] > 5      # ... promoted content back
        assert st["hit_tokens"] > 0
        return c

    def test_tier_interleaving_keeps_invariants(self):
        self._fuzz(300, seed=2026)

    def test_tier_interleaving_int8_pool(self):
        # int8 pool: the tier stores the native codes+scales, so the
        # codec round trip is bit-exact by construction — the fuzz
        # checks the structural accounting holds regardless
        self._fuzz(300, seed=2027, kv_dtype="int8")

    @pytest.mark.slow
    def test_tier_interleaving_long(self):
        c = self._fuzz(2500, seed=909)
        assert c.tier.evictions > 0      # capacity LRU actually hit


class TestRecoveryInterleavingFuzz:
    """r17 satellite: the recovery ladder's pool choreography —
    truncate-to-durable, swap-out publish, re-attach resume, and
    injected BlockPoolExhausted (atomic, no side effects) — interleaved
    with the regular alloc/grow/publish/CoW/free mix. The partition,
    refcount and token-accounting invariants must hold after EVERY op,
    and a refused ensure_many must leave the pool byte-identical."""

    def _fuzz(self, n_ops, seed):
        rs = np.random.RandomState(seed)
        c = _cache(num_blocks=14, block_size=4)
        master = rs.randint(1, 50, size=64).astype(np.int32)
        live = {}          # seq -> full known token stream
        next_seq = [0]
        counters = {"swap_cycles": 0, "refused": 0, "truncates": 0}

        def stream_for(seq):
            """Known tokens covering the sequence's live length (the
            recovery paths need ids for every live position)."""
            n = c.seq_len(seq)
            t = live[seq]
            if t.size < n:
                t = np.concatenate([t, rs.randint(
                    1, 50, size=n - t.size).astype(np.int32)])
                live[seq] = t
            return t[:n]

        def op_admit():
            seq = next_seq[0]
            next_seq[0] += 1
            n = int(rs.randint(1, 30))
            toks = master[:n].copy()
            if rs.rand() < 0.4:
                toks = np.concatenate([toks, rs.randint(
                    1, 50, size=int(rs.randint(1, 7))).astype(np.int32)])
            try:
                cached = c.attach_prefix(seq, toks)
                if cached == 0:
                    c.allocate(seq, toks.size)
                else:
                    c.prepare_write(seq, cached)
                    c.ensure(seq, toks.size)
            except BlockPoolExhausted:
                if c.has_seq(seq):
                    c.free(seq)
                return
            live[seq] = toks

        def op_grow():
            if not live:
                return
            seq = list(live)[int(rs.randint(len(live)))]
            try:
                c.append(seq, int(rs.randint(1, 6)))
            except BlockPoolExhausted:
                pass

        def op_recover_cycle():
            """The engine's _recover_slot shape: roll back to a
            durable length, publish + free through swap_out, then
            re-attach the SAME stream and regrow (the resume)."""
            if not live:
                return
            seq = list(live)[int(rs.randint(len(live)))]
            ids = stream_for(seq)
            durable = int(rs.randint(0, c.seq_len(seq) + 1))
            if durable < c.seq_len(seq):
                c.truncate_seq(seq, durable)
                counters["truncates"] += 1
            check_invariants(c)
            c.swap_out_seq(seq, ids[:durable])
            check_invariants(c)
            del live[seq]
            counters["swap_cycles"] += 1
            if durable < 2 or rs.rand() < 0.3:
                return  # resumed elsewhere / given up
            rseq = next_seq[0]
            next_seq[0] += 1
            try:
                cached = c.attach_prefix(rseq, ids[:durable])
                if cached == 0:
                    c.allocate(rseq, durable)
                else:
                    c.prepare_write(rseq, cached)
                    c.ensure(rseq, durable)
            except BlockPoolExhausted:
                if c.has_seq(rseq):
                    c.free(rseq)
                return
            live[rseq] = ids[:durable].copy()

        def op_injected_exhaustion():
            """An ensure_many asking for more than the pool can ever
            cover must refuse ATOMICALLY: identical free/retained/
            table state before and after."""
            if not live:
                return
            seqs = list(live)[:3]
            before = (list(c._free), list(c._retained),
                      {s: list(t) for s, t in c._tables.items()},
                      dict(c._lens))
            demand = [(s, c.seq_len(s) + c.num_blocks * c.block_size)
                      for s in seqs]
            with pytest.raises(BlockPoolExhausted):
                c.ensure_many(demand)
            counters["refused"] += 1
            assert before == (list(c._free), list(c._retained),
                              {s: list(t) for s, t in c._tables.items()},
                              dict(c._lens))

        def op_publish():
            if not live:
                return
            seq = list(live)[int(rs.randint(len(live)))]
            c.publish_prefix(seq, stream_for(seq))

        def op_free():
            if not live:
                return
            seq = list(live)[int(rs.randint(len(live)))]
            c.free(seq)
            del live[seq]

        ops = [op_admit, op_admit, op_grow, op_recover_cycle,
               op_recover_cycle, op_injected_exhaustion, op_publish,
               op_free]
        for _ in range(n_ops):
            ops[int(rs.randint(len(ops)))]()
            check_invariants(c)
        for seq in list(live):
            c.free(seq)
            check_invariants(c)
        assert c._ref == {}
        assert c.free_block_count + c.retained_block_count \
            == c.num_blocks - 1
        # the fuzz actually exercised every recovery path
        assert counters["swap_cycles"] > 10
        assert counters["truncates"] > 5
        assert counters["refused"] > 5
        st = c.stats()["prefix_cache"]
        assert st["hits"] > 5

    def test_recovery_interleaving_keeps_invariants(self):
        self._fuzz(400, seed=4321)

    @pytest.mark.slow
    def test_recovery_interleaving_long(self):
        self._fuzz(2000, seed=9876)


class TestCachedPrefillLogitParity:
    """Acceptance bar: the final-step logits of a cached-prefix resume
    (attach + packed prefill from the first uncached token) must match
    the full cache-OFF prefill — including a mid-block attach that
    forces CoW."""

    def _setup(self, cfg, bs=4):
        from paddle_tpu.nn.decode import PagedDecoder

        dec = PagedDecoder.for_config(cfg, bs, return_logits=True)
        cache = PagedKVCache(cfg.num_layers, cfg.num_heads,
                             cfg.hidden_size // cfg.num_heads,
                             block_size=bs, num_blocks=32)
        return dec, cache

    def _packed(self, dec, cache, params, seq, toks, start):
        """Run one packed_prefill chunk feeding toks[start:] of `seq`
        (mirrors the server: ensure -> prepare_write -> dispatch)."""
        import jax.numpy as jnp

        from paddle_tpu.sampling import greedy_args

        n = toks.size - start
        T = 8
        while T < n:
            T *= 2
        cache.ensure(seq, toks.size)
        cache.prepare_write(seq, start)
        stream = np.zeros((T,), np.int32)
        seg = np.zeros((T,), np.int32)
        pos = np.full((T,), -1, np.int32)
        stream[:n] = toks[start:]
        pos[:n] = np.arange(start, toks.size, dtype=np.int32)
        tables = jnp.asarray(cache.table_array(
            [seq], blocks_for(toks.size, cache.block_size)))
        tok, _stop, kc, vc, _cnt, logits = dec.packed_prefill(
            params, jnp.asarray(stream), jnp.asarray(seg),
            jnp.asarray(pos), tables, jnp.asarray([n - 1]),
            cache.k_blocks, cache.v_blocks, greedy_args(1))
        cache.swap_arrays(kc, vc)
        return int(np.asarray(tok)[0]), np.asarray(logits)[0]

    def test_cached_resume_logits_match_full_prefill(self, tiny_model):
        model, cfg = tiny_model
        params, _ = model.functional_state()
        dec, cache = self._setup(cfg)
        rs = np.random.RandomState(5)
        prompt = rs.randint(1, cfg.vocab_size, (13,)).astype(np.int32)
        cache.allocate(0, 0)
        tok0, logits0 = self._packed(dec, cache, params, 0, prompt, 0)
        cache.publish_prefix(0, prompt)
        cache.free(0)
        # identical prompt: attach all but the last token, feed 1 token
        cached = cache.attach_prefix(1, prompt)
        assert cached == 12
        tok1, logits1 = self._packed(dec, cache, params, 1, prompt,
                                     cached)
        assert tok1 == tok0
        np.testing.assert_allclose(logits1, logits0, atol=1e-4,
                                   rtol=1e-4)

    def test_midblock_cow_resume_logits_match(self, tiny_model):
        """Shared prefix ending mid-block: the attach claims part of
        the publisher's partial tail block, the resume write forces a
        CoW, and the final logits still match the uncached path."""
        model, cfg = tiny_model
        params, _ = model.functional_state()
        dec, cache = self._setup(cfg)
        rs = np.random.RandomState(6)
        # the published prompt itself ends mid-block (10 % 4 == 2), so
        # its fill-2 tail entry is what the extension prompt attaches
        a = rs.randint(1, cfg.vocab_size, (10,)).astype(np.int32)
        b = np.concatenate([a, rs.randint(
            1, cfg.vocab_size, (5,)).astype(np.int32)])
        cache.allocate(0, 0)
        self._packed(dec, cache, params, 0, a, 0)
        cache.publish_prefix(0, a)                 # stays LIVE: sharing
        cached = cache.attach_prefix(1, b)
        assert cached == 10                        # 2 full + fill-2 tail
        assert cached % cache.block_size != 0      # genuinely mid-block
        assert cache._ref[cache.block_table(0)[2]] == 2
        tok_b, logits_b = self._packed(dec, cache, params, 1, b, cached)
        assert cache.stats()["prefix_cache"]["cow_copies"] >= 1
        # uncached reference on a FRESH cache
        dec2, cache2 = self._setup(cfg)
        cache2.allocate(0, 0)
        tok_ref, logits_ref = self._packed(dec2, cache2, params, 0, b, 0)
        assert tok_b == tok_ref
        np.testing.assert_allclose(logits_b, logits_ref, atol=1e-4,
                                   rtol=1e-4)
        # the publisher's tail block survived the CoW: extending the
        # publisher's own prompt still matches an uncached reference
        a_ext = np.concatenate([a, rs.randint(
            1, cfg.vocab_size, (1,)).astype(np.int32)])
        cached_a = cache.attach_prefix(2, a_ext)
        assert cached_a == 10
        tok_a, logits_a = self._packed(dec, cache, params, 2, a_ext,
                                       cached_a)
        cache3 = self._setup(cfg)[1]
        cache3.allocate(0, 0)
        tok_aref, logits_aref = self._packed(dec2, cache3, params, 0,
                                             a_ext, 0)
        assert tok_a == tok_aref
        np.testing.assert_allclose(logits_a, logits_aref, atol=1e-4,
                                   rtol=1e-4)


class TestServerPrefixParity:
    """The served parity suite: cache-ON outputs must equal the
    cache-OFF path token-for-token, across shared prefixes ending
    mid-block (CoW), bursts, eviction pressure, and zero-hit traffic."""

    def _refs(self, model, prompts, new):
        return [model.generate(p[None], new).numpy()[0] for p in prompts]

    def test_sequential_shared_prefix_matches_solo(self, tiny_model):
        from paddle_tpu.inference import PagedGenerationServer

        model, cfg = tiny_model
        rs = np.random.RandomState(20)
        sys_p = rs.randint(1, cfg.vocab_size, (11,)).astype(np.int32)
        prompts = [np.concatenate([sys_p, rs.randint(
            1, cfg.vocab_size, (n,)).astype(np.int32)])
            for n in (3, 5, 2, 4)]
        prompts.append(prompts[0].copy())   # exact resubmission -> CoW
        srv = PagedGenerationServer(model, max_slots=2, block_size=4,
                                    max_prompt_len=20, max_new_tokens=4,
                                    enable_prefix_cache=True).start()
        try:
            for p, ref in zip(prompts, self._refs(model, prompts, 4)):
                np.testing.assert_array_equal(
                    srv.submit(p).result(timeout=300), ref)
            kv = srv.stats()["kv_cache"]
            assert kv["prefix_cache"]["hit_tokens"] > 0
            assert kv["prefix_cache"]["cow_copies"] >= 1
            assert kv["used_blocks"] == 0       # drained to the pool
            assert kv["retained_blocks"] > 0    # ... via retention
        finally:
            srv.stop()

    def test_burst_shared_prefix_matches_solo(self, tiny_model):
        """Concurrent slots sharing LIVE prefix blocks (refcount > 1
        on-device) must still match solo generate."""
        from paddle_tpu.inference import PagedGenerationServer

        model, cfg = tiny_model
        rs = np.random.RandomState(21)
        sys_p = rs.randint(1, cfg.vocab_size, (9,)).astype(np.int32)
        prompts = [np.concatenate([sys_p, rs.randint(
            1, cfg.vocab_size, (n,)).astype(np.int32)])
            for n in (2, 3, 4, 5, 2, 3)]
        srv = PagedGenerationServer(model, max_slots=3, block_size=4,
                                    max_prompt_len=16, max_new_tokens=3,
                                    enable_prefix_cache=True)
        # seed the cache, then burst the rest before the loop runs
        srv.start()
        srv.submit(prompts[0]).result(timeout=300)
        futs = [srv.submit(p) for p in prompts[1:]]
        try:
            refs = self._refs(model, prompts, 3)
            np.testing.assert_array_equal(
                srv.submit(prompts[0]).result(timeout=300), refs[0])
            for f, ref in zip(futs, refs[1:]):
                np.testing.assert_array_equal(f.result(timeout=300),
                                              ref)
            assert srv.stats()["kv_cache"]["prefix_cache"][
                "hit_tokens"] > 0
        finally:
            srv.stop()

    def test_parity_under_forced_eviction_pressure(self, tiny_model):
        """A pool barely above one request's worst case: every retained
        prefix is evicted by the next admission, and outputs must stay
        exact."""
        from paddle_tpu.inference import PagedGenerationServer

        model, cfg = tiny_model
        rs = np.random.RandomState(22)
        pa = rs.randint(1, cfg.vocab_size, (10,)).astype(np.int32)
        pb = rs.randint(1, cfg.vocab_size, (10,)).astype(np.int32)
        prompts = []
        for _ in range(2):          # alternate prefix families: each
            for base in (pa, pb):   # attach sees a warm OR evicted index
                prompts.append(np.concatenate([base, rs.randint(
                    1, cfg.vocab_size, (2,)).astype(np.int32)]))
        # worst = ceil((12 + 3)/4) + 1 CoW spare = 5; 6 usable blocks
        srv = PagedGenerationServer(model, max_slots=2, block_size=4,
                                    max_prompt_len=12, max_new_tokens=3,
                                    num_blocks=7,
                                    enable_prefix_cache=True).start()
        try:
            for p, ref in zip(prompts, self._refs(model, prompts, 3)):
                np.testing.assert_array_equal(
                    srv.submit(p).result(timeout=300), ref)
            pc = srv.stats()["kv_cache"]["prefix_cache"]
            assert pc["evictions"] > 0      # pressure actually evicted
        finally:
            srv.stop()

    def test_zero_hit_workload_and_disabled_fast_path(self, tiny_model):
        from paddle_tpu.inference import PagedGenerationServer

        model, cfg = tiny_model
        rs = np.random.RandomState(23)
        prompts = [rs.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in (5, 9, 7)]
        refs = self._refs(model, prompts, 3)
        # caching ON, disjoint prompts: zero hits, exact outputs
        srv = PagedGenerationServer(model, max_slots=2, block_size=4,
                                    max_prompt_len=12, max_new_tokens=3,
                                    enable_prefix_cache=True).start()
        try:
            for p, ref in zip(prompts, refs):
                np.testing.assert_array_equal(
                    srv.submit(p).result(timeout=300), ref)
            pc = srv.stats()["kv_cache"]["prefix_cache"]
            assert pc["hit_tokens"] == 0
            assert pc["lookups"] == len(prompts)
            assert pc["cow_copies"] == 0
        finally:
            srv.stop()
        # caching OFF (default): the exact pre-cache allocation path —
        # no lookups, no index, no retention, blocks free on release
        srv = PagedGenerationServer(model, max_slots=2, block_size=4,
                                    max_prompt_len=12,
                                    max_new_tokens=3).start()
        try:
            for p, ref in zip(prompts, refs):
                np.testing.assert_array_equal(
                    srv.submit(p).result(timeout=300), ref)
            kv = srv.stats()["kv_cache"]
            assert kv["prefix_cache"]["lookups"] == 0
            assert kv["prefix_cache"]["index_entries"] == 0
            assert kv["retained_blocks"] == 0
        finally:
            srv.stop()

    def test_on_off_servers_agree_token_for_token(self, tiny_model):
        """The direct acceptance check: the same prompt sequence
        through a cache-ON and a cache-OFF server yields identical
        sequences."""
        from paddle_tpu.inference import PagedGenerationServer

        model, cfg = tiny_model
        rs = np.random.RandomState(24)
        sys_p = rs.randint(1, cfg.vocab_size, (10,)).astype(np.int32)
        prompts = [np.concatenate([sys_p, rs.randint(
            1, cfg.vocab_size, (n,)).astype(np.int32)])
            for n in (1, 4, 2)] + [sys_p.copy()]
        outs = {}
        for on in (False, True):
            srv = PagedGenerationServer(
                model, max_slots=2, block_size=4, max_prompt_len=16,
                max_new_tokens=4, enable_prefix_cache=on).start()
            try:
                outs[on] = [srv.submit(p).result(timeout=300)
                            for p in prompts]
            finally:
                srv.stop()
        for a, b in zip(outs[False], outs[True]):
            np.testing.assert_array_equal(a, b)
