"""Speculative decoding subsystem (round 11 tentpole).

Covers: eager SpecConfig validation, the n-gram/prompt-lookup drafter,
`PagedKVCache.truncate_seq` rollback semantics (incl. shared-prefix
safety), the packed verification plan layout, and the acceptance bar —
fixed-seed greedy AND sampled served output token-identical to
non-speculative decode (alone vs packed slots, penalties, prefix cache
ON/OFF, stop conditions), with the verify dispatch actually amortizing
decode dispatches when drafts are right."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.kv_cache import PagedKVCache
from paddle_tpu.models.gpt2 import GPT2, GPT2Config
from paddle_tpu.sampling import SamplingParams
from paddle_tpu.spec_decode import (DraftModelDrafter, NgramDrafter,
                                    SpecConfig, build_verify_plan)

from near_tie import (clear_prompt, compare_workload, ngram_drafts_during,
                      uniform_prompts)


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(11)
    cfg = GPT2Config.tiny()
    cfg.dropout = 0.0
    model = GPT2(cfg)
    model.eval()
    return model, cfg


class ReplayDrafter:
    """Test oracle: proposes the exact future tokens of a recorded
    reference continuation — 100% acceptance by construction, which
    pins down the all-accepted verify path (incl. sampled requests,
    where a real drafter's greedy guesses would mostly be rejected)."""

    def __init__(self, refs):
        self._refs = [np.asarray(r, np.int32) for r in refs]

    def propose(self, token_ids, max_tokens):
        ctx = np.asarray(token_ids, np.int32)
        for ref in self._refs:
            if ctx.size < ref.size and np.array_equal(ref[:ctx.size],
                                                      ctx):
                return ref[ctx.size:ctx.size + int(max_tokens)]
        return np.empty((0,), np.int32)


class CorruptingReplayDrafter(ReplayDrafter):
    """Replay drafter that deterministically corrupts ONE proposal
    token per round, at a depth that varies with the context length —
    so every round has a known-wrong draft and the accepted prefix
    length sweeps 0..K-1 across rounds. Exercises the partial-accept +
    rollback path on every single round (a draft-model drafter only
    does so by luck) at zero model cost."""

    def propose(self, token_ids, max_tokens):
        prop = np.array(super().propose(token_ids, max_tokens),
                        np.int32, copy=True)
        if prop.size:
            j = int(np.asarray(token_ids).size % prop.size)
            # always a DIFFERENT in-vocab token than the target's pick
            prop[j] = prop[j] - 1 if prop[j] > 0 else 1
        return prop


def _serve(model, subs, spec=None, **kw):
    from paddle_tpu.inference import PagedGenerationServer

    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_prompt_len", 16)
    kw.setdefault("max_new_tokens", 6)
    srv = PagedGenerationServer(model, speculation=spec, **kw)
    futs = [srv.submit(p, sampling=s) for p, s in subs]
    srv.start()
    try:
        return [f.result(timeout=300) for f in futs], srv.stats()
    finally:
        srv.stop()


class TestSpecConfig:
    @pytest.mark.parametrize("kw,field", [
        (dict(max_draft_tokens=0), "max_draft_tokens"),
        (dict(max_draft_tokens=2.5), "max_draft_tokens"),
        (dict(ngram_max_match=0), "ngram_max_match"),
        (dict(ngram_min_match=-1), "ngram_min_match"),
        (dict(drafter="bigram"), "drafter"),
        (dict(drafter=object()), "drafter"),
    ])
    def test_bad_value_names_field(self, kw, field):
        with pytest.raises(ValueError) as ei:
            SpecConfig(**kw)
        assert field in str(ei.value)

    def test_min_above_max_rejected(self):
        with pytest.raises(ValueError, match="ngram_min_match"):
            SpecConfig(ngram_min_match=4, ngram_max_match=2)

    def test_make_drafter(self):
        d = SpecConfig(ngram_max_match=2).make_drafter()
        assert isinstance(d, NgramDrafter) and d.max_match == 2
        custom = ReplayDrafter([])
        assert SpecConfig(drafter=custom).make_drafter() is custom

    def test_server_rejects_bad_combinations(self, tiny_model):
        from paddle_tpu.inference import PagedGenerationServer

        model, _ = tiny_model
        with pytest.raises(ValueError, match="steps_per_dispatch"):
            PagedGenerationServer(model, max_prompt_len=8,
                                  max_new_tokens=4, speculation=True,
                                  steps_per_dispatch=4)
        with pytest.raises(TypeError, match="SpecConfig"):
            PagedGenerationServer(model, max_prompt_len=8,
                                  max_new_tokens=4,
                                  speculation={"max_draft_tokens": 4})


class TestNgramDrafter:
    def test_proposes_continuation_of_repeated_suffix(self):
        d = NgramDrafter(max_match=3, min_match=1)
        ctx = np.array([1, 2, 3, 4, 1, 2, 3, 4, 1, 2, 3], np.int32)
        np.testing.assert_array_equal(d.propose(ctx, 4), [4, 1, 2, 3])

    def test_longest_match_wins(self):
        d = NgramDrafter(max_match=3, min_match=1)
        # suffix [7, 8] occurs earlier followed by 9; suffix [8] also
        # occurs even earlier followed by 5 — the 2-gram must win
        ctx = np.array([8, 5, 7, 8, 9, 1, 7, 8], np.int32)
        np.testing.assert_array_equal(d.propose(ctx, 1), [9])

    def test_most_recent_occurrence_wins(self):
        d = NgramDrafter(max_match=2, min_match=1)
        ctx = np.array([3, 4, 3, 5, 3], np.int32)   # "3" -> 4 then -> 5
        np.testing.assert_array_equal(d.propose(ctx, 1), [5])

    def test_no_match_and_short_context(self):
        d = NgramDrafter(max_match=3, min_match=1)
        assert d.propose(np.array([1, 2, 3], np.int32), 4).size == 0
        assert d.propose(np.array([7], np.int32), 4).size == 0
        assert d.propose(np.array([7, 7], np.int32), 0).size == 0

    def test_periodic_extension_fills_budget(self):
        """A short periodic context still yields a FULL proposal: the
        matched period is extrapolated cyclically (a fresh token run
        would otherwise never be proposed past its current length)."""
        d = NgramDrafter(max_match=1, min_match=1)
        ctx = np.array([5, 9, 5], np.int32)
        np.testing.assert_array_equal(d.propose(ctx, 8),
                                      [9, 5, 9, 5, 9, 5, 9, 5])
        run = np.array([3, 7, 7, 7], np.int32)
        np.testing.assert_array_equal(d.propose(run, 4), [7, 7, 7, 7])

    def test_validation(self):
        with pytest.raises(ValueError):
            NgramDrafter(max_match=1, min_match=2)


class TestTruncateSeq:
    def _cache(self, num_blocks=10, block_size=4):
        return PagedKVCache(1, 1, 2, block_size=block_size,
                            num_blocks=num_blocks)

    def test_rollback_frees_tail_blocks(self):
        c = self._cache()
        c.allocate("a", 14)                    # 4 blocks
        assert c.truncate_seq("a", 9) == 1     # back to 3 blocks
        assert c.seq_len("a") == 9
        assert len(c.block_table("a")) == 3
        assert c.free_block_count == 6
        assert c.truncate_seq("a", 9) == 0     # idempotent at same len
        # blocks are reusable immediately
        c.allocate("b", 4)
        assert c.free_block_count == 5

    def test_truncate_to_zero_and_errors(self):
        c = self._cache()
        c.allocate("a", 6)
        assert c.truncate_seq("a", 0) == 2
        assert c.seq_len("a") == 0 and c.block_table("a") == []
        with pytest.raises(ValueError, match="only rolls back"):
            c.truncate_seq("a", 1)
        with pytest.raises(KeyError, match="unknown sequence"):
            c.truncate_seq("ghost", 0)

    def test_shared_prefix_blocks_survive_rollback(self):
        """Speculative tails grown past an attached prefix roll back
        without disturbing the shared blocks or the content index."""
        c = self._cache()
        toks = np.arange(100, 108, dtype=np.int32)   # 2 full blocks
        c.allocate("a", 8)
        c.publish_prefix("a", toks)
        assert c.attach_prefix("b", np.concatenate(
            [toks, np.arange(5, dtype=np.int32)])) == 8
        shared = c.block_table("b")[:2]
        c.ensure("b", 13)                            # + speculative tail
        assert c.truncate_seq("b", 9) == 1           # rollback the tail
        assert c.block_table("b")[:2] == shared      # prefix intact
        assert c._ref[shared[0]] == 2                # still shared
        # rolling back INTO the shared region releases refcount-aware:
        # "a" keeps its blocks, the index keeps its entries
        assert c.truncate_seq("b", 4) == 2
        assert c._ref[shared[0]] == 2 and c._ref[shared[1]] == 1
        assert c.seq_len("a") == 8
        c.free("b")
        c.free("a")
        # everything indexed parks in retention; pool accounting exact
        assert c.free_block_count + c.retained_block_count \
            == c.num_blocks - 1

    def test_rollback_into_retained_entry_block(self):
        """Truncating a tail block that the index names parks it in the
        LRU retention list instead of the free list."""
        c = self._cache()
        toks = np.arange(10, dtype=np.int32)         # 2 full + fill 2
        c.allocate("a", 10)
        c.publish_prefix("a", toks)
        tail = c.block_table("a")[2]
        assert c.truncate_seq("a", 8) == 1           # drops the tail
        assert tail in c._retained                   # indexed: parked
        assert c.retained_block_count == 1


class TestVerifyPlan:
    def test_layout_and_buckets(self):
        entries = [
            (0, 7, 10, 3, np.array([1, 2], np.int32)),
            (2, 9, 4, 1, np.array([5], np.int32)),
            (3, 8, 6, 2, np.array([4, 5, 6], np.int32)),
        ]
        plan = build_verify_plan(entries, 4, pack_align=8)
        assert plan.rows == 3
        assert plan.dlen.shape[0] == 4               # P pow2-bucketed
        assert plan.toks.shape[0] == 32              # 3 regions * 8
        # row 0: [last=7, d=1,2] at positions 10..12, segment 0
        np.testing.assert_array_equal(plan.toks[:3], [7, 1, 2])
        np.testing.assert_array_equal(plan.pos[:3], [10, 11, 12])
        np.testing.assert_array_equal(plan.seg[:3], [0, 0, 0])
        assert plan.pos[3] == -1                     # packing pad
        # sample_idx clamps past each row's drafts (K1 = 5)
        np.testing.assert_array_equal(plan.sample_idx[0],
                                      [0, 1, 2, 2, 2])
        np.testing.assert_array_equal(plan.sample_idx[1],
                                      [8, 9, 9, 9, 9])
        np.testing.assert_array_equal(plan.dlen, [2, 1, 3, -1])
        np.testing.assert_array_equal(plan.steps, [3, 1, 2, 0])
        # grow covers [last] + drafts per row
        assert plan.grow_updates(["s0", "s2", "s3"]) == [
            ("s0", 13), ("s2", 6), ("s3", 10)]
        assert build_verify_plan([], 4, 8) is None


class TestSpecParity:
    """Acceptance bar: fixed-seed output under speculation is
    token-identical to non-speculative decode — greedy and sampled,
    whatever the acceptance pattern."""

    def test_greedy_ngram_matches_plain(self, tiny_model):
        model, cfg = tiny_model
        spec = SpecConfig(max_draft_tokens=3)
        # the first workload of the seeded stream in whose plain run the
        # n-gram drafter finds something to propose (a random model need
        # not repeat itself), checked on this host's own tokens
        rs = np.random.RandomState(1)
        for _ in range(16):
            prompts = [rs.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
                       for n in (3, 7, 5, 9)]
            subs = [(p, None) for p in prompts]
            ref, _ = _serve(model, subs)
            if any(ngram_drafts_during(r, p.size, spec)
                   for r, p in zip(ref, prompts)):
                break
        else:
            raise AssertionError("no workload the n-gram drafter drafts on")
        out, st = _serve(model, subs, spec=spec)
        for i, (a, b) in enumerate(zip(ref, out)):
            np.testing.assert_array_equal(a, b, err_msg=f"row {i}")
        sp = st["speculation"]
        assert sp["enabled"] and sp["proposed_tokens"] > 0
        assert sp["verify_dispatches"] > 0
        assert sp["proposed_tokens"] == (sp["accepted_tokens"]
                                         + sp["rolled_back_tokens"])

    def test_oracle_drafter_full_acceptance_fewer_dispatches(
            self, tiny_model):
        """A perfect drafter (replaying the reference continuation)
        must be fully accepted, emit K+1 tokens per verify dispatch,
        and cut dispatch count accordingly — the amortization the
        subsystem exists for."""
        model, cfg = tiny_model
        rs = np.random.RandomState(2)
        prompts = [rs.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in (4, 6)]
        subs = [(p, None) for p in prompts]
        ref, st_plain = _serve(model, subs, max_new_tokens=8)
        out, st = _serve(model, subs, max_new_tokens=8,
                         spec=SpecConfig(max_draft_tokens=7,
                                         drafter=ReplayDrafter(ref)))
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(a, b)
        sp = st["speculation"]
        assert sp["acceptance_rate"] == 1.0
        assert sp["rolled_back_tokens"] == 0
        # 8 new tokens: 1 from prefill, 7 from ONE verify dispatch
        # (vs 7 sequential decode steps without speculation)
        assert sp["verify_dispatches"] <= 2
        assert st["decode_steps"] < st_plain["decode_steps"]

    def test_sampled_fixed_seed_matches_plain(self, tiny_model):
        """Sampled requests: proposals with a known-wrong token at a
        varying depth every round are verified against the
        counter-based sampled target — whatever gets accepted, the
        emitted stream is the non-speculative one (every round
        exercises partial accept + rollback by construction)."""
        model, cfg = tiny_model
        rs = np.random.RandomState(3)
        prompts = [rs.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in (3, 7, 5)]
        sp = SamplingParams(temperature=1.0, top_p=0.9, seed=123)
        subs = [(p, sp) for p in prompts]
        ref, _ = _serve(model, subs)
        spec = SpecConfig(max_draft_tokens=3,
                          drafter=CorruptingReplayDrafter(ref))
        out, st = _serve(model, subs, spec=spec)
        for i, (a, b) in enumerate(zip(ref, out)):
            np.testing.assert_array_equal(a, b, err_msg=f"row {i}")
        sps = st["speculation"]
        assert sps["proposed_tokens"] > 0
        assert sps["rolled_back_tokens"] > 0  # every round had a miss

    def test_sampled_full_acceptance_via_replay(self, tiny_model):
        """Sampled + accepted drafts: the replay oracle forces a > 0
        under sampling, pinning the PRNG-step advance (base+j) and the
        penalty count deltas inside the verify dispatch."""
        model, cfg = tiny_model
        rs = np.random.RandomState(4)
        prompts = [rs.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in (4, 6)]
        sp = SamplingParams(temperature=1.1, top_k=8, seed=42,
                            presence_penalty=0.5)
        subs = [(p, sp) for p in prompts]
        ref, _ = _serve(model, subs)
        out, st = _serve(model, subs,
                         spec=SpecConfig(max_draft_tokens=3,
                                         drafter=ReplayDrafter(ref)))
        for i, (a, b) in enumerate(zip(ref, out)):
            np.testing.assert_array_equal(a, b, err_msg=f"row {i}")
        assert st["speculation"]["acceptance_rate"] == 1.0

    def test_alone_vs_packed_invariance_under_speculation(self,
                                                          tiny_model):
        """The PR 5 batch-invariance bar survives speculation: a fixed
        seed reproduces a request's tokens whether it runs alone
        without speculation or packed with speculating co-residents."""
        model, cfg = tiny_model
        rs = np.random.RandomState(5)
        target = rs.randint(1, cfg.vocab_size, (6,)).astype(np.int32)
        others = [rs.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
                  for n in (3, 8)]
        sp = SamplingParams(temperature=1.0, top_p=0.95, seed=321)
        alone = _serve(model, [(target, sp)])[0][0]
        spec = SpecConfig(max_draft_tokens=3,
                          drafter=DraftModelDrafter(model))
        packed = _serve(model, [(o, None) for o in others]
                        + [(target, sp)], spec=spec,
                        max_slots=3)[0][-1]
        np.testing.assert_array_equal(alone, packed)

    def test_prefix_cache_on_off_parity_under_speculation(self,
                                                          tiny_model):
        """Prefix cache ON vs OFF with speculation on both: identical
        fixed-seed tokens, and the cache pool drains clean despite
        attach/publish interleaving with speculative rollback."""
        model, cfg = tiny_model
        rs = np.random.RandomState(6)
        prefix = rs.randint(1, cfg.vocab_size, (10,)).astype(np.int32)
        tails = [rs.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
                 for n in (3, 5)]
        prompts = [np.concatenate([prefix, t]) for t in tails]
        sp = SamplingParams(temperature=1.1, top_p=0.9, seed=5150)
        ref, _ = _serve(model, [(p, sp) for p in prompts],
                        max_new_tokens=5)
        spec = SpecConfig(max_draft_tokens=3,
                          drafter=CorruptingReplayDrafter(ref))
        outs = {}
        for on in (False, True):
            from paddle_tpu.inference import PagedGenerationServer

            srv = PagedGenerationServer(
                model, max_slots=2, block_size=4, max_prompt_len=16,
                max_new_tokens=5, enable_prefix_cache=on,
                speculation=spec).start()
            try:
                outs[on] = [srv.submit(p, sampling=sp)
                            .result(timeout=300) for p in prompts]
                if on:
                    assert srv.cache.stats()["prefix_cache"]["hits"] >= 1
                assert srv.cache.stats()["used_blocks"] == 0
            finally:
                srv.stop()
        for a, b, r in zip(outs[False], outs[True], ref):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, r)  # == non-speculative

    @pytest.fixture(scope="class")
    def distinct_start(self, tiny_model):
        """A prompt whose served continuation starts with three distinct
        tokens (a random tiny model repeats itself, and a stop token that
        also came earlier would end the request there): the first such
        prompt of a seeded stream, checked on this host's own run."""
        model, cfg = tiny_model
        rs = np.random.RandomState(7)
        for _ in range(64):
            p = rs.randint(1, cfg.vocab_size, (4,)).astype(np.int32)
            ref = _serve(model, [(p, None)], max_new_tokens=6)[0][0]
            if len(set(ref[p.size:p.size + 3].tolist())) == 3:
                return p, ref
        raise AssertionError("no prompt with three distinct first tokens")

    @pytest.mark.parametrize("position", [1, 2, 3])
    def test_stop_token_inside_accepted_prefix(self, tiny_model,
                                               distinct_start, position):
        """A stop token emitted mid-prefix must end the request there —
        accepted drafts beyond it are discarded, matching plain
        decode's behavior exactly."""
        model, cfg = tiny_model
        p, ref = distinct_start
        stop = int(ref[p.size + position - 1])
        sp = SamplingParams(stop_token_ids=(stop,))
        plain = _serve(model, [(p, sp)], max_new_tokens=6)[0][0]
        # K=7 reuses the oracle test's compiled verify width
        spec = SpecConfig(max_draft_tokens=7,
                          drafter=ReplayDrafter([ref]))
        out, st = _serve(model, [(p, sp)], max_new_tokens=6, spec=spec)
        np.testing.assert_array_equal(out[0], plain)
        assert out[0].size == p.size + position
        assert out[0][-1] == stop
        assert st["stop_reasons"]["stop_token"] == 1

    def test_verify_failure_cleans_up_and_serves_on(self, tiny_model,
                                                    monkeypatch):
        """With the recovery ladder DISABLED (r17: recovery=False pins
        the legacy blast radius — the default now retries instead), a
        verify dispatch that raises must fail exactly the speculating
        requests, release their blocks, and leave the server serving
        later requests."""
        from paddle_tpu.inference import PagedGenerationServer

        model, cfg = tiny_model
        rs = np.random.RandomState(8)
        # the drafter replays this engine's own continuation, so the very
        # first decode round holds a proposal and dispatches a verify
        rep = np.tile(np.array([5, 6, 7], np.int32), 4)
        served = _serve(model, [(rep, None)], max_new_tokens=4)[0]
        srv = PagedGenerationServer(
            model, max_slots=2, block_size=4, max_prompt_len=16,
            max_new_tokens=4, recovery=False,
            speculation=SpecConfig(max_draft_tokens=3,
                                   drafter=ReplayDrafter(served)))
        boom = {"armed": True}
        real = srv._decoder.packed_verify

        def flaky(*a, **kw):
            if boom.pop("armed", False):
                raise RuntimeError("injected verify failure")
            return real(*a, **kw)

        monkeypatch.setattr(srv._decoder, "packed_verify", flaky)
        srv.start()
        try:
            bad = srv.submit(rep)
            with pytest.raises(RuntimeError, match="injected"):
                bad.result(timeout=300)
            assert srv.cache.stats()["used_blocks"] == 0
            # dense against paged: equal up to a near-tie of the reference
            p = clear_prompt(model,
                             uniform_prompts(rs, cfg.vocab_size, 4), 4)
            compare_workload(
                model, [model.generate(p[None], 4).numpy()[0]],
                [srv.submit(p).result(timeout=300)], [p])
        finally:
            srv.stop()

    def test_disabled_speculation_keeps_schema_zeroed(self, tiny_model):
        model, cfg = tiny_model
        out, st = _serve(model, [(np.array([1, 2, 3], np.int32), None)])
        sp = st["speculation"]
        assert sp["enabled"] is False
        assert sp["proposed_tokens"] == 0
        assert sp["verify_dispatches"] == 0
