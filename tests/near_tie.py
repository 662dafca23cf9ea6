"""Token comparison for served-parity tests that pins no host's arithmetic.

Greedy tokens of a seeded random model are equal between two engines
(quantized against unquantized, paged against dense) only while the
reference's choice is clear.  Where its two best logits lie closer than
the drift the engines are allowed, either token is a right answer and
every token after it follows from that choice: the comparison ends
there.  The margins come from one full float32 forward of the model over
the reference's own sequence, on this host, so no token is pinned to
another machine's rounding.  A random tiny model ties often (its first
new token about every second time), so a test draws its prompts with
`clear_prompt`, which keeps the first candidate of a seeded stream whose
reference has no near-tie at all, and `compare_workload` asserts that the
comparison was not hollow.  `ngram_drafts_during` is the same idea for
speculation: whether a drafter has anything to propose is read from the
tokens this host produced, not assumed of the model.
"""
import functools

import jax
import numpy as np

from paddle_tpu.core.tensor import Tensor

# Logit drift allowed between an int8 engine and the unquantized one, as a
# share of the step's logit scale (docs/SERVING.md, "Quantized serving").
LOGIT_TOL = 0.05


@functools.lru_cache(maxsize=None)
def _forward(model):
    return jax.jit(lambda p, ids: model.functional_call(p, {}, Tensor(ids))
                   ._value.astype("float32"))


def reference_logits(model, seqs):
    """float32 logits of one cache-free forward: a list of [len(s), V],
    one for each sequence.  Rows are padded on the right (the model is
    causal) to few distinct shapes, so a module compiles this once or
    twice."""
    width = -(-max(len(s) for s in seqs) // 32) * 32
    rows = -(-len(seqs) // 8) * 8
    ids = np.zeros((rows, width), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
    logits = np.asarray(_forward(model)(model.functional_state()[0], ids))
    return [logits[i, :len(s)] for i, s in enumerate(seqs)]


def first_near_tie(logits, prompt_len, n_new, tol=LOGIT_TOL):
    """(g, top2): the first generated index g whose deciding logits (row
    prompt_len + g - 1) hold two candidates within `tol` of that row's
    scale, and those two tokens; (n_new, None) when every choice is
    clear."""
    for g in range(n_new):
        row = logits[prompt_len + g - 1]
        second, first = np.argsort(row)[-2:]
        scale = max(float(np.abs(row).max()), 1.0)
        if row[first] - row[second] < tol * scale:
            return g, (int(first), int(second))
    return n_new, None


def compare_to_first_near_tie(ref, out, prompt_len, logits, tol=LOGIT_TOL):
    """Assert `out` is `ref` up to the reference's first near-tie, where
    either candidate is accepted and nothing later is compared.  Returns
    how many generated tokens were compared exactly."""
    ref, out = np.asarray(ref), np.asarray(out)
    g, top2 = first_near_tie(logits, prompt_len, ref.size - prompt_len, tol)
    end = prompt_len + g
    np.testing.assert_array_equal(
        out[:end], ref[:end],
        err_msg=f"differs before the reference's first near-tie "
                f"(generated index {g})")
    if top2 is None:
        assert out.size == ref.size, (out.size, ref.size)
    else:
        assert out.size > end and int(out[end]) in top2, (out[end:], top2)
    return g


def compare_workload(model, refs, outs, prompts, tol=LOGIT_TOL,
                     min_share=0.8):
    """`compare_to_first_near_tie` over a workload, with the margins of
    one full forward over the references; and the comparison must not be
    hollow: at least `min_share` of the references' generated tokens lie
    before any near-tie.  Returns (compared, generated)."""
    refs = [np.asarray(r) for r in refs]
    compared = sum(
        compare_to_first_near_tie(ref, out, len(p), logits, tol)
        for ref, out, p, logits in zip(refs, outs, prompts,
                                       reference_logits(model, refs)))
    generated = sum(r.size - len(p) for r, p in zip(refs, prompts))
    assert compared >= min_share * generated, (
        f"only {compared} of {generated} generated tokens lie before a "
        f"near-tie of the reference: draw the prompts with clear_prompt")
    print(f"[near_tie] compared {compared} of {generated} generated tokens")
    return compared, generated


def uniform_prompts(rs, vocab_size, length):
    """A `draw` for `clear_prompt`: n prompts of `length` uniform tokens
    from the generator `rs`."""
    return lambda n: rs.randint(1, vocab_size, (n, length))


def clear_prompt(model, draw, n_new, tol=LOGIT_TOL, batches=16, batch=64):
    """The first prompt of `draw`'s stream whose greedy continuation by
    `n_new` tokens meets no near-tie.  `draw(n)` returns n candidates of
    one length, `[n, L]` int32, from the caller's seeded generator, so
    the choice is made from this host's own arithmetic."""
    for _ in range(batches):
        cands = np.asarray(draw(batch), np.int32)
        length = cands.shape[1]
        refs = list(model.generate(cands, n_new).numpy())
        for cand, logits in zip(cands, reference_logits(model, refs)):
            if first_near_tie(logits, length, n_new, tol)[1] is None:
                return cand
    raise AssertionError(
        f"no prompt in {batches * batch} whose {n_new} new tokens are "
        f"all clear of a near-tie")


def ngram_drafts_during(seq, prompt_len, spec):
    """Whether `spec`'s drafter proposes anything at some decode round of
    a request that generates `seq` (its context then ends with a token
    generated so far; the last token is never a context's end)."""
    drafter = spec.make_drafter()
    return any(drafter.propose(seq[:k], spec.max_draft_tokens).size
               for k in range(prompt_len + 1, len(seq)))
