"""Seeded inputs, owned by the benchmark.  Plain numpy only: DataLoader
workers are spawned processes that unpickle these classes by import and
must never touch a JAX backend."""
from __future__ import annotations

from statistics import NormalDist

import numpy as np

MLM_IGNORE = -100


def rng(seed, *stream):
    """A generator for `seed` (any non-negative whole number, also past
    2**32) and a stream label, independent across labels."""
    return np.random.default_rng([int(seed), *map(int, stream)])


class SeededSequences:
    """Map-style dataset: item i is one fresh sequence of uniform random
    tokens, a pure function of (seed, i).  objective "lm": seq+1 tokens,
    collated to inputs and next-token labels.  objective "mlm": seq tokens
    with exactly round(mask_prob * seq) positions replaced by `mask_id`,
    labels -100 elsewhere (a fixed count, so every batch is the same
    amount of work)."""

    def __init__(self, seed, n_items, seq, vocab_size, objective,
                 mask_prob=0.15, mask_id=103):
        if objective not in ("lm", "mlm"):
            raise ValueError(f"unknown objective {objective!r}")
        self.seed, self.n, self.seq = int(seed), int(n_items), int(seq)
        self.vocab, self.objective = int(vocab_size), objective
        self.n_mask = int(round(mask_prob * seq))
        self.mask_id = int(mask_id)

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        g = rng(self.seed, 1, i)
        if self.objective == "lm":
            return g.integers(0, self.vocab, self.seq + 1, dtype=np.int32)
        ids = g.integers(0, self.vocab, self.seq, dtype=np.int32)
        labels = np.full(self.seq, MLM_IGNORE, np.int32)
        where = g.choice(self.seq, self.n_mask, replace=False)
        labels[where] = ids[where]
        ids[where] = self.mask_id
        return np.stack([ids, labels])


def collate_lm(samples):
    ids = np.stack(samples)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def collate_mlm(samples):
    both = np.stack(samples)
    return {"input_ids": both[:, 0], "labels": both[:, 1]}


COLLATE = {"lm": collate_lm, "mlm": collate_mlm}


def lognormal_quantiles(n, median, sigma, lo, hi):
    """n whole lengths: the quantiles (i + 1/2) / n of a lognormal with the
    given median, clipped — the distribution's shape with no sampling
    noise, so a small multiset stands for it."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(np.log(median) + sigma * z)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def request_sizes(spec):
    """The cell's fixed multiset of (prompt_len, new_tokens) pairs as a
    k x k grid, k = sqrt(distinct_sizes): row a holds the a-th k-quantile
    group of the prompt lengths, and the outputs are dealt as a Latin
    square (cell (a, b) gets member a of output group (a + b) mod k), so
    every row holds one output of every group and the two lengths are all
    but unrelated (r = 0.11).
    Nothing here depends on --seed: every seed offers the same sizes."""
    n = int(spec["distinct_sizes"])
    k = int(round(n ** 0.5))
    if k * k != n or k & (k - 1):
        raise ValueError("distinct_sizes must be the square of a power of 2")
    p, o = spec["prompt_len"], spec["new_tokens"]
    prompts = lognormal_quantiles(n, p["median"], p["sigma"], p["min"],
                                  p["max"])
    outputs = lognormal_quantiles(n, o["median"], o["sigma"], o["min"],
                                  o["max"])
    return [[(int(prompts[k * a + b]), int(outputs[k * ((a + b) % k) + a]))
             for b in range(k)] for a in range(k)]


class RequestStream:
    """An endless stream of (prompt tokens, new_tokens).  A pass hands out
    every cell of the grid once, in k blocks of k: block m takes cell
    (a, (m + 2a) mod k) of every row a, which is one prompt of every length
    group and one output of every length group (output group (m + 3a) mod
    k: 3 is coprime to a power of two).  So any k consecutive requests are
    the whole mix in small, and a window's work does not depend on where
    it cuts the stream.  `seed` orders the blocks of a pass and the
    requests inside a block, and draws the prompt tokens: unshared uniform
    random ids in [1, vocab)."""

    def __init__(self, spec, vocab_size, seed):
        self.grid = request_sizes(spec)
        self.k = len(self.grid)
        self.vocab = int(vocab_size)
        self.g = rng(seed, 3)
        self.order = []

    def __next__(self):
        if not self.order:
            k = self.k
            for m in self.g.permutation(k):
                self.order.extend(
                    self.grid[a][(m + 2 * a) % k]
                    for a in self.g.permutation(k))
            self.order.reverse()
        plen, new = self.order.pop()
        return (self.g.integers(1, self.vocab, plen, dtype=np.int32), new)
