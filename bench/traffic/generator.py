"""The traffic generator: federated token streams and the rows each round
feeds, made from the seed.

``make_token_federation`` and its helpers are a copy of the program's
``repro.data.tokens`` generator, kept here so that the inputs a cell is
measured on cannot change with the program: each client draws from a
Zipf-like unigram source with bigram structure; priority clients share one
source, non-priority clients interpolate towards an independent one by a
per-client misalignment level.

``RowDraws`` stands in for the ``numpy`` generator that the program's
``launch.train.build_batches`` draws its rows with. It hands out rows
without replacement, cycling through a seeded permutation of each pool, so
the rounds that the correctness check follows train on rows that all
differ, and it logs every draw so that the reference can build the same
batches from the raw streams on its own.
"""
from __future__ import annotations

import numpy as np


def _zipf_probs(vocab, s=1.1, rng=None, perm=True):
    p = 1.0 / np.arange(1, vocab + 1) ** s
    p /= p.sum()
    if perm and rng is not None:
        p = p[rng.permutation(vocab)]
    return p


def _markov_stream(rng, n, vocab, unigram, shift):
    """Cheap bigram structure: next-token dist = unigram rolled by a
    source-specific shift of the previous token (deterministic mixing)."""
    toks = rng.choice(vocab, size=n, p=unigram)
    prev = np.roll(toks, 1)
    mix = (prev * shift) % vocab
    use_mix = rng.random(n) < 0.3
    return np.where(use_mix, mix, toks).astype(np.int32)


def make_token_federation(seed=0, vocab=512, n_clients=8, n_priority=4,
                          tokens_per_client=8192, seq_len=128,
                          misalign_max=1.0, misalign_skew=1.5):
    """Returns dict with tokens [C, n_seq, seq_len+1] (input+shifted label),
    priority_mask, weights, misalignment levels."""
    rng = np.random.default_rng(seed)
    pri_unigram = _zipf_probs(vocab, rng=rng)
    alt_unigram = _zipf_probs(vocab, rng=rng)
    n_seq = tokens_per_client // (seq_len + 1)
    C = n_clients

    streams, levels = [], []
    for c in range(C):
        if c < n_priority:
            lvl = 0.0
            unigram = pri_unigram
            shift = 3
        else:
            rank = (c - n_priority) / max(C - n_priority - 1, 1)
            lvl = min(1.0, misalign_max * rank ** misalign_skew)
            unigram = (1 - lvl) * pri_unigram + lvl * alt_unigram
            shift = 3 if lvl < 0.5 else 7
        streams.append(_markov_stream(rng, n_seq * (seq_len + 1), vocab,
                                      unigram, shift).reshape(n_seq, seq_len + 1))
        levels.append(lvl)

    priority_mask = np.zeros(C, bool)
    priority_mask[:n_priority] = True
    weights = np.full(C, 1.0 / n_priority, np.float32)
    # held-out global (priority-source) eval stream
    test = _markov_stream(rng, 64 * (seq_len + 1), vocab, pri_unigram, 3
                          ).reshape(64, seq_len + 1)
    return dict(tokens=np.stack(streams), priority_mask=priority_mask,
                weights=weights, misalignment=np.asarray(levels, np.float32),
                test_tokens=test)


def federation(traffic, vocab, seed):
    """The cell's federation from its traffic mix and the seed."""
    return make_token_federation(
        seed=seed, vocab=vocab, n_clients=traffic["clients"],
        n_priority=traffic["priority"],
        tokens_per_client=traffic["pool_sequences"] * (traffic["seq"] + 1),
        seq_len=traffic["seq"], misalign_max=traffic["misalign_max"])


class RowDraws:
    """Row draws for ``build_batches`` without replacement.

    ``build_batches`` asks ``integers(0, n, size)`` once for the client rows
    (``size=(clients, per_client)``) and once for the server rows
    (``size=(per_client,)``). Each pool (keyed by the call's place in the
    round) is a seeded permutation per client row; successive rounds take
    the next rows of it, so no row repeats until the pool is used up.
    ``log`` keeps every draw, in order."""

    def __init__(self, seed):
        self._rng = np.random.default_rng(seed)
        self._perms = {}
        self._cursor = {}
        self._call = 0
        self.log = []

    def integers(self, low, high, size):
        size = (size,) if np.isscalar(size) else tuple(size)
        key = (self._call % 2, high, size)
        self._call += 1
        rows, per = (size[0], size[1]) if len(size) == 2 else (1, size[0])
        if key not in self._perms:
            self._perms[key] = np.stack([self._rng.permutation(high - low)
                                         for _ in range(rows)]) + low
            self._cursor[key] = 0
        at = self._cursor[key] + np.arange(per)
        self._cursor[key] += per
        out = self._perms[key][:, at % (high - low)].reshape(size)
        self.log.append(out)
        return out
