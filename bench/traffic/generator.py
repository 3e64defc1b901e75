"""The one traffic generator: closed-loop federated rounds over a synthetic
classification task, every number taken from a traffic file.

A pool of ``pool_rows`` sequences of ``seq_len`` tokens is drawn as the
program's synthetic tasks draw theirs: a Zipf background over the
vocabulary, with a ``signal`` share of positions replaced by keywords of
the row's class. The pool is split over ``total_clients`` clients with
per-class Dirichlet(``dirichlet_alpha``) proportions, each client holding at
least ``batch_size * first_rounds`` rows. Each round picks
``clients_per_round`` distinct clients, and each picked client hands over the
next ``batch_size`` rows of its own shuffled shard, so the rows of the
first ``first_rounds`` rounds all differ. Everything is drawn from the seed,
and every seed gives rounds of the same shapes.
"""
from __future__ import annotations

import numpy as np


def make_pool(rng, n_rows, seq_len, vocab, n_classes, signal):
    ranks = np.arange(1, vocab + 1)
    background = (1.0 / ranks) / np.sum(1.0 / ranks)
    n_keywords = max(4, vocab // (8 * n_classes))
    keywords = [rng.choice(vocab // 2, size=n_keywords, replace=False)
                + vocab // 2 for _ in range(n_classes)]
    y = rng.integers(0, n_classes, size=n_rows)
    x = rng.choice(vocab, size=(n_rows, seq_len), p=background)
    mask = rng.random((n_rows, seq_len)) < signal
    for c in range(n_classes):
        rows = y == c
        kw = rng.choice(keywords[c], size=(int(rows.sum()), seq_len))
        x[rows] = np.where(mask[rows], kw, x[rows])
    return x.astype(np.int32), y.astype(np.int32)


def dirichlet_shards(rng, labels, n_clients, alpha, min_rows):
    """Per class, split its rows over clients in Dirichlet(alpha)
    proportions; then move rows from the largest shard to any shard below
    ``min_rows``."""
    shards = [[] for _ in range(n_clients)]
    for c in range(int(labels.max()) + 1):
        idx = np.flatnonzero(labels == c)
        rng.shuffle(idx)
        cuts = (np.cumsum(rng.dirichlet(np.full(n_clients, alpha)))
                * len(idx)).astype(int)[:-1]
        for client, part in enumerate(np.split(idx, cuts)):
            shards[client].extend(part.tolist())
    shards = [np.array(sorted(s), np.int64) for s in shards]
    if n_clients * min_rows > len(labels):
        raise ValueError("pool too small for the clients' minimum shards")
    for client in range(n_clients):
        while len(shards[client]) < min_rows:
            donor = int(np.argmax([len(s) for s in shards]))
            shards[client] = np.append(shards[client], shards[donor][-1])
            shards[donor] = shards[donor][:-1]
    return shards


class Rounds:
    """``next()`` gives one round: tokens (M, B, T) int32, labels (M, B)
    int32."""

    def __init__(self, spec, vocab, n_classes, seed):
        self.spec = spec
        self.rng = np.random.default_rng(seed)
        self.x, self.y = make_pool(self.rng, spec["pool_rows"],
                                   spec["seq_len"], vocab, n_classes,
                                   spec["signal"])
        shards = dirichlet_shards(self.rng, self.y, spec["total_clients"],
                                  spec["dirichlet_alpha"],
                                  spec["batch_size"] * spec["first_rounds"])
        self.order = [self.rng.permutation(s) for s in shards]
        self.pos = [0] * len(shards)

    def _rows(self, client, n):
        out = []
        while len(out) < n:
            if self.pos[client] == len(self.order[client]):
                self.order[client] = self.rng.permutation(self.order[client])
                self.pos[client] = 0
            order, pos = self.order[client], self.pos[client]
            take = min(n - len(out), len(order) - pos)
            out.extend(order[pos:pos + take])
            self.pos[client] += take
        return np.asarray(out)

    def next(self):
        spec = self.spec
        chosen = self.rng.choice(spec["total_clients"],
                                 spec["clients_per_round"], replace=False)
        rows = np.stack([self._rows(int(c), spec["batch_size"])
                         for c in chosen])
        return self.x[rows], self.y[rows]
