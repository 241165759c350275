"""IBM Quest synthetic transactions, as Agrawal & Srikant publish them.

"Fast Algorithms for Mining Association Rules", VLDB 1994, section
2.4.3 -- the generator behind T10I4D100K and T40I10D100K:

* |L| potentially large itemsets ("patterns"). Their sizes are Poisson
  with mean |I|. The first pattern's items are drawn at random; each
  later pattern takes a fraction of its items from the previous one,
  the fraction exponentially distributed with mean equal to the
  correlation level (0.5), and draws the rest at random.
* Each pattern has a weight, exponential with unit mean, normalised so
  the weights sum to 1, and a corruption level, normal with mean 0.5
  and variance 0.1. When a pattern is put in a transaction, items are
  dropped from it as long as a uniform draw is below its corruption
  level.
* Transaction sizes are Poisson with mean |T|. Each transaction is
  filled with patterns chosen by an |L|-sided weighted coin. A pattern
  that does not fit goes into the transaction anyway in half the cases
  and moves on to the next transaction in the rest.

Transactions are item sets, so duplicates that overlapping patterns
bring are removed; a transaction that would be empty is never made
(its first pattern always goes in). Everything but the last step runs
as whole-array numpy; cutting the pattern stream into transactions is
one ``searchsorted`` per transaction.

``generate`` returns a :class:`Transactions` in CSR form (``offsets``,
``items``), items sorted within each transaction.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass(frozen=True)
class QuestParams:
    n_transactions: int      # |D|
    avg_transaction: float   # |T|
    avg_pattern: float       # |I|
    n_items: int             # N
    n_patterns: int          # |L|
    correlation: float = 0.5
    corruption_mean: float = 0.5
    corruption_var: float = 0.1

    @classmethod
    def from_config(cls, gen: dict) -> "QuestParams":
        return cls(n_transactions=int(gen["D"]),
                   avg_transaction=float(gen["T"]),
                   avg_pattern=float(gen["I"]),
                   n_items=int(gen["N"]),
                   n_patterns=int(gen["L"]),
                   correlation=float(gen["correlation"]),
                   corruption_mean=float(gen["corruption_mean"]),
                   corruption_var=float(gen["corruption_var"]))


@dataclass
class Transactions:
    """CSR transactions: transaction t holds
    ``items[offsets[t]:offsets[t + 1]]``, sorted and distinct."""
    offsets: np.ndarray      # [n + 1] int64
    items: np.ndarray        # [nnz] int32
    n_items: int

    def __len__(self) -> int:
        return len(self.offsets) - 1

    def lengths(self) -> np.ndarray:
        return np.diff(self.offsets)

    def tx_ids(self) -> np.ndarray:
        """Transaction id of every entry of ``items``."""
        return np.repeat(np.arange(len(self), dtype=np.int64),
                         self.lengths())

    def slice(self, lo: int, hi: int) -> "Transactions":
        a, b = self.offsets[lo], self.offsets[hi]
        return Transactions(self.offsets[lo:hi + 1] - a,
                            self.items[a:b], self.n_items)

    def to_lists(self) -> List[List[int]]:
        """Plain Python lists, the form the system's entry points take."""
        return [a.tolist() for a in
                np.split(self.items, self.offsets[1:-1])]


def _patterns(p: QuestParams, rng: np.random.Generator):
    """The |L| patterns as CSR (offsets, items), with weights and
    corruption levels."""
    sizes = np.maximum(rng.poisson(p.avg_pattern, p.n_patterns), 1)
    sizes = np.minimum(sizes, p.n_items)
    fractions = np.minimum(rng.exponential(p.correlation, p.n_patterns),
                           1.0)
    pats: List[np.ndarray] = []
    prev = np.empty(0, np.int64)
    for j in range(p.n_patterns):
        size = int(sizes[j])
        n_old = min(int(round(fractions[j] * size)), len(prev)) if j else 0
        old = rng.choice(prev, n_old, replace=False) if n_old else prev[:0]
        pool = np.setdiff1d(np.arange(p.n_items), old, assume_unique=True)
        new = rng.choice(pool, size - n_old, replace=False)
        prev = np.sort(np.concatenate([old, new]))
        pats.append(prev)
    offsets = np.zeros(p.n_patterns + 1, np.int64)
    offsets[1:] = np.cumsum([len(x) for x in pats])
    weights = rng.exponential(1.0, p.n_patterns)
    weights /= weights.sum()
    corruption = np.clip(rng.normal(p.corruption_mean,
                                    np.sqrt(p.corruption_var),
                                    p.n_patterns), 0.0, 0.999)
    return offsets, np.concatenate(pats), weights, corruption


def generate(p: QuestParams, seed: int) -> Transactions:
    rng = np.random.default_rng(seed)
    p_off, p_items, weights, corruption = _patterns(p, rng)
    p_len = np.diff(p_off)
    n = p.n_transactions
    target = np.maximum(rng.poisson(p.avg_transaction, n), 1)
    # the pattern stream, corrupted instance by instance: enough
    # instances for every target with room for the half that overflow
    mean_kept = float(np.dot(weights, p_len * 0.5 + 0.5))
    n_inst = int(target.sum() / max(mean_kept, 1.0) * 1.5) + 64
    inst = rng.choice(p.n_patterns, n_inst, p=weights)
    # items dropped: a geometric count, stopping at the first uniform
    # draw at or above the corruption level
    drop = rng.geometric(1.0 - corruption[inst]) - 1
    keep = np.maximum(p_len[inst] - drop, 0)
    # flatten the instances' pattern items and keep, per instance, the
    # ``keep`` items that rank first under a random key
    inst_len = p_len[inst]
    inst_id = np.repeat(np.arange(n_inst), inst_len)
    start = np.repeat(p_off[inst], inst_len)
    pos = np.arange(len(inst_id)) - np.repeat(np.cumsum(inst_len) - inst_len,
                                              inst_len)
    flat = p_items[start + pos]
    order = np.argsort(inst_id + rng.random(len(inst_id)), kind="stable")
    rank = np.empty(len(order), np.int64)
    first = np.repeat(np.cumsum(inst_len) - inst_len, inst_len)
    rank[order] = np.arange(len(order)) - first
    kept = rank < keep[inst_id]
    flat, inst_id = flat[kept], inst_id[kept]
    # cut the stream into transactions; empty instances are skipped
    nz = np.nonzero(keep)[0]
    k_len = keep[nz]
    cum = np.concatenate([[0], np.cumsum(k_len)])
    coin = rng.random(n) < 0.5
    owner = np.full(n_inst, -1, np.int64)
    j = 0
    for t in range(n):
        if j >= len(nz):
            raise RuntimeError("pattern stream exhausted; raise n_inst")
        # instances j..k-1 fit within the target size
        k = int(np.searchsorted(cum, cum[j] + target[t], side="right")) - 1
        if k == j or coin[t]:
            k += 1              # the one that does not fit goes in anyway
        owner[nz[j:k]] = t
        j = k
    tx = owner[inst_id]
    sel = tx >= 0
    key = np.unique(tx[sel] * p.n_items + flat[sel])
    tx_of, items = np.divmod(key, p.n_items)
    offsets = np.zeros(n + 1, np.int64)
    offsets[1:] = np.cumsum(np.bincount(tx_of, minlength=n))
    return Transactions(offsets, items.astype(np.int32), p.n_items)


def relabel(db: Transactions, seed: int, blocks=()) -> Transactions:
    """The same transactions under a random permutation of item ids,
    with transaction order shuffled within each block (``blocks`` are
    cut points; none = one block). Mining work depends on the itemset
    lattice, which a relabelling keeps, so runs with different seeds do
    the same amount of work on different bit layouts."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(db.n_items).astype(np.int32)
    n = len(db)
    cuts = [0, *blocks, n]
    new_tx = np.empty(n, np.int64)
    for a, b in zip(cuts[:-1], cuts[1:]):
        new_tx[a:b] = a + rng.permutation(b - a)
    tx = new_tx[db.tx_ids()]
    key = np.sort(tx * db.n_items + perm[db.items])
    tx_of, items = np.divmod(key, db.n_items)
    offsets = np.zeros(n + 1, np.int64)
    offsets[1:] = np.cumsum(np.bincount(tx_of, minlength=n))
    return Transactions(offsets, items.astype(np.int32), db.n_items)
