"""The plain reference: frequent itemsets and supports from the
transactions alone.

It imports nothing of the system under test and takes nothing it made.
Each item's transactions are one packed bit row (uint64 words); a
depth-first walk over equivalence classes ANDs the class prefix's row
with its candidate extensions' rows and counts bits, one numpy pass per
class. Supports are exact integers.

``boundaries`` (sorted transaction counts) makes every support a vector:
the itemset's support over each prefix ``db[:b]`` of the transactions.
A stream's generations are such prefixes, so one walk over the last
boundary answers every generation: an itemset frequent at any
generation is frequent at the last boundary under the smallest
threshold, because supports only grow with the prefix.

``count_dtype`` is for the control alone: the sums of per-word counts
are taken in that dtype (bfloat16: the rounding a cheaper counter would
bring) instead of int64.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

Itemset = Tuple[int, ...]


def min_support_count(fraction: float, n_transactions: int) -> int:
    """A support fraction as a transaction count: floor, at least 1."""
    return max(1, int(fraction * n_transactions))


def item_rows(tx_ids: np.ndarray, items: np.ndarray, n_items: int,
              n_transactions: int) -> np.ndarray:
    """[n_items, ceil(n/64)] uint64: bit t of row i is set when
    transaction t holds item i."""
    n_words = (n_transactions + 63) // 64
    rows = np.zeros((n_items, n_words), np.uint64)
    bit = np.left_shift(np.uint64(1), (tx_ids & 63).astype(np.uint64))
    np.bitwise_or.at(rows, (items.astype(np.int64), tx_ids >> 6), bit)
    return rows


class Counter:
    """Support counts of row blocks at a list of prefix boundaries."""

    def __init__(self, n_words: int, boundaries: Sequence[int],
                 count_dtype=None):
        self.boundaries = np.asarray(boundaries, np.int64)
        self.count_dtype = count_dtype
        self.word = self.boundaries >> 6
        rem = (self.boundaries & 63).astype(np.uint64)
        self.partial = np.where(
            rem > 0,
            np.left_shift(np.uint64(1), rem) - np.uint64(1),
            np.uint64(0)).astype(np.uint64)
        self.n_words = n_words

    def counts(self, rows: np.ndarray) -> np.ndarray:
        """rows [E, W] -> [E, len(boundaries)] supports."""
        per_word = np.bitwise_count(rows)
        if self.count_dtype is not None:
            return self._counts_lossy(rows, per_word)
        cum = np.zeros((rows.shape[0], self.n_words + 1), np.int64)
        np.cumsum(per_word, axis=1, dtype=np.int64, out=cum[:, 1:])
        out = cum[:, self.word]
        edge = self.word < self.n_words
        if edge.any():
            w = self.word[edge]
            out[:, edge] += np.bitwise_count(
                rows[:, w] & self.partial[edge][None, :]).astype(np.int64)
        return out

    def _counts_lossy(self, rows, per_word):
        out = np.zeros((rows.shape[0], len(self.boundaries)), np.int64)
        for b, (w, mask) in enumerate(zip(self.word, self.partial)):
            parts = per_word[:, :w].astype(self.count_dtype)
            total = parts.sum(axis=1, dtype=self.count_dtype)
            if w < self.n_words:
                total = (total + np.bitwise_count(rows[:, w] & mask)
                         .astype(self.count_dtype))
            out[:, b] = total.astype(np.float64).astype(np.int64)
        return out


def mine(rows: np.ndarray, thresholds: Sequence[int],
         boundaries: Sequence[int], count_dtype=None
         ) -> Dict[Itemset, np.ndarray]:
    """Every itemset whose support at boundary g reaches thresholds[g]
    for some g, mapped to its supports at every boundary.

    ``rows`` must hold no bit at or past the last boundary."""
    thresholds = np.asarray(thresholds, np.int64)
    counter = Counter(rows.shape[1], boundaries, count_dtype)
    floor = int(thresholds.min())

    def frequent(c: np.ndarray) -> np.ndarray:
        return (c >= thresholds[None, :]).any(axis=1)

    c1 = counter.counts(rows)
    keep = frequent(c1)
    items = np.nonzero(keep)[0]
    out: Dict[Itemset, np.ndarray] = {(int(i),): c1[i] for i in items}
    # each stack entry: (prefix, its row, candidate extensions after it)
    stack: List[Tuple[Itemset, np.ndarray, np.ndarray]] = [
        ((int(i),), rows[i], items[k + 1:]) for k, i in enumerate(items)]
    while stack:
        prefix, prow, exts = stack.pop()
        if not len(exts):
            continue
        block = rows[exts] & prow[None, :]
        # the last boundary's count bounds every earlier one
        total = np.bitwise_count(block).sum(axis=1, dtype=np.int64)
        live = total >= floor
        if not live.any():
            continue
        exts, block = exts[live], block[live]
        c = counter.counts(block)
        ok = frequent(c)
        exts, block, c = exts[ok], block[ok], c[ok]
        for k, e in enumerate(exts):
            x = prefix + (int(e),)
            out[x] = c[k]
            stack.append((x, block[k], exts[k + 1:]))
    return out


def supports_of(rows: np.ndarray, itemsets: Iterable[Sequence[int]],
                boundaries: Sequence[int]) -> List[np.ndarray]:
    """Supports of arbitrary itemsets at every boundary."""
    counter = Counter(rows.shape[1], boundaries)
    out = []
    for x in itemsets:
        r = rows[int(x[0])].copy()
        for i in x[1:]:
            r &= rows[int(i)]
        out.append(counter.counts(r[None, :])[0])
    return out


def frequent_at(table: Dict[Itemset, np.ndarray], g: int, threshold: int
                ) -> Dict[Itemset, int]:
    """The complete frequent-itemset result at boundary g."""
    return {x: int(c[g]) for x, c in table.items() if c[g] >= threshold}


def top_k(supports: Dict[Itemset, int], prefix: Sequence[int], k: int
          ) -> List[Tuple[Itemset, int]]:
    """The k highest-support itemsets strictly extending ``prefix``
    (their leading items equal it), ties in lexicographic order."""
    p = tuple(sorted(int(i) for i in prefix))
    rows = [(x, s) for x, s in supports.items()
            if len(x) > len(p) and x[:len(p)] == p]
    rows.sort(key=lambda r: (-r[1], r[0]))
    return rows[:k]


def compare(got: Dict[Itemset, int], want: Dict[Itemset, int]
            ) -> Tuple[int, int, int]:
    """(missing, extra, wrong support) between two complete results."""
    missing = sum(1 for x in want if x not in got)
    extra = sum(1 for x in got if x not in want)
    wrong = sum(1 for x, s in got.items()
                if x in want and int(s) != want[x])
    return missing, extra, wrong


def brute_force(transactions: Sequence[Sequence[int]], threshold: int,
                max_len: Optional[int] = None) -> Dict[Itemset, int]:
    """Every itemset counted by enumerating each transaction's subsets:
    for toy sizes only, to check :func:`mine` itself."""
    from itertools import combinations
    counts: Dict[Itemset, int] = {}
    for t in transactions:
        t = sorted(set(int(i) for i in t))
        top = len(t) if max_len is None else min(len(t), max_len)
        for k in range(1, top + 1):
            for x in combinations(t, k):
                counts[x] = counts.get(x, 0) + 1
    return {x: c for x, c in counts.items() if c >= threshold}
