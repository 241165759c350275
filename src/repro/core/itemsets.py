"""Apriori itemset machinery: candidate generation, prefix clustering.

Itemsets are sorted tuples of item ids. The paper clusters k-itemset tasks
by their (k-1)-prefix via XOR of per-item hashes (Section 4); we reproduce
that hash exactly (std::hash of an integer is the identity in libstdc++ —
we use a mixing hash to avoid degenerate buckets, but keep the XOR
combiner).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Sequence, Tuple

Itemset = Tuple[int, ...]


def _mix(x: int) -> int:
    """64-bit integer mixing hash (splitmix64 finalizer)."""
    x = (x + 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
    return x ^ (x >> 31)


def itemset_hash(items: Iterable[int]) -> int:
    """XOR of per-item mixing hashes — the paper's §4 combiner. Used
    directly by the depth-first engine to key an equivalence class by
    its full prefix."""
    h = 0
    for item in items:
        h ^= _mix(item)
    return h


def prefix_hash(itemset: Itemset) -> int:
    """Paper §4: XOR of per-item hashes over the first (k-1) items —
    itemsets sharing a (k-1)-prefix land in the same bucket."""
    return itemset_hash(itemset[:-1])


@dataclasses.dataclass(frozen=True)
class Bucket:
    """All level-k candidates sharing one (k-1)-prefix.

    ``key`` is the paper's XOR'd prefix hash (the clustered policy's
    bucket key); ``exts`` are the candidates' last items, sorted, so the
    bucket's candidate set is ``{prefix + (e,) for e in exts}``.
    """
    key: int
    prefix: Itemset
    exts: Tuple[int, ...]

    def __len__(self) -> int:
        return len(self.exts)

    def candidates(self) -> List[Itemset]:
        return [self.prefix + (e,) for e in self.exts]


def gen_buckets(frequent: Sequence[Itemset],
                known_frequent: Iterable[Itemset] = ()) -> List[Bucket]:
    """F_{k-1} -> C_k by prefix join + anti-monotone prune (Apriori),
    emitted already grouped by (k-1)-prefix: one bucket per joined
    ``pref + (a,)`` whose extensions survive the prune, in the frontier's
    first-seen (k-2)-prefix order, then ``a`` ascending.

    ``known_frequent`` widens the prune set beyond the join frontier:
    granularity="auto" detaches whole subtrees to depth-first class
    tasks, so their itemsets never re-enter ``frequent`` — without the
    full known-frequent membership, a candidate whose (k-1)-subset was
    mined inside a detached subtree would be falsely pruned."""
    if not frequent:
        return []
    k = len(frequent[0]) + 1
    # group by (k-2)-prefix; join pairs within a group
    by_prefix: Dict[Itemset, List[int]] = {}
    for it in frequent:
        by_prefix.setdefault(it[:-1], []).append(it[-1])
    if k > 2:
        # the prune's lookup: (k-2)-prefix -> last items of every known
        # (k-1)-itemset under it
        lasts_of: Dict[Itemset, set] = {p: set(ls)
                                        for p, ls in by_prefix.items()}
        for it in known_frequent:
            if len(it) == k - 1:
                lasts_of.setdefault(it[:-1], set()).add(it[-1])
    out: List[Bucket] = []
    for pref, lasts in by_prefix.items():
        lasts.sort()
        h = itemset_hash(pref)
        for i, a in enumerate(lasts):
            exts = lasts[i + 1:]
            # pref + (a,) and pref + (b,) are in the frontier; each other
            # (k-1)-subset of pref + (a, b) drops one item of pref
            for j in range(k - 2):
                if not exts:
                    break
                known = lasts_of.get(pref[:j] + pref[j + 1:] + (a,), ())
                exts = [b for b in exts if b in known]
            if exts:
                # the key is itemset_hash(pref + (a,)): XOR combines
                out.append(Bucket(h ^ _mix(a), pref + (a,), tuple(exts)))
    return out


def gen_candidates(frequent: Sequence[Itemset],
                   known_frequent: Iterable[Itemset] = ()) -> List[Itemset]:
    """:func:`gen_buckets`, flattened to one itemset per candidate."""
    return [c for b in gen_buckets(frequent, known_frequent)
            for c in b.candidates()]


def brute_force_frequent(db: Sequence[Sequence[int]], min_support: int,
                         max_k: int = 6) -> Dict[Itemset, int]:
    """Oracle for tests: enumerate all itemsets by breadth-first Apriori
    over explicit set intersections (no bitmaps, no scheduler)."""
    from itertools import combinations
    tidsets: Dict[int, set] = {}
    for t, txn in enumerate(db):
        for i in set(txn):
            tidsets.setdefault(i, set()).add(t)
    result: Dict[Itemset, int] = {}
    frequent = []
    for i, tids in sorted(tidsets.items()):
        if len(tids) >= min_support:
            result[(i,)] = len(tids)
            frequent.append((i,))
    k = 2
    while frequent and k <= max_k:
        cands = gen_candidates(frequent)
        frequent = []
        for c in cands:
            tids = tidsets[c[0]]
            for i in c[1:]:
                tids = tids & tidsets[i]
            if len(tids) >= min_support:
                result[c] = len(tids)
                frequent.append(c)
        k += 1
    return result
