"""The plain reference against brute force at toy size."""
import numpy as np

import reference as ref
from datagen.quest import QuestParams, generate


def _toy(n=300, seed=5):
    db = generate(QuestParams(n, 6, 3, 40, 30), seed)
    return db, ref.item_rows(db.tx_ids(), db.items, db.n_items, n)


def test_mine_equals_brute_force():
    db, rows = _toy()
    lists = db.to_lists()
    for threshold in (6, 12, 30):
        got = ref.frequent_at(ref.mine(rows, [threshold], [len(db)]), 0,
                              threshold)
        assert got == ref.brute_force(lists, threshold)


def test_boundaries_give_each_prefix_its_result():
    db, rows = _toy()
    lists = db.to_lists()
    bounds = [100, 163, 250, 300]
    thresholds = [4, 6, 9, 11]
    table = ref.mine(rows, thresholds, bounds)
    for g, (b, th) in enumerate(zip(bounds, thresholds)):
        assert ref.frequent_at(table, g, th) == \
            ref.brute_force(lists[:b], th)
    some = [(1, 2, 3), (0, 5), tuple(lists[7][:3])]
    for x, counts in zip(some, ref.supports_of(rows, some, bounds)):
        for g, b in enumerate(bounds):
            want = sum(1 for t in lists[:b] if set(x) <= set(t))
            assert counts[g] == want


def test_top_k_order_and_prefix():
    sup = {(1,): 9, (2,): 9, (1, 2): 5, (1, 3): 7, (2, 3): 7, (3,): 1}
    assert ref.top_k(sup, (), 3) == [((1,), 9), ((2,), 9), ((1, 3), 7)]
    assert ref.top_k(sup, (1,), 5) == [((1, 3), 7), ((1, 2), 5)]


def test_control_counts_lose_exactness():
    import ml_dtypes
    db, rows = _toy(n=3000)
    exact = ref.mine(rows, [60], [len(db)])
    lossy = ref.mine(rows, [60], [len(db)], count_dtype=ml_dtypes.bfloat16)
    a, b = ref.frequent_at(exact, 0, 60), ref.frequent_at(lossy, 0, 60)
    assert sum(ref.compare(b, a)) > 0


def test_compare_counts_each_kind_of_difference():
    want = {(1,): 5, (2,): 4, (1, 2): 3}
    got = {(1,): 5, (2,): 3, (3,): 9}
    assert ref.compare(got, want) == (1, 1, 1)
    assert np.all(ref.Counter(2, [64, 100]).counts(
        np.array([[2 ** 64 - 1, 1]], np.uint64)) == [[64, 65]])
