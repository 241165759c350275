"""The benchmark's Quest generator against the parameters it is given."""
import numpy as np
import pytest

from datagen.quest import QuestParams, generate, relabel


@pytest.mark.parametrize("t_len, i_len", [(10, 4), (40, 10)])
def test_mean_length_and_item_range(t_len, i_len):
    p = QuestParams(n_transactions=4000, avg_transaction=t_len,
                    avg_pattern=i_len, n_items=1000, n_patterns=2000)
    db = generate(p, seed=3)
    lengths = db.lengths()
    assert len(db) == 4000 and lengths.min() >= 1
    # duplicates across overlapping patterns are removed, so the mean
    # sits a little under |T|
    assert 0.9 * t_len <= lengths.mean() <= 1.1 * t_len
    assert db.items.min() >= 0 and db.items.max() < 1000
    assert len(np.unique(db.items)) > 900
    # sorted and distinct within each transaction
    for t in range(0, 4000, 97):
        row = db.items[db.offsets[t]:db.offsets[t + 1]]
        assert np.all(np.diff(row) > 0)


def test_same_seed_same_data_and_relabel_keeps_the_lattice():
    p = QuestParams(2000, 10, 4, 300, 200)
    a, b = generate(p, 7), generate(p, 7)
    assert np.array_equal(a.items, b.items)
    assert np.array_equal(a.offsets, b.offsets)
    r = relabel(a, seed=2 ** 40 + 11, blocks=[1500])
    assert sorted(r.lengths()) == sorted(a.lengths())
    assert np.array_equal(np.sort(np.bincount(r.items, minlength=300)),
                          np.sort(np.bincount(a.items, minlength=300)))
    # the blocks are shuffled apart: the tail holds the same
    # transactions as before, in another order, under new labels
    assert sorted(r.slice(1500, 2000).lengths()) == \
        sorted(a.slice(1500, 2000).lengths())
