"""MovieLens preprocessing: the reference's recommendation pipeline contract
(filter >= 20 ratings, zero-index, leave-last-out, eval negatives excluding
seen items, HR@K/NDCG@K) — offline, numpy, shard-writable."""

import numpy as np
import pytest

from autodist_tpu.data import movielens


def _write_ratings(path, rows, sep=",", header=True):
    with open(path, "w") as f:
        if header:
            f.write(sep.join(["user_id", "item_id", "rating", "timestamp"])
                    + "\n")
        for r in rows:
            f.write(sep.join(str(x) for x in r) + "\n")


def _rows(n_users=4, n_per_user=25, n_items=50, seed=0):
    """Synthetic interactions with DISTINCT items per user, increasing
    timestamps, and non-contiguous raw ids (to exercise zero-indexing)."""
    rng = np.random.RandomState(seed)
    rows = []
    for u in range(n_users):
        items = rng.choice(n_items, size=n_per_user, replace=False)
        for t, i in enumerate(items):
            rows.append((100 + 7 * u, 1000 + 3 * int(i), 5, 10_000 + t))
    return rows


def test_load_filter_zero_index_and_leave_last_out(tmp_path):
    rows = _rows(n_users=4, n_per_user=25)
    # One user below the threshold: must be dropped entirely.
    rows += [(999, 1000, 5, 1), (999, 1003, 4, 2)]
    path = str(tmp_path / "ratings.csv")
    _write_ratings(path, rows)
    data = movielens.load_ratings(path, min_ratings=20)

    assert data.num_users == 4                      # 999 filtered out
    assert data.train_users.max() == 3              # zero-indexed
    assert data.train_items.max() < data.num_items
    assert len(data.eval_users) == 4                # one eval row per user
    assert data.num_train == 4 * 24                 # last item held out
    # The eval item is each user's LAST-timestamped interaction.
    raw_by_user = {}
    for u, i, _, t in rows[:-2]:
        if u not in raw_by_user or t > raw_by_user[u][1]:
            raw_by_user[u] = (i, t)
    # Rebuild the raw->zero-index item map the loader used.
    kept_items = sorted({i for u, i, _, t in rows[:-2]})
    item_map = {raw: idx for idx, raw in enumerate(kept_items)}
    expected = {uu: item_map[i] for uu, (i, _) in raw_by_user.items()}
    for u_new, i_new in zip(data.eval_users, data.eval_items):
        u_raw = sorted(raw_by_user)[u_new]          # users zero-indexed sorted
        assert expected[u_raw] == i_new


def test_ml1m_double_colon_format(tmp_path):
    path = str(tmp_path / "ratings.dat")
    _write_ratings(path, _rows(n_users=2), sep="::", header=False)
    data = movielens.load_ratings(path, min_ratings=20)
    assert data.num_users == 2 and data.num_train == 2 * 24


def test_training_epoch_negatives_and_labels():
    rows = _rows(n_users=3)
    import tempfile, os
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "r.csv")
        _write_ratings(path, rows)
        data = movielens.load_ratings(path, min_ratings=20)
    epoch = movielens.sample_training_epoch(data, num_neg=4, seed=1)
    n = data.num_train
    assert len(epoch["users"]) == n * 5
    assert epoch["labels"].sum() == n               # 1 positive : 4 negatives
    assert epoch["items"].min() >= 0
    assert epoch["items"].max() < data.num_items
    # Per-user example count is preserved (positives + 4x negatives each).
    for u in range(data.num_users):
        want = 5 * (data.train_users == u).sum()
        assert (epoch["users"] == u).sum() == want
    # A different seed re-samples the negatives (per-epoch regeneration).
    epoch2 = movielens.sample_training_epoch(data, num_neg=4, seed=2)
    assert not np.array_equal(epoch["items"], epoch2["items"])


def test_eval_negatives_exclude_seen_items(tmp_path):
    path = str(tmp_path / "r.csv")
    _write_ratings(path, _rows(n_users=3, n_per_user=25, n_items=200))
    data = movielens.load_ratings(path, min_ratings=20)
    # num_items counts KEPT (interacted) items only — draw within that pool.
    negs = movielens.sample_eval_negatives(data, num_negatives=30, seed=0)
    assert negs.shape == (3, 30)
    for row, u in enumerate(data.eval_users):
        seen = set(data.train_items[data.train_users == u].tolist())
        seen.add(int(data.eval_items[row]))
        assert not seen & set(negs[row].tolist())   # never a seen item
        assert len(set(negs[row].tolist())) == 30   # distinct


def test_hit_rate_and_ndcg_oracle():
    """A scorer that ranks the true item first gives HR=NDCG=1; one that
    ranks it below k gives 0; a rank-2 scorer gives NDCG=1/log2(3)."""
    import tempfile, os
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "r.csv")
        _write_ratings(path, _rows(n_users=3, n_items=300))
        data = movielens.load_ratings(path, min_ratings=20)

    truth = {int(u): int(i) for u, i in zip(data.eval_users, data.eval_items)}

    def oracle(users, items):
        return np.array([1.0 if truth[int(u)] == int(i) else 0.0
                         for u, i in zip(users, items)])

    hr, ndcg = movielens.hit_rate_and_ndcg(oracle, data, k=10, seed=3,
                                           num_negatives=30)
    assert hr == 1.0 and ndcg == 1.0

    def anti_oracle(users, items):
        return -oracle(users, items)

    hr, ndcg = movielens.hit_rate_and_ndcg(anti_oracle, data, k=10, seed=3,
                                           num_negatives=30)
    assert hr == 0.0 and ndcg == 0.0

    def one_better(users, items):
        # Exactly one negative outranks the positive -> rank 1 for every user.
        base = oracle(users, items)
        out = base.copy()
        boosted = set()
        for j, (u, i) in enumerate(zip(users, items)):
            if base[j] == 0.0 and int(u) not in boosted:
                out[j] = 2.0
                boosted.add(int(u))
        return out

    hr, ndcg = movielens.hit_rate_and_ndcg(one_better, data, k=10, seed=3,
                                           num_negatives=30)
    assert hr == 1.0
    np.testing.assert_allclose(ndcg, 1.0 / np.log2(3))

    # A CONSTANT scorer (a model that learned nothing) must score at CHANCE
    # level: rank uniform over the full candidate list, so HR@10 = 10/31 and
    # NDCG@10 = mean over positions 0..30 of (p<10)/log2(p+2) — including
    # when the clamp leaves fewer than 2k negatives (the all-or-nothing
    # failure mode of point-estimate tie ranks).
    flat = lambda u, i: np.zeros(len(u))  # noqa: E731
    hr, ndcg = movielens.hit_rate_and_ndcg(flat, data, k=10, seed=3,
                                           num_negatives=30)
    np.testing.assert_allclose(hr, 10 / 31)
    np.testing.assert_allclose(
        ndcg, np.mean([1 / np.log2(p + 2) for p in range(10)] + [0] * 21))
    hr, ndcg = movielens.hit_rate_and_ndcg(flat, data, k=10, seed=3,
                                           num_negatives=18)
    np.testing.assert_allclose(hr, 10 / 19)  # NOT 1.0


def test_ncf_example_trains_on_real_ratings(tmp_path):
    """End-to-end: the NCF benchmark trains on a ratings file and reports
    HR@10/NDCG@10 on the held-out items."""
    path = str(tmp_path / "ratings.csv")
    _write_ratings(path, _rows(n_users=6, n_per_user=24, n_items=40, seed=2))
    import examples.benchmark.ncf as bench
    avg = bench.main(["--steps", "4", "--batch_size", "64", "--log_every", "2",
                      "--ratings", path])
    assert avg is None or avg >= 0


def test_shard_writer_roundtrip(tmp_path):
    path = str(tmp_path / "r.csv")
    _write_ratings(path, _rows(n_users=3))
    data = movielens.load_ratings(path, min_ratings=20)
    files = movielens.write_training_shards(data, str(tmp_path / "shards"),
                                            num_neg=2, rows_per_shard=50)
    from autodist_tpu.data import DataLoader
    dl = DataLoader(files=files, batch_size=16, shuffle=False)
    b = dl.next()
    assert set(b) == {"users", "items", "labels"}
    assert dl.n_rows == data.num_train * 3
    dl.close()


def test_low_activity_dataset_raises(tmp_path):
    path = str(tmp_path / "r.csv")
    _write_ratings(path, [(1, 1, 5, 1), (1, 2, 5, 2)])
    with pytest.raises(ValueError, match="min_ratings"):
        movielens.load_ratings(path, min_ratings=20)