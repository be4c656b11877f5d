import numpy as np

from benchmark.lib import data

PAD = {
    "next_token_shift": 0, "pad_id": 0,
    "lengths": {"dist": "lognormal", "median": 40, "sigma": 0.6,
                "min": 8, "max": 128},
    "labels": {"kind": "classes", "num_labels": 2},
}


def _pool(spec, seed, **kw):
    args = dict(vocab_size=1000, global_batch=64, seq_len=128, n_batches=4)
    args.update(kw)
    return data.make_pool(spec, seed=seed, **args)


def test_same_seed_same_batches_other_seed_other_batches():
    a, b, c = _pool(PAD, 3), _pool(PAD, 3), _pool(PAD, 4)
    for x, y in zip(a, b):
        assert x.keys() == y.keys()
        assert all(np.array_equal(x[k], y[k]) for k in x)
    assert not np.array_equal(a[0]["tokens"], c[0]["tokens"])
    assert not np.array_equal(a[0]["tokens"], a[1]["tokens"])


def test_seed_decides_no_shape():
    for seed in (0, 1, 99):
        for b in _pool(PAD, seed):
            assert b["tokens"].shape == (64, 128)
            assert b["attention_mask"].shape == (64, 128)
            assert b["labels"].shape == (64,)
            assert {v.dtype for v in b.values()} == {np.dtype("int32")}


def test_lengths_are_clipped_and_padding_holds_the_pad_id():
    pool = _pool(PAD, 0, n_batches=16)
    lengths = np.concatenate([b["attention_mask"].sum(1) for b in pool])
    assert lengths.min() >= 8 and lengths.max() <= 128
    assert 30 <= np.median(lengths) <= 50
    for b in pool:
        mask = b["attention_mask"].astype(bool)
        assert (b["tokens"][~mask] == 0).all()
        # a mask is a prefix of ones
        assert (np.diff(b["attention_mask"], axis=1) <= 0).all()
        assert set(np.unique(b["labels"])) <= {0, 1}
    share = data.padding_share(pool)
    assert share == 1 - lengths.sum() / (len(lengths) * 128)
    assert 0.5 < share < 0.75


def test_lm_batch_carries_one_more_token_and_no_mask():
    spec = {"next_token_shift": 1, "lengths": None, "labels": None}
    b = _pool(spec, 0, seq_len=32)[0]
    assert set(b) == {"tokens"} and b["tokens"].shape == (64, 33)
    assert 0 <= b["tokens"].min() and b["tokens"].max() < 1000
    assert data.padding_share([b]) == 0.0


def test_token_labels_cover_every_position():
    spec = {"next_token_shift": 0, "lengths": None,
            "labels": {"kind": "tokens"}}
    b = _pool(spec, 0, seq_len=16)[0]
    assert b["labels"].shape == b["tokens"].shape == (64, 16)


def test_cycle_goes_round():
    it = data.cycle([1, 2, 3])
    assert [next(it) for _ in range(7)] == [1, 2, 3, 1, 2, 3, 1]
