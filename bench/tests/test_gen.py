"""The copied generator is a function of the seed, and draws what it says."""
import numpy as np

from bench import gen


def _draw(seed):
    laws = gen.key_laws({"law": "zipf", "alpha": 1.2, "top_k": 64}, [5000, 40, 3], seed)
    return gen.make_batches(laws, [1, 2, 1], 13, 256, 2, gen.rng(seed, gen.STREAM_POOL))


def test_zipf_is_deterministic_per_seed():
    big = 2**31 + 99
    a, b, c = _draw(big), _draw(big), _draw(big + 1)
    for (ia, da), (ib, db) in zip(a, b):
        np.testing.assert_array_equal(ia, ib)
        np.testing.assert_array_equal(da, db)
    assert not np.array_equal(a[0][0], c[0][0])


def test_zipf_skew_and_scattered_hot_ids():
    g = gen.rng(3, 0)
    law = gen.ZipfTable(100_000, 1.2, 1024, g)
    ids = law.sample(gen.rng(3, 1), (200_000,))
    assert ids.min() >= 0 and ids.max() < 100_000
    top = np.bincount(ids).argmax()
    assert top == law.hot_ids[0]  # rank 1 is the most drawn id
    share = np.mean(ids == top)
    assert abs(share - law.hot_p[0]) < 0.01
    assert law.hot_ids[:16].max() > 1024  # not a contiguous prefix
    # the tail never lands on a hot id
    tail = ids[~np.isin(ids, law.hot_ids)]
    assert tail.size > 0


def test_arrivals_keep_their_gaps_across_seeds():
    a = gen.arrivals(1000.0, 5000, 1)
    b = gen.arrivals(1000.0, 5000, 2)
    assert np.all(np.diff(a) > 0)
    np.testing.assert_allclose(np.sort(np.diff(a, prepend=0.0)), np.sort(np.diff(b, prepend=0.0)),
                               rtol=1e-9, atol=1e-12)
    assert abs(a[-1] - b[-1]) < 1e-6 and abs(a[-1] - 5.0) < 0.01
    ph = gen.arrivals([[1.0, 2000.0], [1.0, 0.0001]], 4000, 1)
    assert np.mean(ph < 1.0) > 0.49 and ph.max() < 1.0 + 1.0 + 2.0
