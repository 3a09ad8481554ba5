"""The exact reference and the recall arithmetic against numpy brute
force at a tiny size."""
import jax
import numpy as np
import pytest

from chipbench import data, reference


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((3000, 24)).astype(np.float32)
    q = rng.standard_normal((300, 24)).astype(np.float32)
    d2 = ((q[:, None, :].astype(np.float64) - x[None]) ** 2).sum(-1)
    return x, q, d2


def test_exact_topk_matches_numpy_brute_force(corpus):
    x, q, d2 = corpus
    ids, dists = reference.exact_topk(jax.device_put(x), q, 10)
    want = np.argsort(d2, axis=1, kind="stable")[:, :10]
    assert np.array_equal(ids, want)
    assert np.allclose(dists, np.take_along_axis(d2, want, 1), rtol=1e-4,
                       atol=1e-4)


def test_host_distances_in_float64(corpus):
    x, q, d2 = corpus
    ids = np.array([[0, 5, -1], [2999, 3000, 7]])
    got = reference.host_sq_dists(x, q[:2], ids)
    assert got[0, 0] == d2[0, 0] and got[0, 1] == d2[0, 5]
    assert got[1, 0] == d2[1, 2999] and got[1, 2] == d2[1, 7]
    assert np.isnan(got[0, 2]) and np.isnan(got[1, 1])


def test_recall_counts_each_true_id_once():
    truth = np.array([[1, 2, 3, 4], [5, 6, 7, 8]])
    ids = np.array([[4, 3, 9, 9], [5, 5, 5, 5]])
    assert reference.recall_hits(ids, truth).tolist() == [2, 1]


def test_bf16_control_is_exact_search_in_lower_precision(corpus):
    x, q, d2 = corpus
    ctl = reference.Bf16Search(x, 10)
    ids, dists = ctl.search(q[:32])
    ctl.close()
    truth = np.argsort(d2[:32], axis=1)[:, :10]
    hits = reference.recall_hits(ids, truth).sum() / truth.size
    assert hits > 0.9
    exact = reference.host_sq_dists(x, q[:32], ids)
    err = np.max(np.abs(dists - exact) / exact)
    assert 1e-4 < err < 0.1      # bfloat16 rounding, not float32's


def test_data_is_made_from_the_seed_alone():
    cfg = {"n_vectors": 512, "d": 16, "n_queries": 8,
           "data": {"intrinsic": 6, "curvature": 0.8, "noise": 0.05}}
    big = 2 ** 31 + 12345
    x1, q1, ts1 = data.make(big, cfg)
    x2, q2, ts2 = data.make(big, cfg)
    assert np.array_equal(np.asarray(x1), np.asarray(x2))
    assert np.array_equal(q1, q2) and np.array_equal(ts1, ts2)
    x3, q3, ts3 = data.make(big + 1, cfg)
    # one collection for every seed; the seed draws queries and ts
    assert np.array_equal(np.asarray(x1), np.asarray(x3))
    assert not np.array_equal(q1, q3) and not np.array_equal(ts1, ts3)
    assert np.allclose(np.linalg.norm(np.asarray(x1), axis=1), 1, atol=1e-5)
    assert sorted(ts1.tolist()) == list(range(512))
