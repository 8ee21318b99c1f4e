"""Clustering + nearest-neighbour + t-SNE tests.

Reference test parity: deeplearning4j-nearestneighbors-parent tests
(KMeansTest, VPTreeTest, KDTreeTest) and BarnesHutTsne's convergence tests —
each structure is validated against brute force / known geometry.
"""

import numpy as np
import pytest

from deeplearning4j_tpu.clustering import (KDTree, KMeans,
                                           RandomProjectionLSH, VPTree)
from deeplearning4j_tpu.manifold import Tsne

R = np.random.default_rng(3)


def _blobs(n_per=20, d=5, centers=((0,) * 5, (8,) * 5, (-8, 8, -8, 8, -8))):
    xs, labels = [], []
    for li, c in enumerate(centers):
        xs.append(R.normal(size=(n_per, d)).astype(np.float32)
                  + np.asarray(c, np.float32))
        labels += [li] * n_per
    return np.concatenate(xs), np.asarray(labels)


class TestKMeans:
    def test_recovers_blobs(self):
        x, labels = _blobs()
        km = KMeans(k=3, seed=1).fit(x)
        # each true cluster must map to exactly one predicted cluster
        mapping = {}
        for li in range(3):
            pred = km.labels[labels == li]
            assert len(set(pred.tolist())) == 1, "cluster split"
            mapping[li] = pred[0]
        assert len(set(mapping.values())) == 3, "clusters merged"
        # centers near the true means
        for li, c in enumerate(km.centers[list(mapping.values())]):
            true_mean = x[labels == li].mean(axis=0)
            assert np.linalg.norm(c - true_mean) < 1.0

    def test_predict_matches_fit_labels(self):
        x, _ = _blobs()
        km = KMeans(k=3, seed=1).fit(x)
        np.testing.assert_array_equal(km.predict(x), km.labels)

    def test_inertia_decreases_with_k(self):
        x, _ = _blobs()
        i2 = KMeans(k=2, seed=1).fit(x).inertia
        i6 = KMeans(k=6, seed=1).fit(x).inertia
        assert i6 < i2

    def test_random_init_and_convergence_iterations(self):
        x, _ = _blobs()
        km = KMeans(k=3, init="random", seed=4).fit(x)
        assert km.n_iterations <= km.max_iterations
        assert km.inertia is not None and np.isfinite(km.inertia)


def _brute_knn(items, x, k, metric="euclidean"):
    if metric == "euclidean":
        d = np.linalg.norm(items - x, axis=1)
    else:
        na = np.linalg.norm(items, axis=1) * np.linalg.norm(x)
        d = 1 - (items @ x) / np.maximum(na, 1e-12)
    order = np.argsort(d, kind="stable")[:k]
    return order.tolist(), d[order].tolist()


class TestTrees:
    def test_vptree_exact_vs_bruteforce(self):
        items = R.normal(size=(200, 8))
        tree = VPTree(items)
        for _ in range(10):
            q = R.normal(size=8)
            idx, dist = tree.query(q, k=5)
            bidx, bdist = _brute_knn(items, q, 5)
            np.testing.assert_allclose(sorted(dist), sorted(bdist),
                                       rtol=1e-10)
            assert set(idx) == set(bidx)

    def test_vptree_cosine(self):
        items = R.normal(size=(100, 6))
        tree = VPTree(items, distance="cosine")
        q = R.normal(size=6)
        idx, dist = tree.query(q, k=3)
        bidx, bdist = _brute_knn(items, q, 3, metric="cosine")
        np.testing.assert_allclose(sorted(dist), sorted(bdist), rtol=1e-10)
        assert set(idx) == set(bidx)

    def test_kdtree_exact_vs_bruteforce(self):
        items = R.normal(size=(300, 3))
        tree = KDTree(items)
        for _ in range(10):
            q = R.normal(size=3)
            idx, dist = tree.query(q, k=4)
            bidx, bdist = _brute_knn(items, q, 4)
            np.testing.assert_allclose(sorted(dist), sorted(bdist),
                                       rtol=1e-10)
            assert set(idx) == set(bidx)

    def test_vptree_duplicate_heavy_data(self):
        """Review-finding regression: all-tied distances must not recurse
        once per point (RecursionError at N=2000 before the positional
        split fallback)."""
        items = np.zeros((2000, 3))
        items[:5] += np.arange(5)[:, None]  # a few distinct rows
        tree = VPTree(items)
        idx, dist = tree.query(np.asarray([4.0, 4.0, 4.0]), k=1)
        assert dist[0] == 0.0 and np.allclose(items[idx[0]], 4.0)

    def test_k1_is_nearest(self):
        items = np.asarray([[0.0, 0.0], [5.0, 5.0], [1.0, 1.0]])
        for tree in (VPTree(items), KDTree(items)):
            idx, dist = tree.query(np.asarray([0.9, 0.9]), k=1)
            assert idx == [2]


class TestLSH:
    def test_exact_bucket_hit(self):
        items = R.normal(size=(150, 16)).astype(np.float32)
        lsh = RandomProjectionLSH(hash_bits=12, seed=2).fit(items)
        # querying a stored item must return it first (distance 0)
        idx, dist = lsh.query(items[17], k=1)
        assert idx[0] == 17
        assert dist[0] < 1e-6

    def test_approximate_recall(self):
        items = R.normal(size=(300, 10)).astype(np.float32)
        lsh = RandomProjectionLSH(hash_bits=10, seed=2).fit(items)
        hits = 0
        for _ in range(20):
            q = R.normal(size=10).astype(np.float32)
            idx, _ = lsh.query(q, k=5, max_probes=64, oversample=8)
            bidx, _ = _brute_knn(items, q, 5, metric="cosine")
            hits += len(set(idx) & set(bidx))
        assert hits / (20 * 5) > 0.5, "LSH recall collapsed"

    def test_max_probes_is_a_cap(self):
        """Review-finding regression: a query whose first bucket already
        holds oversample*k candidates must stop after ONE probe."""
        items = np.ones((50, 8), np.float32) + R.normal(
            size=(50, 8)).astype(np.float32) * 1e-3  # one dense bucket
        lsh = RandomProjectionLSH(hash_bits=8, seed=0).fit(items)
        probed = {"n": 0}
        orig = dict(lsh._buckets)

        class Counting(dict):
            def __getitem__(self, key):
                probed["n"] += 1
                return orig[key]

        lsh._buckets = Counting(orig)
        lsh.query(items[0], k=2, max_probes=64)
        assert probed["n"] == 1


class TestTsne:
    def test_blobs_separate(self):
        x, labels = _blobs(n_per=15, d=8,
                           centers=((0,) * 8, (10,) * 8,
                                    (-10, 10) * 4))
        emb = Tsne(perplexity=10, n_iter=300, seed=0).fit_transform(x)
        assert emb.shape == (45, 2)
        intra, inter = [], []
        for i in range(3):
            pts = emb[labels == i]
            intra.append(np.mean(np.linalg.norm(
                pts - pts.mean(axis=0), axis=1)))
            for j in range(i + 1, 3):
                inter.append(np.linalg.norm(
                    pts.mean(axis=0) - emb[labels == j].mean(axis=0)))
        assert min(inter) > 2.0 * max(intra), (intra, inter)

    def test_affinity_perplexity_calibration(self):
        import jax.numpy as jnp

        from deeplearning4j_tpu.manifold.tsne import (
            _calibrate_affinities, _pairwise_sq_dists)

        x = jnp.asarray(R.normal(size=(60, 4)).astype(np.float32))
        target = 12.0
        p = np.asarray(_calibrate_affinities(_pairwise_sq_dists(x), target))
        # effective perplexity = 2^H(row) must hit the target
        h = -np.sum(np.where(p > 0, p * np.log2(np.maximum(p, 1e-20)), 0),
                    axis=1)
        np.testing.assert_allclose(2.0 ** h, target, rtol=0.05)

    def test_kl_is_finite_and_small_vs_random(self):
        rng = np.random.default_rng(11)
        x = np.concatenate([
            rng.normal(size=(12, 6)).astype(np.float32) + np.asarray(c,
                                                                     np.float32)
            for c in ((0,) * 6, (9,) * 6, (-9, 9) * 3)])
        # past stop_lying_iteration (250): until there the steps descend the
        # KL against 12 x P, not the one read here, and where iteration 250
        # lands (0.64, 1.01 or 1.13) follows how the host's compiler rounds;
        # by 500 every optimisation level reads 0.065-0.069
        t = Tsne(perplexity=8, n_iter=500, seed=0).fit(x)
        assert np.isfinite(t.kl_divergence)
        # optimized KL must beat the KL of the random init by a wide margin
        t0 = Tsne(perplexity=8, n_iter=1, seed=0).fit(x)
        assert t.kl_divergence < t0.kl_divergence * 0.5

    def test_perplexity_guard(self):
        with pytest.raises(ValueError):
            Tsne(perplexity=30).fit(np.zeros((10, 3), np.float32))
