"""TSDG core behaviour: diversification invariants + end-to-end recall."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import metrics as M
from repro.core import search_ref
from repro.core.diversify import (PackedGraph, append_reverse, build_gd_baseline,
                                  build_tsdg, relaxed_gd, soft_gd)
from repro.core.knn_build import exact_knn, nn_descent, reverse_neighbors
from repro.core.search_large import large_batch_search
from repro.core.search_small import small_batch_search
from repro.data.synthetic import make_clustered, recall_at_k


@pytest.fixture(scope="module")
def ds():
    return make_clustered(n=4000, d=16, n_queries=48, n_clusters=24,
                          noise=0.6, seed=0)


@pytest.fixture(scope="module")
def knn(ds):
    return exact_knn(jnp.asarray(ds.X), 16)


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(get_arch("tsdg-paper"), k_graph=16,
                               max_degree=24, lambda0=8, bridge_hubs=64,
                               bridge_k=8)


@pytest.fixture(scope="module")
def graph(ds, knn, cfg):
    return build_tsdg(jnp.asarray(ds.X), cfg, knn_ids=knn[0],
                      knn_dists=knn[1])


# ----------------------------------------------------------------------
# graph construction
# ----------------------------------------------------------------------

def test_exact_knn_matches_ground_truth(ds, knn):
    ids, dists = knn
    # ground truth was computed in float64 numpy; spot check rows
    X64 = ds.X.astype(np.float64)
    for r in range(0, 4000, 511):
        d = ((X64 - X64[r]) ** 2).sum(1)
        d[r] = np.inf
        true = set(np.argsort(d)[:16].tolist())
        got = set(np.asarray(ids[r]).tolist())
        assert len(true & got) >= 15  # fp32 tie tolerance


def test_nn_descent_converges(ds, knn):
    ids_a, _ = nn_descent(jnp.asarray(ds.X), 16, iters=6)
    hits = 0
    for r in range(0, 4000, 97):
        hits += len(set(np.asarray(ids_a[r]).tolist())
                    & set(np.asarray(knn[0][r]).tolist())) / 16
    assert hits / len(range(0, 4000, 97)) > 0.85


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_nn_descent_row_tiles_match_one_tile(backend):
    """Rows are independent: evaluating candidates in row tiles (with a
    padded last tile) gives the graph one tile over all rows gives."""
    X = jnp.asarray(np.random.default_rng(3).normal(size=(1000, 16))
                    .astype(np.float32))
    one = nn_descent(X, 8, iters=3, backend=backend)
    tiled = nn_descent(X, 8, iters=3, tile=300, backend=backend)
    np.testing.assert_array_equal(np.asarray(one[0]), np.asarray(tiled[0]))
    np.testing.assert_array_equal(np.asarray(one[1]), np.asarray(tiled[1]))


def test_reverse_neighbors_correct():
    ids = jnp.asarray([[1, 2], [2, 3], [0, 3], [0, 1]], jnp.int32)
    rev = reverse_neighbors(ids, ids < 4, cap=4)
    rev = np.asarray(rev)
    # node 0 is pointed to by 2 and 3
    assert set(rev[0][rev[0] < 4].tolist()) == {2, 3}
    assert set(rev[3][rev[3] < 4].tolist()) == {1, 2}


def test_relaxed_gd_keeps_closest_and_prunes(ds, knn):
    X = jnp.asarray(ds.X)
    keep = relaxed_gd(X, knn[0], knn[1], alpha=1.2, metric="l2")
    keep = np.asarray(keep)
    assert keep[:, 0].all()          # closest neighbor always kept
    frac = keep.mean()
    assert 0.05 < frac < 0.9         # meaningful pruning (paper: 6-26%)


def test_alpha_one_prunes_more_than_relaxed(ds, knn):
    X = jnp.asarray(ds.X)
    k_relaxed = np.asarray(relaxed_gd(X, knn[0], knn[1], alpha=1.2,
                                      metric="l2")).mean()
    k_plain = np.asarray(relaxed_gd(X, knn[0], knn[1], alpha=1.0,
                                    metric="l2")).mean()
    assert k_relaxed >= k_plain      # relaxation keeps more edges (paper §3.2)


def test_lambda_sorted_rows(graph, ds):
    lam = np.asarray(graph.lambdas)
    nbrs = np.asarray(graph.neighbors)
    N = ds.X.shape[0]
    for r in range(0, N, 211):
        row = lam[r][nbrs[r] < N]
        assert (np.diff(row) >= 0).all()


def test_degrees_match_valid_entries(graph, ds):
    N = ds.X.shape[0]
    deg = np.asarray(graph.degrees)
    valid = (np.asarray(graph.neighbors) < N).sum(1)
    np.testing.assert_array_equal(deg, valid)


def test_tsdg_denser_than_gd_baseline(ds, knn, cfg):
    X = jnp.asarray(ds.X)
    g_tsdg = build_tsdg(X, cfg, knn_ids=knn[0], knn_dists=knn[1])
    g_gd = build_gd_baseline(X, cfg, knn_ids=knn[0], knn_dists=knn[1])
    assert g_tsdg.avg_degree() > g_gd.avg_degree()


def test_degree_at_lambda_monotone(graph):
    d1 = np.asarray(graph.degree_at(1))
    d5 = np.asarray(graph.degree_at(5))
    d10 = np.asarray(graph.degree_at(10))
    assert (d1 <= d5).all() and (d5 <= d10).all()


# ----------------------------------------------------------------------
# search procedures
# ----------------------------------------------------------------------

def test_small_batch_recall(ds, graph):
    ids, dists = small_batch_search(jnp.asarray(ds.X), graph,
                                    jnp.asarray(ds.Q), k=10, t0=16, hops=6)
    r = recall_at_k(np.asarray(ids), ds.gt, 10)
    assert r > 0.85, r


def test_large_batch_recall(ds, graph):
    ids, dists = large_batch_search(jnp.asarray(ds.X), graph,
                                    jnp.asarray(ds.Q), k=10, ef=64, hops=96)
    r = recall_at_k(np.asarray(ids), ds.gt, 10)
    assert r > 0.8, r


def test_reference_search_recall(ds, graph):
    ids, _ = search_ref.search_batch(ds.X, graph, ds.Q[:24], k=10, ef=64)
    r = recall_at_k(ids, ds.gt[:24], 10)
    assert r > 0.6, r


def test_search_results_sorted_and_unique(ds, graph):
    ids, dists = large_batch_search(jnp.asarray(ds.X), graph,
                                    jnp.asarray(ds.Q), k=10, ef=64, hops=96)
    ids, dists = np.asarray(ids), np.asarray(dists)
    for r in range(ids.shape[0]):
        valid = (ids[r] >= 0) & (ids[r] < ds.X.shape[0]) \
            & np.isfinite(dists[r]) & (dists[r] < 1e37)
        assert (np.diff(dists[r][valid]) >= -1e-5).all()
        vals = ids[r][valid]
        assert len(set(vals.tolist())) == len(vals)


def test_lambda_limit_tradeoff(ds, graph):
    """Visiting more edges (higher λ limit) must not hurt recall."""
    X, Q = jnp.asarray(ds.X), jnp.asarray(ds.Q)
    r = {}
    for lim in (2, 10):
        ids, _ = small_batch_search(X, graph, Q, k=10, t0=16, hops=6,
                                    lambda_limit=lim, seed=3)
        r[lim] = recall_at_k(np.asarray(ids), ds.gt, 10)
    assert r[10] >= r[2] - 0.02, r


def test_exact_merge_at_least_as_good(ds, graph):
    X, Q = jnp.asarray(ds.X), jnp.asarray(ds.Q)
    r = {}
    for em in (False, True):
        ids, _ = small_batch_search(X, graph, Q, k=10, t0=8, hops=6,
                                    exact_merge=em, seed=5)
        r[em] = recall_at_k(np.asarray(ids), ds.gt, 10)
    assert r[True] >= r[False] - 0.02, r


def test_exact_visited_recall_parity(ds, graph):
    """Beyond-paper bitset-V: same recall as the paper's lossy circular V."""
    X, Q = jnp.asarray(ds.X), jnp.asarray(ds.Q)
    r = {}
    for ev in (False, True):
        ids, _ = large_batch_search(X, graph, Q, k=10, ef=64, hops=96,
                                    exact_visited=ev)
        r[ev] = recall_at_k(np.asarray(ids), ds.gt, 10)
    assert r[True] >= r[False] - 0.03, r


def test_metrics_ip_cos():
    ds = make_clustered(n=2000, d=16, n_queries=24, n_clusters=16,
                        noise=0.6, metric="cos", seed=1)
    cfg = dataclasses.replace(get_arch("tsdg-paper"), k_graph=12,
                              max_degree=16, lambda0=8, metric="cos",
                              bridge_hubs=32, bridge_k=8)
    g = build_tsdg(jnp.asarray(ds.X), cfg)
    ids, _ = small_batch_search(jnp.asarray(ds.X), g, jnp.asarray(ds.Q),
                                k=10, t0=16, hops=6, metric="cos")
    assert recall_at_k(np.asarray(ids), ds.gt, 10) > 0.8
