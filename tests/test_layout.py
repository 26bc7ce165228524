"""Locality-packed graph layout + in-kernel visited filter (DESIGN.md §10).

Three layers of contract:

* host layout algebra — ``locality_order`` is a permutation,
  ``apply_layout`` is a bitwise row gather with exactly relabeled
  adjacency, ``unpack_rows`` inverts it (per shard slice on the mesh),
  and the layout module's ``span_group`` agrees with the kernel's;
* kernel spans — a contiguous-span idx block through the grouped-DMA
  gather path is bitwise the XLA oracle (the coalesced copies move the
  same bytes), and the visited-filter Pallas kernel is bitwise its XLA
  scan reference;
* end-to-end equivariance — a packed index answers bitwise-identically
  to an unpacked one through the facade, in both regimes, on both
  planes, with and without the hash visited filter, across streaming
  mutations, compaction, and a v5 artifact round-trip (zero compiles).
"""
import dataclasses
import functools
import tempfile
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ann import Index
from repro.ann import layout as LY
from repro.configs.base import ANNConfig
from repro.core import hotpath as HP
from repro.kernels import l2dist as L2
from repro.kernels import visited as VF

PACKED_PIPE = ("knn", "diversify", "bridges", "layout")


@pytest.fixture(scope="module")
def base_kwargs():
    return dict(max_degree=8, hop_width=8, k_graph=12, n_seeds=4,
                small_t0=4, small_hops=3, large_ef=24, large_hops=10,
                serve_buckets=(8, 64), kernel_backend="xla")


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(7)
    X = rng.standard_normal((384, 24)).astype(np.float32)
    Qs = rng.standard_normal((4, 24)).astype(np.float32)
    Ql = rng.standard_normal((64, 24)).astype(np.float32)
    return X, Qs, Ql


@pytest.fixture(scope="module")
def built(corpus, base_kwargs):
    """One build of each variant, shared by the equivalence tests."""
    X, _, _ = corpus
    out = {}
    out["plain"] = Index.build(X, ANNConfig(**base_kwargs))
    out["packed"] = Index.build(
        X, ANNConfig(**base_kwargs, build_pipeline=PACKED_PIPE))
    out["hash"] = Index.build(
        X, ANNConfig(**base_kwargs, visited_filter="hash"))
    out["packed_hash"] = Index.build(
        X, ANNConfig(**base_kwargs, build_pipeline=PACKED_PIPE,
                     visited_filter="hash"))
    return out


def _bitwise(a, b):
    return (bool(np.array_equal(a[0], b[0]))
            and bool(np.array_equal(np.asarray(a[1]).view(np.uint32),
                                    np.asarray(b[1]).view(np.uint32))))


# ----------------------------------------------------------------------
# host layout algebra
# ----------------------------------------------------------------------

def test_locality_order_is_permutation(rng):
    N, M = 97, 6
    nb = rng.integers(0, N + 1, size=(N, M)).astype(np.int32)
    perm = LY.locality_order(nb)
    assert perm.dtype == np.int32
    assert sorted(perm.tolist()) == list(range(N))
    inv = LY.inverse_permutation(perm)
    np.testing.assert_array_equal(inv[perm], np.arange(N))


def test_locality_order_starts_first(rng):
    N = 40
    nb = np.full((N, 4), N, np.int32)  # edgeless: order = starts then scan
    perm = LY.locality_order(nb, starts=[7, 3])
    assert perm[0] == 7 and perm[1] == 3 and perm[2] == 0


def test_apply_layout_bitwise_rows_and_exact_relabel(rng):
    N, M, d = 64, 5, 12
    X = rng.standard_normal((N, d)).astype(np.float32)
    nb = rng.integers(-1, N + 1, size=(N, M)).astype(np.int32)
    nb[nb < 0] = N  # sentinel for absent
    lam = rng.standard_normal((N, M)).astype(np.float32)
    deg = (nb < N).sum(1).astype(np.int32)
    hubs = np.array([3, 9, 41], np.int32)
    perm = LY.locality_order(nb, starts=hubs)
    X2, nb2, lam2, deg2, hubs2 = LY.apply_layout(perm, X, nb, lam, deg, hubs)
    inv = LY.inverse_permutation(perm)
    # rows are the SAME bits, just moved
    np.testing.assert_array_equal(X2.view(np.uint32), X[perm].view(np.uint32))
    np.testing.assert_array_equal(deg2, deg[perm])
    # hubs keep pointing at the same vectors
    np.testing.assert_array_equal(X2[hubs2].view(np.uint32),
                                  X[hubs].view(np.uint32))
    # each packed row holds the same neighbor SET, relabeled, sentinel kept
    for i in range(N):
        old = nb[perm[i]]
        want = sorted(int(inv[v]) if v < N else N for v in old)
        assert nb2[i].tolist() == want
        # λ follows its lane through the re-sort
        lam_of = {int(inv[v]) if v < N else N: set() for v in old}
        for v, l in zip(old, lam[perm[i]]):
            lam_of[int(inv[v]) if v < N else N].add(np.float32(l))
        for v, l in zip(nb2[i], lam2[i]):
            assert np.float32(l) in lam_of[int(v)]


@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_unpack_rows_roundtrip(rng, n_shards):
    N, d = 48, 7
    X = rng.standard_normal((N, d)).astype(np.float32)
    n_local = N // n_shards
    perms = [np.random.default_rng(s).permutation(n_local).astype(np.int32)
             for s in range(n_shards)]
    packed = np.concatenate(
        [X[s * n_local:(s + 1) * n_local][p] for s, p in enumerate(perms)])
    out = LY.unpack_rows(packed, np.concatenate(perms), n_shards=n_shards)
    np.testing.assert_array_equal(out.view(np.uint32), X.view(np.uint32))


def test_unpack_rows_rejects_ragged_shards(rng):
    with pytest.raises(ValueError, match="not divisible"):
        LY.unpack_rows(np.zeros((10, 2), np.float32),
                       np.arange(10), n_shards=3)


def test_span_group_matches_kernel():
    for C in range(1, 65):
        assert LY.span_group(C) == L2.span_group(C), C
    assert LY.span_group(32) == 8
    assert LY.span_group(24) == 8
    assert LY.span_group(12) == 4
    assert LY.span_group(7) == 1


def test_span_stats_contiguous_vs_shuffled(rng):
    N, C = 32, 16  # G = 8
    contig = (np.arange(N)[:, None] % (N - C) + np.arange(C)).astype(np.int32)
    st = LY.span_stats(contig)
    assert st["group"] == 8
    assert st["frac_coalesced"] == 1.0
    assert st["rows_per_copy"] == 8.0
    shuf = rng.permuted(contig, axis=1).astype(np.int32)
    st2 = LY.span_stats(shuf)
    assert st2["rows_per_copy"] < st["rows_per_copy"]
    # layout actually raises the metric on a real graph (degree >= 2*G so
    # a row's fresh run can cover whole aligned groups)
    from repro.data.synthetic import make_clustered
    ds = make_clustered(n=1024, d=16, n_queries=4, n_clusters=24,
                        noise=0.6, seed=0)
    cfg = ANNConfig(max_degree=16, k_graph=24, kernel_backend="xla")
    g_plain = Index(ds.X, cfg).graph
    before = LY.span_stats(np.asarray(g_plain.neighbors))
    g_packed = Index(ds.X, dataclasses.replace(
        cfg, build_pipeline=PACKED_PIPE)).graph
    after = LY.span_stats(np.asarray(g_packed.neighbors))
    assert after["rows_per_copy"] > before["rows_per_copy"]
    assert after["rows_per_copy"] > 1.0


# ----------------------------------------------------------------------
# kernel spans: coalesced-DMA gather parity
# ----------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("backend", "gf"))
def _ndg(Q, X, idx, mask, backend, gf):
    return HP.neighbor_distances(Q, X, idx, metric="l2", mask=mask,
                                 backend=backend, gather_fused=gf)


@pytest.mark.parametrize("C", [8, 16, 24, 32])
def test_gather_fused_span_parity(rng, C):
    """Fully-contiguous, partially-contiguous, and shuffled idx blocks all
    agree bitwise with the XLA oracle — the span fast path and the per-row
    fallback move the same bytes."""
    S, d, N = 12, 32, 200
    X = jnp.asarray(rng.normal(size=(N, d)).astype(np.float32))
    Q = jnp.asarray(rng.normal(size=(S, d)).astype(np.float32))
    base = rng.integers(0, N - C, size=(S, 1))
    cases = {
        "contig": base + np.arange(C),
        "shuffled": rng.permuted(base + np.arange(C), axis=1),
        "mixed": np.where(np.arange(C) < C // 2,
                          base + np.arange(C),
                          rng.integers(-2, N + 9, size=(S, C))),
        "boundary": np.clip(base + np.arange(C), 0, N - 1) * 0 + (N - C)
        + np.arange(C),  # span ending exactly at N
    }
    for name, idx_np in cases.items():
        idx = jnp.asarray(idx_np.astype(np.int32))
        mask = jnp.asarray(rng.random((S, C)) > 0.2)
        a = _ndg(Q, X, idx, mask, "xla", None)
        b = _ndg(Q, X, idx, mask, "pallas", "on")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"C={C} {name}")


def test_gather_fused_span_parity_int8(rng):
    """Quantized rows ride the same span detector (1 byte/row element)."""
    S, C, d, N = 9, 16, 24, 150
    X = rng.normal(size=(N, d)).astype(np.float32)
    from repro.ann.quantize import quantize_rows
    codes, scales = quantize_rows(jnp.asarray(X))
    Q = jnp.asarray(rng.normal(size=(S, d)).astype(np.float32))
    base = rng.integers(0, N - C, size=(S, 1))
    idx = jnp.asarray((base + np.arange(C)).astype(np.int32))
    mask = jnp.asarray(np.ones((S, C), bool))

    @functools.partial(jax.jit, static_argnames=("backend", "gf"))
    def nd(Q, Xc, idx, mask, sc, backend, gf):
        return HP.neighbor_distances(Q, Xc, idx, metric="l2", mask=mask,
                                     backend=backend, gather_fused=gf,
                                     scales=sc)

    a = nd(Q, codes, idx, mask, scales, "xla", None)
    b = nd(Q, codes, idx, mask, scales, "pallas", "on")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------------------
# visited filter primitive
# ----------------------------------------------------------------------

def _vf_case(rng, B, M, W, S, id_bound):
    table = np.full((B, W, S), VF.VF_EMPTY, np.int32)
    ids = rng.integers(0, id_bound, size=(B, M)).astype(np.int32)
    valid = rng.random((B, M)) > 0.25
    return jnp.asarray(table), jnp.asarray(ids), jnp.asarray(valid)


@pytest.mark.parametrize("B,M,W,S", [(3, 5, 2, 8), (16, 24, 8, 64),
                                     (13, 17, 4, 32)])
def test_visited_filter_backend_parity(rng, B, M, W, S):
    table, ids, valid = _vf_case(rng, B, M, W, S, id_bound=40)
    a_t, a_f = jax.jit(VF.visited_filter_xla)(table, ids, valid)
    b_t, b_f = VF.visited_filter_pallas(table, ids, valid, interpret=True)
    np.testing.assert_array_equal(np.asarray(a_t), np.asarray(b_t))
    np.testing.assert_array_equal(np.asarray(a_f), np.asarray(b_f))


def test_visited_filter_semantics():
    # duplicates within a call: only the FIRST lane of an id is fresh
    table = jnp.full((1, 2, 8), VF.VF_EMPTY, jnp.int32)
    ids = jnp.asarray([[5, 5, 9, 5]], jnp.int32)
    valid = jnp.ones((1, 4), bool)
    t2, fresh = VF.visited_filter_xla(table, ids, valid)
    assert fresh.tolist() == [[True, False, True, False]]
    # a second call re-presenting the ids sees them all as visited
    _, fresh2 = VF.visited_filter_xla(t2, ids, valid)
    assert not bool(np.asarray(fresh2).any())
    # invalid lanes are never fresh and never inserted
    _, fresh3 = VF.visited_filter_xla(table, ids, jnp.zeros((1, 4), bool))
    assert not bool(np.asarray(fresh3).any())


def test_visited_filter_full_bucket_drops():
    """W ids in one bucket fill it; the (W+1)-th distinct id hashing there
    reports not-fresh (a safe drop, never a duplicate)."""
    W, S = 2, 8
    shift = VF.shift_for(S)
    bucket0 = [i for i in range(1000)
               if int(VF.hash_bucket(jnp.int32(i), shift)) == 0][:W + 1]
    table = jnp.full((1, W, S), VF.VF_EMPTY, jnp.int32)
    ids = jnp.asarray([bucket0], jnp.int32)
    valid = jnp.ones((1, W + 1), bool)
    _, fresh = VF.visited_filter_xla(table, ids, valid)
    assert fresh.tolist() == [[True] * W + [False]]


def test_visited_table_sizing():
    tab = HP.visited_table(4, 100)
    B, W, S = tab.shape
    assert B == 4 and S & (S - 1) == 0
    assert W * S >= 2 * 100  # load factor <= 1/2
    assert int(jnp.min(tab)) == VF.VF_EMPTY


# ----------------------------------------------------------------------
# end-to-end equivariance through the facade
# ----------------------------------------------------------------------

def test_packed_graph_carries_perm(built):
    g = built["packed"].graph
    assert g.perm is not None
    assert sorted(np.asarray(g.perm).tolist()) == list(range(g.n))
    assert built["plain"].graph.perm is None
    # the perm rides the operand list last
    assert built["packed"].plane.operands()[-1] is g.perm


@pytest.mark.parametrize("pair", [("plain", "packed"),
                                  ("hash", "packed_hash")])
def test_packed_vs_unpacked_bitwise_single_plane(built, corpus, pair):
    X, Qs, Ql = corpus
    a_i, b_i = built[pair[0]], built[pair[1]]
    for Q in (Qs, Ql):
        assert _bitwise(a_i.search(Q, k=5), b_i.search(Q, k=5))


def test_packed_vs_unpacked_bitwise_mesh_1x1(corpus, base_kwargs):
    X, Qs, Ql = corpus
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    cfg_p = ANNConfig(**base_kwargs, build_pipeline=PACKED_PIPE,
                      visited_filter="hash")
    cfg_u = ANNConfig(**base_kwargs, visited_filter="hash")
    i_p = Index.build(X, cfg_p, mesh=mesh)
    i_u = Index.build(X, cfg_u, mesh=mesh)
    assert i_p.graph.perm is not None
    for Q in (Qs, Ql):
        assert _bitwise(i_u.search(Q, k=5), i_p.search(Q, k=5))


def test_packed_streaming_tombstones_external_ids(corpus, base_kwargs):
    """delete()/add() speak EXTERNAL ids on a packed plane; compaction
    un-permutes before cutting the corpus so id_map stays external."""
    X, Qs, _ = corpus
    cfg = ANNConfig(**base_kwargs, build_pipeline=PACKED_PIPE,
                    visited_filter="hash")
    idx = Index.build(X, cfg)
    victim = int(idx.search(Qs, k=1)[0][0, 0])
    new_ids = idx.add(np.random.default_rng(3).standard_normal(
        (3, X.shape[1])).astype(np.float32))
    idx.delete([victim, int(new_ids[0])])
    ids, _ = idx.search(Qs, k=5)
    assert victim not in ids and int(new_ids[0]) not in ids
    id_map = idx.compact()
    assert id_map[victim] == -1 and id_map[int(new_ids[0])] == -1
    assert idx.generation == 1
    # post-compaction: packed again, victim still gone
    assert idx.graph.perm is not None
    ids2, _ = idx.search(Qs, k=5)
    assert victim not in np.asarray(ids2)


def test_packed_compaction_bitwise_cold_build(corpus, base_kwargs):
    X, Qs, _ = corpus
    cfg = ANNConfig(**base_kwargs, build_pipeline=PACKED_PIPE)
    idx = Index.build(X, cfg)
    idx.delete([0, 1])
    idx.compact()
    cold = Index.build(X[2:], cfg)
    a = idx.search(Qs, k=5)
    b = cold.search(Qs, k=5)
    # compaction densified: new ids == positions in the trimmed corpus
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]).view(np.uint32),
                                  np.asarray(b[1]).view(np.uint32))


def test_v5_artifact_roundtrip_zero_compiles(corpus, base_kwargs, tmp_path):
    X, Qs, _ = corpus
    cfg = ANNConfig(**base_kwargs, build_pipeline=PACKED_PIPE,
                    visited_filter="hash")
    idx = Index.build(X, cfg)
    a = idx.search(Qs, k=5)
    idx.save(tmp_path / "v5", extra_ks=(5,))
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # fingerprint mismatch would warn
        idx2 = Index.load(tmp_path / "v5")
    assert idx2.graph.perm is not None
    b = idx2.search(Qs, k=5)
    assert _bitwise(a, b)
    assert idx2.stats.compiles == 0
    assert idx2.stats.aot_primed > 0


def test_v5_mesh_artifact_roundtrip(corpus, base_kwargs, tmp_path):
    X, Qs, _ = corpus
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    cfg = ANNConfig(**base_kwargs, build_pipeline=PACKED_PIPE)
    idx = Index.build(X, cfg, mesh=mesh)
    a = idx.search(Qs, k=5)
    idx.save(tmp_path / "m5", extra_ks=(5,))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        idx2 = Index.load(tmp_path / "m5",
                          mesh=jax.make_mesh((1, 1), ("data", "model")))
    b = idx2.search(Qs, k=5)
    assert _bitwise(a, b)
    assert idx2.stats.compiles == 0


def test_h2d_staging_counter(corpus, base_kwargs):
    X, Qs, Ql = corpus
    idx = Index.build(X, ANNConfig(**base_kwargs))
    for _ in range(2):
        idx.search(Qs, k=5)
        idx.search(Ql, k=5)
    st = idx.stats
    assert st.h2d_staged == 4
    # both bucket shapes were re-hit on round 2: the staging route is
    # per-(shape, dtype) cached, not rebuilt per call
    assert st.h2d_stage_reuses >= 2
    assert st.snapshot()["h2d_stage_reuses"] == st.h2d_stage_reuses
    # the plane names the route the staging took
    assert idx.plane.stage_route in ("pinned_host", "device_put")


def test_gather_limit_rejected_on_packed_graph(base_kwargs):
    with pytest.raises(ValueError, match="gather_limit"):
        ANNConfig(**base_kwargs, build_pipeline=PACKED_PIPE,
                  gather_limit=4)
