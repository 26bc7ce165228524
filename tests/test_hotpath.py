"""Kernel-backend parity: Pallas (interpret) vs XLA for the three hot-path
primitives and both search procedures end-to-end.

Both backends are required to agree *bitwise* (ids AND distances) when run
inside jit — that is the contract that makes ``kernel_backend`` a pure
deployment knob (DESIGN.md §3).  The primitive-level tests therefore wrap
the calls in ``jax.jit``: the search stack always runs them under jit, and
outside jit XLA's op-by-op evaluation may fuse multiply-adds differently
at the last ulp.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.configs.base import ANNConfig
from repro.core import hotpath as HP
from repro.core.diversify import build_tsdg
from repro.core.search_large import large_batch_search
from repro.core.search_small import small_batch_search
from repro.data.synthetic import make_clustered

METRICS = ("l2", "ip", "cos")


@functools.partial(jax.jit, static_argnames=("metric", "backend"))
def _nd(Q, X, idx, mask, metric, backend):
    return HP.neighbor_distances(Q, X, idx, metric=metric, mask=mask,
                                 backend=backend)


@functools.partial(jax.jit, static_argnames=("keep", "backend"))
def _rm(dists, ids, mask, keep, backend):
    return HP.rank_merge(dists, ids, keep=keep, mask=mask, backend=backend)


@functools.partial(jax.jit, static_argnames=("metric", "k", "backend"))
def _ss(Q, X, seeds, metric, k, backend):
    return HP.seed_select(Q, X, seeds, metric=metric, k=k, backend=backend)


# ----------------------------------------------------------------------
# primitive parity (non-multiple-of-tile shapes, all metrics)
# ----------------------------------------------------------------------

@pytest.mark.parametrize("S,C,d", [(5, 7, 9), (33, 32, 16), (64, 33, 40),
                                   (130, 24, 128)])
@pytest.mark.parametrize("metric", METRICS)
def test_neighbor_distances_parity(rng, S, C, d, metric):
    N = 200
    X = jnp.asarray(rng.normal(size=(N, d)).astype(np.float32))
    Q = jnp.asarray(rng.normal(size=(S, d)).astype(np.float32))
    # out-of-range ids (incl. the sentinel N) must come back INF
    idx = jnp.asarray(rng.integers(-2, N + 20, size=(S, C)).astype(np.int32))
    mask = jnp.asarray(rng.random((S, C)) > 0.3)
    a = _nd(Q, X, idx, mask, metric, "xla")
    b = _nd(Q, X, idx, mask, metric, "pallas")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # masked + invalid lanes are INF on both
    inv = ~(np.asarray(mask) & (np.asarray(idx) >= 0) & (np.asarray(idx) < N))
    assert (np.asarray(a)[inv] > 1e37).all()


@pytest.mark.parametrize("metric", METRICS)
def test_neighbor_distances_parity_3d(rng, metric):
    """The diversify-tile shape: [T, Kq, d] queries x [T, C] candidates."""
    T, K, d, N = 6, 5, 8, 40
    X = jnp.asarray(rng.normal(size=(N, d)).astype(np.float32))
    nbr = jnp.asarray(rng.integers(0, N + 5, size=(T, K)).astype(np.int32))
    Q3 = X[jnp.clip(nbr, 0, N - 1)]
    a = _nd(Q3, X, nbr, None, metric, "xla")
    b = _nd(Q3, X, nbr, None, metric, "pallas")
    assert a.shape == (T, K, K)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("R,W,keep", [(7, 5, 3), (33, 48, 16), (64, 96, 64),
                                      (200, 17, 1)])
def test_rank_merge_parity(rng, R, W, keep):
    # duplicate distances exercise the shared (dist, id) tie-break
    dists = jnp.asarray(rng.integers(0, 6, size=(R, W)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 50, size=(R, W)).astype(np.int32))
    mask = jnp.asarray(rng.random((R, W)) > 0.2)
    for m in (None, mask):
        ad, ai = _rm(dists, ids, m, keep, "xla")
        bd, bi = _rm(dists, ids, m, keep, "pallas")
        np.testing.assert_array_equal(np.asarray(ad), np.asarray(bd))
        np.testing.assert_array_equal(np.asarray(ai), np.asarray(bi))
        # ascending by (dist, id)
        ad = np.asarray(ad)
        assert (np.diff(ad, axis=1) >= 0).all()


def test_rank_merge_validates_keep(rng):
    d = jnp.zeros((4, 8), jnp.float32)
    i = jnp.zeros((4, 8), jnp.int32)
    for backend in ("xla", "pallas"):
        with pytest.raises(ValueError, match="keep"):
            HP.rank_merge(d, i, keep=9, backend=backend)


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("k", [1, 5])
def test_seed_select_parity(rng, metric, k):
    N, S, C, d = 100, 21, 13, 12
    X = jnp.asarray(rng.normal(size=(N, d)).astype(np.float32))
    Q = jnp.asarray(rng.normal(size=(S, d)).astype(np.float32))
    seeds = jnp.asarray(rng.integers(0, N + 10, size=(S, C)).astype(np.int32))
    ad, ai = _ss(Q, X, seeds, metric, k, "xla")
    bd, bi = _ss(Q, X, seeds, metric, k, "pallas")
    assert ad.shape == (S, k)
    np.testing.assert_array_equal(np.asarray(ad), np.asarray(bd))
    np.testing.assert_array_equal(np.asarray(ai), np.asarray(bi))
    # best seed really is the closest valid one (oracle check, row 0)
    dd = ((np.asarray(X)[np.clip(np.asarray(seeds)[0], 0, N - 1)]
           - np.asarray(Q)[0]) ** 2).sum(-1)
    if metric == "l2":
        valid = np.asarray(seeds)[0] < N
        assert abs(np.asarray(ad)[0, 0] - dd[valid].min()) < 1e-4


# ----------------------------------------------------------------------
# backend registry / resolution
# ----------------------------------------------------------------------

def test_resolve_backend():
    expect = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert HP.resolve_backend("auto") == expect
    assert HP.resolve_backend(None) == expect
    assert HP.resolve_backend("xla") == "xla"
    assert HP.resolve_backend("pallas") == "pallas"
    with pytest.raises(ValueError, match="unknown kernel backend"):
        HP.resolve_backend("cuda")


def test_register_backend_roundtrip():
    class Probe:
        name = "probe"
        calls = []

        @staticmethod
        def neighbor_distances(Q, X, idx, **kw):
            Probe.calls.append("nd")
            return HP._XlaBackend.neighbor_distances(Q, X, idx, **kw)

        @staticmethod
        def rank_merge(d, i, **kw):
            Probe.calls.append("rm")
            return HP._XlaBackend.rank_merge(d, i, **kw)

    HP.register_backend("probe", Probe)
    try:
        assert "probe" in HP.backends()
        Q = jnp.zeros((2, 4))
        X = jnp.zeros((8, 4))
        idx = jnp.zeros((2, 3), jnp.int32)
        HP.seed_select(Q, X, idx, k=1, backend="probe")
        assert Probe.calls == ["nd", "rm"]
    finally:
        del HP._REGISTRY["probe"]


def test_config_has_kernel_backend():
    assert ANNConfig().kernel_backend == "auto"


# ----------------------------------------------------------------------
# end-to-end: identical (ids, dists) across backends for both regimes
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def index():
    ds = make_clustered(n=1200, d=12, n_queries=16, n_clusters=16,
                        noise=0.6, seed=0)
    cfg = dataclasses.replace(get_arch("tsdg-paper"), k_graph=10,
                              max_degree=12, lambda0=8, bridge_hubs=24,
                              bridge_k=4)
    X = jnp.asarray(ds.X)
    return ds, X, build_tsdg(X, cfg)


def test_small_batch_backend_parity(index):
    ds, X, g = index
    Q = jnp.asarray(ds.Q)
    for em in (False, True):
        a = small_batch_search(X, g, Q, k=10, t0=4, hops=4, width=16,
                               n_seeds=8, exact_merge=em, backend="xla")
        b = small_batch_search(X, g, Q, k=10, t0=4, hops=4, width=16,
                               n_seeds=8, exact_merge=em, backend="pallas")
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_large_batch_backend_parity(index):
    ds, X, g = index
    Q = jnp.asarray(ds.Q)
    for kw in ({}, dict(exact_visited=True), dict(gather_limit=6)):
        a = large_batch_search(X, g, Q, k=10, ef=32, hops=40,
                               backend="xla", **kw)
        b = large_batch_search(X, g, Q, k=10, ef=32, hops=40,
                               backend="pallas", **kw)
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_build_backend_parity(index):
    """The graph build (nn_descent + diversify tiles) agrees across
    backends too — the whole stack sits behind the seam."""
    ds, _, _ = index
    cfg = dataclasses.replace(get_arch("tsdg-paper"), k_graph=8,
                              max_degree=8, lambda0=6, bridge_hubs=16,
                              bridge_k=4)
    ga = build_tsdg(ds.X, dataclasses.replace(cfg, kernel_backend="xla"))
    gb = build_tsdg(ds.X, dataclasses.replace(cfg, kernel_backend="pallas"))
    np.testing.assert_array_equal(np.asarray(ga.neighbors),
                                  np.asarray(gb.neighbors))
    np.testing.assert_array_equal(np.asarray(ga.lambdas),
                                  np.asarray(gb.lambdas))


def test_engine_cache_key_includes_backend(index):
    from repro.serve.engine import ANNEngine

    ds, _, _ = index
    cfg = dataclasses.replace(get_arch("tsdg-paper"), k_graph=8,
                              max_degree=8, lambda0=6, bridge_hubs=16,
                              bridge_k=4, serve_buckets=(8,),
                              kernel_backend="xla")
    eng = ANNEngine(ds.X, cfg, k=5)
    assert eng.backend == "xla"
    eng.query(ds.Q[:2])
    assert all(key[3] == "xla" for key in eng._compiled)


# ----------------------------------------------------------------------
# gather-fused path: in-kernel neighbor gather (scalar-prefetch DMA)
# ----------------------------------------------------------------------

from repro.kernels import l2dist as L2  # noqa: E402


@functools.partial(jax.jit, static_argnames=("metric", "backend", "gf"))
def _ndg(Q, X, idx, mask, metric, backend, gf):
    return HP.neighbor_distances(Q, X, idx, metric=metric, mask=mask,
                                 backend=backend, gather_fused=gf)


@pytest.mark.parametrize("d", [8, 100, 128, 960])
@pytest.mark.parametrize("metric", METRICS)
def test_gather_fused_parity_dims(rng, d, metric):
    """Fused DMA gather vs XLA oracle, bitwise, across dimensionalities
    including non-128-multiple d (100) and GIST-sized d (960)."""
    S, C, N = 13, 9, 150
    X = jnp.asarray(rng.normal(size=(N, d)).astype(np.float32))
    Q = jnp.asarray(rng.normal(size=(S, d)).astype(np.float32))
    idx = jnp.asarray(rng.integers(-2, N + 20, size=(S, C)).astype(np.int32))
    mask = jnp.asarray(rng.random((S, C)) > 0.3)
    a = _ndg(Q, X, idx, mask, metric, "xla", None)
    b = _ndg(Q, X, idx, mask, metric, "pallas", "on")
    c = _ndg(Q, X, idx, mask, metric, "pallas", "off")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_gather_fused_degenerate_idx(rng):
    """All-(-1), all-duplicate, all-out-of-range, and fully masked idx
    arrays must agree with the oracle and return INF where invalid."""
    S, C, d, N = 7, 6, 16, 64
    X = jnp.asarray(rng.normal(size=(N, d)).astype(np.float32))
    Q = jnp.asarray(rng.normal(size=(S, d)).astype(np.float32))
    cases = {
        "all_minus_one": np.full((S, C), -1, np.int32),
        "all_duplicate": np.full((S, C), 3, np.int32),
        "all_out_of_range": np.full((S, C), N + 7, np.int32),
        "sentinel_N": np.full((S, C), N, np.int32),
        "mixed": rng.integers(-5, N + 5, size=(S, C)).astype(np.int32),
    }
    for name, idx_np in cases.items():
        idx = jnp.asarray(idx_np)
        for mask in (None, jnp.zeros((S, C), bool)):
            a = _ndg(Q, X, idx, mask, "l2", "xla", None)
            b = _ndg(Q, X, idx, mask, "l2", "pallas", "on")
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
            invalid = ~((idx_np >= 0) & (idx_np < N))
            if mask is not None:
                invalid |= True
            assert (np.asarray(a)[invalid] > 1e37).all(), name


@pytest.mark.parametrize("bs", [2, 4, 8])
def test_gather_fused_multi_tile_parity(rng, bs):
    """Force a multi-tile grid (bs < S) so the double-buffered DMA path —
    the @pl.when(i+1<n) prefetch and the slot rotation — actually executes
    (the auto-picked bs covers small test batches in one tile)."""
    S, C, d, N = 20, 6, 32, 120
    X = jnp.asarray(rng.normal(size=(N, d)).astype(np.float32))
    Q3 = jnp.asarray(rng.normal(size=(S, 1, d)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, N, size=(S, C)).astype(np.int32))
    mask = jnp.asarray(rng.random((S, C)) > 0.2)
    a = _nd(Q3, X, idx, mask, "l2", "xla")
    b = L2.gather_block_distances_pallas(Q3, X, idx, mask, metric="l2",
                                         bs=bs, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("metric", METRICS)
def test_gather_fused_self_q_parity(rng, metric):
    """The diversify-tile pairwise block via q_idx: BOTH operand sides are
    gathered in-kernel (no [T, K, d] materialization at all)."""
    T, K, d, N = 6, 5, 24, 80
    X = jnp.asarray(rng.normal(size=(N, d)).astype(np.float32))
    nbr = jnp.asarray(rng.integers(0, N + 5, size=(T, K)).astype(np.int32))

    @functools.partial(jax.jit, static_argnames=("backend", "gf"))
    def pair(X, nbr, backend, gf):
        return HP.neighbor_distances(None, X, nbr, metric=metric,
                                     backend=backend, gather_fused=gf,
                                     q_idx=nbr)

    a = pair(X, nbr, "xla", None)
    b = pair(X, nbr, "pallas", "on")
    c = pair(X, nbr, "pallas", "off")
    assert a.shape == (T, K, K)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_gather_fused_e2e_small_batch(index):
    """End-to-end Algorithm 1: forced fused DMA path vs XLA oracle,
    bitwise ids AND dists."""
    ds, X, g = index
    Q = jnp.asarray(ds.Q)
    a = small_batch_search(X, g, Q, k=10, t0=4, hops=4, width=16,
                           n_seeds=8, backend="xla")
    b = small_batch_search(X, g, Q, k=10, t0=4, hops=4, width=16,
                           n_seeds=8, backend="pallas", gather_fused="on")
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_gather_fused_e2e_large_batch(index):
    """End-to-end Algorithm 2: forced fused DMA path vs XLA oracle."""
    ds, X, g = index
    Q = jnp.asarray(ds.Q)
    a = large_batch_search(X, g, Q, k=10, ef=32, hops=24, backend="xla")
    b = large_batch_search(X, g, Q, k=10, ef=32, hops=24, backend="pallas",
                           gather_fused="on")
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_gather_fused_hlo_elides_neighbor_buffer(rng):
    """The acceptance check: the [S, C, d] gathered-neighbor buffer exists
    in the lowered HLO of the gather-then-block path and does NOT exist in
    the fused path (the gather happens via in-kernel DMA)."""
    S, C, d, N = 11, 7, 19, 60
    X = jnp.asarray(rng.normal(size=(N, d)).astype(np.float32))
    Q = jnp.asarray(rng.normal(size=(S, d)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, N, size=(S, C)).astype(np.int32))

    def lower(gf):
        f = jax.jit(lambda q, x, i, _g=gf: HP.neighbor_distances(
            q, x, i, metric="l2", backend="pallas", gather_fused=_g))
        return f.lower(Q, X, idx).as_text()

    buf = f"tensor<{S}x{C}x{d}xf32>"
    assert buf in lower("off")
    assert buf not in lower("on")


def test_gather_fused_hlo_e2e_search(rng):
    """Same check through a whole jitted search: the per-hop [B, M, d]
    neighbor buffer disappears from the HLO when the fused path is on."""
    from repro.core.diversify import PackedGraph

    B, N, M, d = 5, 90, 6, 22
    X = jnp.asarray(rng.normal(size=(N, d)).astype(np.float32))
    Q = jnp.asarray(rng.normal(size=(B, d)).astype(np.float32))
    g = PackedGraph(
        neighbors=jnp.asarray(
            rng.integers(0, N, size=(N, M)).astype(np.int32)),
        lambdas=jnp.zeros((N, M), jnp.int32),
        degrees=jnp.full((N,), M, jnp.int32))

    def lower(gf):
        f = jax.jit(functools.partial(
            large_batch_search, k=4, ef=8, hops=6, n_seeds=8,
            backend="pallas", gather_fused=gf))
        return f.lower(X, g, Q).as_text()

    buf = f"tensor<{B}x{M}x{d}xf32>"
    assert buf in lower("off")
    assert buf not in lower("on")


# ----------------------------------------------------------------------
# VMEM budgeting: _pick_bs never overflows, C-split keeps parity
# ----------------------------------------------------------------------

def test_pick_bs_never_exceeds_budget(rng):
    """Property: for any realistic (Kq, C, d) the chosen block set fits
    the VMEM budget — including the former overflow regime (the old code
    stopped halving at bs=8 and could pick ~17 MB blocks)."""
    for _ in range(300):
        Kq = int(rng.integers(1, 65))
        C = int(rng.integers(1, 513))
        d = int(rng.integers(1, 1025))
        bs, bc = L2._pick_bs(Kq, C, d)
        assert 1 <= bs <= 128 and 1 <= bc <= C
        assert L2._block_bytes(bs, Kq, bc, d) <= L2.VMEM_BUDGET, \
            (Kq, C, d, bs, bc)


def test_pick_bs_gist_regression():
    """GIST d=960 with a wide candidate set: the old halving loop stopped
    at bs=8 (8*(32*960 + 512*960 + 32*512)*4 ≈ 17 MB > 4 MB budget); the
    fix keeps halving to bs=1, which fits."""
    bs, bc = L2._pick_bs(32, 512, 960)
    assert L2._block_bytes(bs, 32, bc, 960) <= L2.VMEM_BUDGET
    assert bs == 1 and bc == 512
    # even wider: a single row exceeds the budget -> candidate axis split
    bs, bc = L2._pick_bs(64, 1024, 960)
    assert L2._block_bytes(bs, 64, bc, 960) <= L2.VMEM_BUDGET
    assert bs == 1 and bc < 1024


def test_block_distances_csplit_parity(rng):
    """Forcing the candidate-split grid (bc < C) stays bitwise-identical
    to the oracle — padded candidate lanes are masked INF."""
    S, Kq, C, d, N = 9, 3, 11, 20, 70
    X = jnp.asarray(rng.normal(size=(N, d)).astype(np.float32))
    Q3 = jnp.asarray(rng.normal(size=(S, Kq, d)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, N + 5, size=(S, C)).astype(np.int32))
    a = _nd(Q3, X, idx, None, "l2", "xla")
    V = X[jnp.clip(idx, 0, N - 1)]
    m = (idx >= 0) & (idx < N)
    for bs, bc in ((2, 4), (1, 3), (4, 11)):
        b = L2.block_distances_pallas(Q3, V, m, metric="l2", bs=bs, bc=bc,
                                      interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"bs={bs},bc={bc}")


def test_gather_fused_fits_budget_check():
    assert L2.gather_fused_fits(1, 32, 128)
    assert not L2.gather_fused_fits(1, 4096, 1024)
    # self_q drops the Q tile from the bill: this shape fits only when the
    # query side is gathered in-kernel from the same ids
    assert L2.gather_fused_fits(512, 256, 960, self_q=True)
    assert not L2.gather_fused_fits(512, 256, 960)


def test_gather_fused_fits_int8_headroom():
    """d=960 headroom regression (DESIGN.md §8): the fp32 bill for a wide
    GIST-shaped gather blows the VMEM budget, but the same tile over int8
    rows is ~4x smaller (1-byte candidate rows + a 4-byte scale per
    candidate) and fits — compressed residency widens the fused-gather
    regime, it never narrows it."""
    assert not L2.gather_fused_fits(64, 1024, 960)               # fp32
    assert L2.gather_fused_fits(64, 1024, 960, itemsize=1)       # int8
    # the byte bill itself must reflect the operand itemsize
    fp32 = L2._gather_tile_bytes(64, 1024, 960, self_q=False)
    int8 = L2._gather_tile_bytes(64, 1024, 960, self_q=False, itemsize=1)
    assert int8 < fp32
    # candidate-row DMA bytes (the 2*C*row double buffer) shrink from
    # d*4 to d bytes rounded up to whole 512-byte packed-word rows
    assert 2 * 1024 * 960 * 4 - 2 * 1024 * 1024 == fp32 - int8 + 1024 * 4


def test_pick_bs_itemsize_aware(rng):
    """The block picker bills actual operand bytes: int8 candidate tiles
    admit equal-or-larger blocks than fp32 at every shape with d >= 2
    (below that the 4-byte scale column outweighs the 3-byte/element row
    saving), and the chosen blocks always fit the budget under their own
    itemsize."""
    for _ in range(100):
        Kq = int(rng.integers(1, 65))
        C = int(rng.integers(1, 513))
        d = int(rng.integers(2, 1025))
        bs32, bc32 = L2._pick_bs(Kq, C, d)
        bs8, bc8 = L2._pick_bs(Kq, C, d, itemsize=1)
        assert bs8 * bc8 >= bs32 * bc32, (Kq, C, d)
        assert L2._block_bytes(bs8, Kq, bc8, d, itemsize=1) \
            <= L2.VMEM_BUDGET, (Kq, C, d, bs8, bc8)


def test_gather_dispatch_pinned():
    """The gather-fused placement decision, exhaustively pinned: "on"
    always fuses, "off" never, and "auto" fuses only off-interpret (real
    TPU) AND inside the VMEM budget — the regression for the auto path
    silently fusing under interpret-mode DMA emulation."""
    assert HP.gather_dispatch("auto", interp=True, fits=True) is False
    assert HP.gather_dispatch("auto", interp=True, fits=False) is False
    assert HP.gather_dispatch("auto", interp=False, fits=True) is True
    assert HP.gather_dispatch("auto", interp=False, fits=False) is False
    assert HP.gather_dispatch("on", interp=True, fits=True) is True
    assert HP.gather_dispatch("on", interp=True, fits=False) is True
    assert HP.gather_dispatch("off", interp=False, fits=True) is False
    with pytest.raises(ValueError, match="gather_fused"):
        HP.gather_dispatch("always", interp=False, fits=True)


def test_pallas_paths_are_recorded(rng):
    """Each Pallas primitive notes the path it took and whether it ran in
    interpret mode (what chip_smoke.py reports and gates on)."""
    N, d, S, C = 50, 8, 4, 6
    X = jnp.asarray(rng.normal(size=(N, d)).astype(np.float32))
    Q = jnp.asarray(rng.normal(size=(S, d)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, N, size=(S, C)).astype(np.int32))
    HP.PATHS.clear()
    dist = HP.neighbor_distances(Q, X, idx, backend="pallas",
                                 gather_fused="on", interpret=True)
    HP.rank_merge(dist, idx, keep=2, backend="pallas", interpret=True)
    HP.neighbor_distances(Q, X, idx, backend="pallas", gather_fused="off",
                          interpret=True)
    assert HP.PATHS.get(("neighbor_distances", "fused_gather", True))
    assert HP.PATHS.get(("neighbor_distances", "gather_then_block", True))
    assert HP.PATHS.get(("rank_merge", "bitonic", True))
    HP.PATHS.clear()
    HP.neighbor_distances(Q, X, idx, backend="xla")
    assert not HP.PATHS  # the reference backend runs no kernel


def test_compile_cache_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins where set; otherwise the cache sits
    at a fixed .jax_cache/ in the checkout."""
    from repro.utils import compile_cache as CC

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert CC.use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = CC.use_compile_cache()
        assert path == str(CC.CHECKOUT / ".jax_cache")
        assert (CC.CHECKOUT / "src" / "repro").is_dir()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
