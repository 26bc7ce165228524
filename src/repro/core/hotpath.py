"""Kernel-dispatch seam for the search hot path (DESIGN.md §3).

Every hot loop in the ANN stack — ``search_small`` hops, ``search_large``
expansions, ``nn_descent`` candidate evaluation, the ``diversify`` pairwise
tiles — reduces to three primitives:

  * :func:`neighbor_distances` — fused gather-of-neighbor-vectors -> tiled
    distance block ([S, W, d] batched-rowwise generalization of
    ``kernels/l2dist.py``), with the validity keep-mask applied in-kernel.
    On the Pallas backend the gather itself moves in-kernel when
    ``gather_fused`` allows it: neighbor rows stream HBM->VMEM via
    scalar-prefetch DMAs instead of materializing ``X[idx]`` (DESIGN.md
    §2);
  * :func:`rank_merge` — (dist, id)-ascending merge of a candidate block
    into a ranking array, keeping the best ``keep`` per row (the id-carrying,
    keep-masked generalization of ``kernels/topk.py``);
  * :func:`seed_select` — distance + masked top-k over seed candidates
    (composition of the two, sharing one backend);
  * :func:`scan_distances` — whole-shard brute-force distance block (the
    streaming delta shard's scoring, DESIGN.md §7): no gather, one GEMM
    of the query batch against a small append-only array.

Two registered backends compute them:

  * ``"pallas"`` — the Pallas TPU kernels (interpret mode off-TPU, so CPU
    tests exercise the real kernel bodies);
  * ``"xla"`` — plain jnp with the *same* arithmetic formulation and the
    same (dist, id) total order, so the two backends are bit-identical —
    the parity contract ``tests/test_hotpath.py`` enforces end-to-end.

Selection comes from ``ANNConfig.kernel_backend``; the default ``"auto"``
resolves to ``"pallas"`` on TPU and falls back to ``"xla"`` elsewhere.
Third-party backends can be plugged in with :func:`register_backend` —
this seam is where every future kernel optimization lands.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import l2dist as _l2
from repro.kernels import topk as _topk
from repro.kernels import visited as _vf

INF = jnp.float32(3.4e38)

# (primitive, path, interpret) -> trace count, recorded when a Pallas
# primitive is traced: which kernel path each primitive took at the shapes
# it saw, and whether it ran in interpret mode (chip_smoke.py reports it)
PATHS: dict = {}


def _note(primitive: str, path: str, interp: bool) -> None:
    key = (primitive, path, bool(interp))
    PATHS[key] = PATHS.get(key, 0) + 1


def gather_dispatch(mode: str, interp: bool, fits: bool) -> bool:
    """The gather-fused placement decision, named so tests can pin it.

    ``"on"`` forces the in-kernel DMA gather (the parity tests);
    ``"off"`` never fuses; ``"auto"`` fuses only where it wins — on real
    TPU (BENCH_hotpath.json measured the fused path at 0.59x under
    interpret-mode DMA emulation, so ``interp`` opts out) and only when
    the tile fits the VMEM budget (``fits``).
    """
    if mode not in ("auto", "on", "off"):
        raise ValueError(
            f"gather_fused={mode!r} must be 'auto', 'on', or 'off'")
    return mode == "on" or (mode == "auto" and not interp and fits)


def _dist_block(Q3, V3, mask, metric: str, v_scale=None):
    """The shared arithmetic formulation (XLA reference). Mirrors
    ``l2dist._block_kernel`` op-for-op so both backends agree bitwise.
    ``v_scale`` [S, C] dequantizes int8 candidate rows exactly as the
    Pallas kernels do: widen to fp32, then scale, then contract.  On the
    quantized path the dequantized rows sit behind an optimization
    barrier (XLA would otherwise hoist the per-row scale out of the dot)
    and the norm terms are batched self-``dot_general`` contractions
    rather than multiply-then-``sum`` — a plain reduce's accumulation
    order varies with the surrounding program (1-ulp drift between the
    two backends' traces), a ``dot_general`` contraction does not.  The
    kernels mirror both choices under their ``pin`` flag."""
    Q3 = Q3.astype(jnp.float32)
    V3 = V3.astype(jnp.float32)
    pin = v_scale is not None
    if pin:
        V3 = jax.lax.optimization_barrier(V3 * v_scale[:, :, None])
    dots = jax.lax.dot_general(Q3, V3, (((2,), (2,)), ((0,), (0,))),
                               preferred_element_type=jnp.float32)
    if metric in ("ip", "cos"):
        dist = -dots
    else:
        if pin:
            nd = (((2,), (2,)), ((0, 1), (0, 1)))
            qn = jax.lax.dot_general(Q3, Q3, nd,
                                     preferred_element_type=jnp.float32)
            vn = jax.lax.dot_general(V3, V3, nd,
                                     preferred_element_type=jnp.float32)
        else:
            qn = jnp.sum(Q3 * Q3, axis=2)
            vn = jnp.sum(V3 * V3, axis=2)
        dist = qn[:, :, None] + vn[:, None, :] - 2.0 * dots
    return jnp.where(mask[:, None, :], dist, INF)


def _valid_mask(X, idx, mask):
    valid = (idx >= 0) & (idx < X.shape[0])
    if mask is not None:
        valid = valid & mask
    return valid


def _gather_and_mask(X, idx, mask):
    return X[jnp.clip(idx, 0, X.shape[0] - 1)], _valid_mask(X, idx, mask)


def _q3_of(Q, X, q_idx):
    """Resolve the query block: explicit Q ([S, d] squeezed or [S, Kq, d]),
    or rows of X named by ``q_idx`` [S, Kq] (the diversify tiles — lets the
    fused kernel gather the query side in-kernel too)."""
    if q_idx is not None:
        return X[jnp.clip(q_idx, 0, X.shape[0] - 1)], False
    squeeze = Q.ndim == 2
    return (Q[:, None, :] if squeeze else Q), squeeze


def _interp(interpret):
    """Interpret mode unless on a TPU (or told otherwise): the CPU tests
    run the real kernel bodies through the Pallas interpreter."""
    return (jax.default_backend() != "tpu") if interpret is None else interpret


class _XlaBackend:
    """Pure-jnp reference path — always available, always the oracle."""

    name = "xla"

    @staticmethod
    def neighbor_distances(Q, X, idx, *, metric, mask=None, interpret=None,
                           gather_fused=None, q_idx=None, scales=None):
        V, m = _gather_and_mask(X, idx, mask)
        Q3, squeeze = _q3_of(Q, X, q_idx)
        sc = None if scales is None \
            else scales[jnp.clip(idx, 0, X.shape[0] - 1)]
        out = _dist_block(Q3, V, m, metric, v_scale=sc)
        return out[:, 0] if squeeze else out

    @staticmethod
    def rank_merge(dists, ids, *, keep, mask=None, interpret=None):
        if not 0 < keep <= dists.shape[1]:
            raise ValueError(f"keep={keep} must be in (0, {dists.shape[1]}]")
        if mask is not None:
            dists = jnp.where(mask, dists, INF)
        # lexsort((ids, dists)) = ascending (dist, id) — exactly the bitonic
        # network's compare-exchange order, so backends agree on ties
        order = jnp.lexsort((ids, dists), axis=1)
        return (jnp.take_along_axis(dists, order, axis=1)[:, :keep],
                jnp.take_along_axis(ids, order, axis=1)[:, :keep])

    @staticmethod
    def scan_distances(Q, Xd, *, metric, mask=None, interpret=None,
                       scales=None):
        m = jnp.ones((Xd.shape[0],), bool) if mask is None else mask
        sc = None if scales is None else scales[None]
        return _dist_block(Q[None], Xd[None], m[None], metric,
                           v_scale=sc)[0]

    @staticmethod
    def visited_filter(table, ids, valid, *, interpret=None):
        return _vf.visited_filter_xla(table, ids, valid)


class _PallasBackend:
    """Fused device kernels (interpret mode when not on TPU)."""

    name = "pallas"

    @staticmethod
    def neighbor_distances(Q, X, idx, *, metric, mask=None, interpret=None,
                           gather_fused=None, q_idx=None, scales=None):
        interp = _interp(interpret)
        mode = gather_fused or "auto"
        if mode not in ("auto", "on", "off"):
            raise ValueError(
                f"gather_fused={mode!r} must be 'auto', 'on', or 'off'")
        C = idx.shape[-1]
        d = X.shape[1]
        # the in-kernel query gather only pays off when the query rows are
        # the candidate rows (the diversify tiles pass the same id array)
        self_q = q_idx is idx and q_idx is not None
        if self_q and scales is not None:
            raise ValueError("self_q tiles (build-time diversify) score "
                             "fp32 rows; scales= is a search-time knob")
        Kq = C if self_q else (
            1 if (q_idx is None and Q.ndim == 2) else
            (q_idx.shape[-1] if q_idx is not None else Q.shape[1]))
        # int8 codes DMA 1 byte/element — the fused window widens ~4x
        fits = _l2.gather_fused_fits(Kq, C, d, self_q=self_q,
                                     itemsize=X.dtype.itemsize)
        use_fused = gather_dispatch(mode, interp, fits)
        _note("neighbor_distances",
              "fused_gather" if use_fused else "gather_then_block", interp)
        idx_c = jnp.clip(idx, 0, X.shape[0] - 1)
        sc = None if scales is None else scales[idx_c]
        if not use_fused:
            V, m = _gather_and_mask(X, idx, mask)
            Q3, squeeze = _q3_of(Q, X, q_idx)
            out = _l2.block_distances_pallas(Q3, V, m, sc, metric=metric,
                                             interpret=interp)
            return out[:, 0] if squeeze else out
        m = _valid_mask(X, idx, mask)
        if self_q:
            out = _l2.gather_block_distances_pallas(
                None, X, idx_c, m, metric=metric, interpret=interp,
                self_q=True)
            return out
        Q3, squeeze = _q3_of(Q, X, q_idx)
        out = _l2.gather_block_distances_pallas(
            Q3, X, idx_c, m, sc, metric=metric, interpret=interp)
        return out[:, 0] if squeeze else out

    @staticmethod
    def rank_merge(dists, ids, *, keep, mask=None, interpret=None):
        interp = _interp(interpret)
        _note("rank_merge", "bitonic", interp)
        return _topk.rank_merge_pallas(dists, ids, mask, keep=keep,
                                       interpret=interp)

    @staticmethod
    def scan_distances(Q, Xd, *, metric, mask=None, interpret=None,
                       scales=None):
        # bs=1: the whole scan is ONE [1, B, cap] block — the same operand
        # shapes as the XLA reference's single contraction, so the backends
        # keep their bitwise-parity contract (row tiling would change the
        # gemm's accumulation grouping)
        m = jnp.ones((Xd.shape[0],), bool) if mask is None else mask
        sc = None if scales is None else scales[None]
        interp = _interp(interpret)
        _note("scan_distances", "block", interp)
        out = _l2.block_distances_pallas(Q[None], Xd[None], m[None], sc,
                                         metric=metric, bs=1,
                                         interpret=interp)
        return out[0]

    @staticmethod
    def visited_filter(table, ids, valid, *, interpret=None):
        interp = _interp(interpret)
        _note("visited_filter", "hash_table", interp)
        return _vf.visited_filter_pallas(table, ids, valid,
                                         interpret=interp)


_REGISTRY = {"xla": _XlaBackend, "pallas": _PallasBackend}


def register_backend(name: str, impl) -> None:
    """Register a kernel backend (must provide ``neighbor_distances`` and
    ``rank_merge`` with the signatures above)."""
    _REGISTRY[name] = impl


def backends() -> tuple:
    return tuple(sorted(_REGISTRY))


def resolve_backend(name: str | None = None) -> str:
    """``"auto"``/None -> "pallas" on TPU, "xla" everywhere else (the
    auto-fallback that keeps CPU runs on the compiled-XLA path instead of
    slow interpret-mode kernels).  Explicit names are validated."""
    name = name or "auto"
    if name == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown kernel backend {name!r}; registered: {backends()}")
    return name


# --------------------------------------------------------------------------
# the three public primitives
# --------------------------------------------------------------------------

def neighbor_distances(Q, X, idx, *, metric: str = "l2", mask=None,
                       backend: str | None = None, interpret=None,
                       gather_fused: str | None = None, q_idx=None,
                       scales=None):
    """Fused gather + distance block, smaller = closer.

    Q [S, d] (or [S, Kq, d]), X [N, d], idx [S, C] -> [S, C] (or
    [S, Kq, C]) float32.  Rows of ``idx`` outside [0, N) and lanes where
    ``mask`` (optional [S, C] bool) is False come back as INF.

    ``q_idx`` [S, Kq] replaces ``Q`` (pass Q=None) with rows of ``X`` —
    the diversify tiles' pairwise blocks.  When ``q_idx`` is the SAME
    array object as ``idx`` (Python ``is`` — value equality cannot be
    detected at trace time) the fused Pallas path gathers the query side
    in-kernel too; a distinct-but-equal array still computes correctly
    but pays an XLA-level gather for the query block.

    ``gather_fused`` selects the Pallas backend's gather placement:
    ``"auto"`` (in-kernel scalar-prefetch DMA gather on real TPU, the
    XLA-gather-then-block path in interpret mode or when the tile exceeds
    the VMEM budget), ``"on"`` (force the DMA path — the parity tests),
    ``"off"`` (always gather at the XLA level).  The XLA backend ignores
    it: that path stays the bitwise oracle.

    ``scales`` [N] float32 switches on compressed residency (DESIGN.md
    §8): X is then the per-row int8 code matrix and every candidate row
    is dequantized in-kernel as ``code * scale`` before the contraction —
    approximate distances whose survivors the search re-ranks exactly.
    """
    b = resolve_backend(backend)
    return _REGISTRY[b].neighbor_distances(
        Q, X, idx, metric=metric, mask=mask, interpret=interpret,
        gather_fused=gather_fused, q_idx=q_idx, scales=scales)


def rank_merge(dists, ids, *, keep: int, mask=None,
               backend: str | None = None, interpret=None):
    """Row-wise ascending (dist, id) sort carrying ids; returns the best
    ``keep`` per row as (dists [S, keep], ids [S, keep]).  ``mask`` lanes
    that are False are demoted to INF distance (ids untouched)."""
    b = resolve_backend(backend)
    return _REGISTRY[b].rank_merge(dists, ids, keep=keep, mask=mask,
                                   interpret=interpret)


def scan_distances(Q, Xd, *, metric: str = "l2", mask=None,
                   backend: str | None = None, interpret=None,
                   scales=None):
    """Brute-force distance block of a whole (delta) shard against a query
    batch: Q [B, d], Xd [cap, d] -> [B, cap] float32, smaller = closer.

    The streaming delta shard's scoring primitive (DESIGN.md §7): freshly
    added vectors live in a small append-only array searched exhaustively —
    one [B, cap] GEMM per call, no graph — and merged with the base graph's
    candidates by ``distributed.merge_topk``.  ``mask`` (optional [cap]
    bool) demotes unfilled / tombstoned delta slots to INF in-kernel, the
    same keep-mask semantics as :func:`neighbor_distances`.  Both backends
    share the :func:`_dist_block` arithmetic, so they agree bitwise (the
    parity contract of ``tests/test_hotpath.py``).  ``scales`` [cap]
    float32 marks Xd as int8 codes (compressed delta shard) and
    dequantizes in-kernel, same as :func:`neighbor_distances`."""
    b = resolve_backend(backend)
    impl = _REGISTRY[b]
    fn = getattr(impl, "scan_distances", None)
    if fn is None:  # third-party backend: synthesize from the gather form
        idx = jnp.broadcast_to(
            jnp.arange(Xd.shape[0], dtype=jnp.int32),
            (Q.shape[0], Xd.shape[0]))
        m = None if mask is None else jnp.broadcast_to(mask, idx.shape)
        return impl.neighbor_distances(Q, Xd, idx, metric=metric, mask=m,
                                       interpret=interpret, scales=scales)
    return fn(Q, Xd, metric=metric, mask=mask, interpret=interpret,
              scales=scales)


def visited_table(rows: int, bound: int, *, ways: int = 8) -> jax.Array:
    """Empty visited-filter table for ``rows`` independent searches, sized
    for at most ``bound`` distinct insertions each at load factor <= 1/2
    (buckets are a power of two >= 64, so bucket-overflow drops stay
    rare).  Shape [rows, ways, n_buckets] int32, all ``EMPTY``."""
    n_buckets = 64
    need = -(-2 * bound // ways)
    while n_buckets < need:
        n_buckets *= 2
    return jnp.full((rows, ways, n_buckets), _vf.VF_EMPTY, jnp.int32)


def visited_filter(table, ids, *, valid, backend: str | None = None,
                   interpret=None):
    """Probe-and-insert a lane block into per-row visited hash sets.

    ``table`` [B, W, S] int32 (from :func:`visited_table`), ``ids``
    [B, M] int32 node ids, ``valid`` [B, M] bool -> ``(table', fresh)``
    with ``fresh`` [B, M] bool marking lanes that are valid, were NOT
    already in the row's set, and were inserted now.  A full bucket
    reports not-fresh (safe drop, never a duplicate) — see
    ``kernels/visited.py`` for the structure.

    Lanes are processed in a canonical order (ascending id, invalid lanes
    last; ties are bitwise-duplicate inserts, so their order cannot
    matter) rather than lane order: within one call every id is probed
    against the SAME table state regardless of how the caller's lanes are
    arranged, which is what makes the packed-layout searches — whose hops
    present the same multiset of ids in a permuted lane order — bitwise
    equal to the unpacked baseline.  Backends share one int32 formulation
    (``kernels/visited.lane_step``), so the parity contract holds here
    too.
    """
    B, M = ids.shape
    key = jnp.where(valid, ids, jnp.int32(2147483647))
    order = jnp.argsort(key, axis=1, stable=True)
    s_ids = jnp.take_along_axis(ids, order, axis=1)
    s_valid = jnp.take_along_axis(valid, order, axis=1)
    b = resolve_backend(backend)
    impl = _REGISTRY[b]
    fn = getattr(impl, "visited_filter", None)
    if fn is None:  # third-party backend: the reference path is always legal
        fn = _XlaBackend.visited_filter
    table2, s_fresh = fn(table, s_ids, s_valid, interpret=interpret)
    unsort = jnp.argsort(order, axis=1)
    return table2, jnp.take_along_axis(s_fresh, unsort, axis=1)


def seed_select(Q, X, seeds, *, metric: str = "l2", k: int = 1, mask=None,
                backend: str | None = None, interpret=None,
                gather_fused: str | None = None, scales=None):
    """Distance + masked top-k over seed candidates: returns
    (dists [S, k], ids [S, k]) of the k closest valid seeds per row.
    ``scales`` as in :func:`neighbor_distances` (int8 codes in X)."""
    b = resolve_backend(backend)
    d = _REGISTRY[b].neighbor_distances(Q, X, seeds, metric=metric,
                                        mask=mask, interpret=interpret,
                                        gather_fused=gather_fused,
                                        scales=scales)
    return _REGISTRY[b].rank_merge(d, seeds, keep=k, interpret=interpret)
