"""Small-batch NN search — paper Algorithm 1, TPU adaptation.

Per query, `t0` independent cheap greedy searches run in parallel; quality
comes from the *number* of searches, not per-search care (paper §4.1).  The
whole (B x t0) search population advances in lock-step: each hop is

  gather neighbor ids -> gather vectors -> one batched GEMM of distances
  -> lane-paired R_temp update -> half-merge into R_ij -> pick next u

which is exactly the paper's warp schedule with the 32-lane warp replaced by
vector lanes and the per-warp distance loop replaced by an MXU contraction.
The hop's distance evaluation and ranking merges go through the
``repro.core.hotpath`` primitives, so the Pallas and XLA kernel backends
share this file bit-for-bit (DESIGN.md §3).

Faithful details preserved:
  * 32 random seeds, best becomes the start node (no hierarchy needed);
  * R_temp lane-paired approximate update — candidate i only compares with
    cell i (cheap, deliberately lossy);
  * half-merge: best 16 of R_temp replace the worst 16 of R_ij (bitonic
    half-cleaner semantics), then R_ij is fully re-sorted; all merges dedup
    by id — a node reached through two edges (duplicate graph lanes, bridge
    splices) never occupies two ranking slots (explicit-set semantics,
    enforced by tests/test_search_dedup.py);
  * no expansion queue, no visited set; termination on no-improvement or T;
  * λ-prefix dynamic degree: only edges with λ < λ_limit are visited (the
    graph rows are λ-sorted, so this is a prefix mask).
`exact_merge=True` (beyond-paper toggle) replaces the lossy half-merge with
an exact top-32 merge — measured in benchmarks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import hotpath as HP
from repro.core.diversify import PackedGraph
from repro.utils.trace import scoped

INF = jnp.float32(3.4e38)


@functools.partial(
    jax.jit,
    static_argnames=("k", "t0", "hops", "hop_width", "n_seeds",
                     "lambda_limit", "metric", "exact_merge", "width",
                     "unroll", "backend", "gather_fused", "t0_total",
                     "rerank_mult", "visited"))
@scoped("search_small")
def _small_batch_search(X, graph: PackedGraph, Q, *, k: int = 10,
                       t0: int = 32, hops: int = 6, hop_width: int = 32,
                       n_seeds: int = 32, lambda_limit: int = 10,
                       metric: str = "l2", exact_merge: bool = False,
                       width: int = 32, seed: int = 0,
                       unroll: bool = False, seed_offset=0,
                       t0_offset=0, t0_total: int | None = None,
                       alive=None,
                       backend: str = "auto",
                       gather_fused: str | None = None,
                       codes=None, scales=None, rerank_mult: int = 0,
                       visited: str = "none"):
    """Returns (ids [B, k], dists [B, k]).  `seed_offset` may be traced
    (it perturbs the base key — a cheap way to decorrelate restarts).

    `alive` (optional traced [N] bool) is the streaming tombstone mask
    (DESIGN.md §7): dead rows are excluded from seed selection, from every
    hop's neighbor evaluation, and from the final merge, so a tombstoned
    vector can never surface in the results.  Tombstoned nodes are fully
    invisible (not routed *through* either) — adequate at serving-window
    deletion rates; heavier churn is folded back by compaction.  ``None``
    (the default) traces exactly the frozen-index computation.

    Random seeds are derived per search row (`fold_in` by global row index),
    so row i's draws depend only on (seed, seed_offset, i) — never on the
    batch size.  Appending padding queries (the serving engine's shape
    buckets) therefore leaves the real rows bitwise-identical to an unpadded
    call.

    `t0_offset` / `t0_total` place this call's searches inside a LARGER
    t0 population: query b's search j here is globally search
    ``b * t0_total + t0_offset + j`` (defaults: ``t0_total = t0``,
    ``t0_offset = 0`` — the whole population, bit-identical to older
    revisions).  The mesh execution plane splits the paper's t0 searches
    over the `model` axis with ``t0_offset = column * t0_local``, so the
    union of the columns' searches IS the single-device search population —
    the sharded small regime is bitwise-identical to the single-device one
    (DESIGN.md §6).  `t0_offset` may be traced (it is an `axis_index`
    product inside shard_map).

    ``codes`` [N, d] int8 + ``scales`` [N] f32 (compressed residency,
    DESIGN.md §8): seed selection and every hop score against the
    quantized rows in-kernel; the final merge keeps the best
    ``rerank_mult * k`` distinct survivors, re-scores them exactly
    against the fp32 ``X``, and only then takes top-k — returned
    distances are exact.  ``codes=None`` traces the frozen fp32
    computation bit-for-bit.

    ``graph.perm`` (locality-packed layout, DESIGN.md §10): when present,
    X/codes rows and graph ids are in packed (internal) order, but every
    externally-meaningful quantity stays in ORIGINAL id space — random
    seeds are drawn externally and mapped in, the ``alive`` mask is
    external, the visited filter hashes external ids, and candidate ids
    are mapped back external *before* the final (id, dist) dedup merge —
    so a packed index returns bitwise-identical results to the unpacked
    baseline.

    ``visited="hash"`` (DESIGN.md §10) consults a per-search bucketed
    hash set (:func:`repro.core.hotpath.visited_filter`) before
    candidates enter R_temp: already-seen ids drop to (INF, N) sentinels
    up front, so the hop skips the O(width²) dedup-by-id scans and the
    extra re-rank merge the paper path needs.  ``"none"`` traces the
    frozen computation bit-for-bit.
    """
    N, d = X.shape
    B = Q.shape[0]
    S = B * t0
    if k > t0 * width:
        raise ValueError(
            f"k={k} exceeds the candidate pool t0*width={t0 * width}; "
            "raise t0/width or lower k")
    if visited not in ("none", "hash"):
        raise ValueError(f"visited={visited!r} must be 'none' or 'hash'")
    perm = graph.perm
    if perm is not None:
        # old->new, in-trace (one [N] scatter per call — negligible vs the
        # search itself); maps external draws/ids into packed space
        inv = jnp.zeros((N,), jnp.int32).at[perm].set(
            jnp.arange(N, dtype=jnp.int32))
        alive_int = None if alive is None else alive[perm]
    else:
        inv = None
        alive_int = alive
    half = width // 2
    key = jax.random.fold_in(jax.random.key(seed), seed_offset)
    t0_total = t0 if t0_total is None else t0_total
    flat = jnp.arange(S)
    row_ids = (flat // t0) * t0_total + t0_offset + flat % t0
    row_keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(row_ids)

    Qs = jnp.repeat(Q, t0, axis=0)                            # [S, d]

    # --- seeds: best of n_seeds randoms (paper: as good as hierarchies);
    # half are drawn from the hub set when bridges are enabled ---------------
    seeds = jax.vmap(
        lambda rk: jax.random.randint(rk, (n_seeds,), 0, N, jnp.int32))(
        row_keys)                                             # [S, n_seeds]
    if perm is not None:  # draws are EXTERNAL ids (seed parity) -> map in
        seeds = inv[seeds]
    if graph.hubs is not None:
        nh = graph.hubs.shape[0]
        hub_pick = jax.vmap(
            lambda rk: jax.random.randint(jax.random.fold_in(rk, 1),
                                          (n_seeds // 2,), 0, nh))(row_keys)
        # hubs hold internal ids at layout-invariant POSITIONS, so the
        # same draw picks the same vector packed or not
        seeds = seeds.at[:, : n_seeds // 2].set(graph.hubs[hub_pick])
    seed_mask = alive_int[seeds] if alive is not None else None
    X_score = X if codes is None else codes  # int8 codes when quantized
    sd1, si1 = HP.seed_select(Qs, X_score, seeds, metric=metric, k=1,
                              mask=seed_mask, backend=backend,
                              gather_fused=gather_fused,
                              scales=scales)                  # [S, 1] each
    u, u_d = si1[:, 0], sd1[:, 0]

    rij_ids = jnp.full((S, width), N, jnp.int32)
    rij_d = jnp.full((S, width), INF)
    rij_ids = rij_ids.at[:, 0].set(u)
    rij_d = rij_d.at[:, 0].set(u_d)

    nbrs_all = graph.neighbors
    lams_all = graph.lambdas
    M_deg = nbrs_all.shape[1]
    n_chunks = max(1, -(-M_deg // hop_width))
    pad_m = n_chunks * hop_width - M_deg  # short NN lists -> one padded chunk
    tril_w = jnp.tril(jnp.ones((width, width), bool), k=-1)
    if perm is not None and n_chunks > 1:
        raise ValueError(
            f"packed layout requires hop_width >= max_degree (got "
            f"{hop_width} < {M_deg}): the chunked R_temp argmin pairs "
            "lanes positionally, which is only permutation-equivariant "
            "when a hop is a single chunk")

    def _ext(ids):  # internal -> external (hash keys, output ids)
        if perm is None:
            return ids
        return jnp.where(ids < N, perm[jnp.clip(ids, 0, N - 1)], ids)

    if visited == "hash":
        # <= M_deg fresh inserts per hop + the start node, per search row
        vtab = HP.visited_table(S, hops * M_deg + 1)
        vtab, _ = HP.visited_filter(vtab, _ext(u)[:, None],
                                    valid=(u < N)[:, None], backend=backend)

    def hop(state, _):
        if visited == "hash":
            u, rij_ids, rij_d, active, vtab = state
        else:
            u, rij_ids, rij_d, active = state
        nbrs = nbrs_all[u]                                    # [S, M]
        lams = lams_all[u]
        visit = lams < lambda_limit  # idx >= N masked by the primitive
        if alive is not None:  # tombstoned neighbors never enter a ranking
            visit = visit & alive_int[jnp.clip(nbrs, 0, N - 1)]
        if visited == "hash":
            # already-seen ids drop to (INF, N) sentinels BEFORE scoring:
            # the hop then needs no dedup scans and no re-rank merge.
            # External-id keys + the filter's canonical probe order make
            # the drop set layout-invariant (graph.perm docstring above).
            vtab_new, fresh = HP.visited_filter(
                vtab, _ext(nbrs), valid=visit & (nbrs < N) & active[:, None],
                backend=backend)
            visit = fresh
            nbrs = jnp.where(fresh, nbrs, N)
        dists = HP.neighbor_distances(Qs, X_score, nbrs, metric=metric,
                                      mask=visit, backend=backend,
                                      gather_fused=gather_fused,
                                      scales=scales)
        if pad_m:
            dists = jnp.concatenate(
                [dists, jnp.full((S, pad_m), INF)], axis=1)
            nbrs = jnp.concatenate(
                [nbrs, jnp.full((S, pad_m), N, jnp.int32)], axis=1)

        # R_temp: lane-paired min across chunks of `hop_width` (the warp trick)
        cd = dists.reshape(S, n_chunks, hop_width)
        ci = nbrs.reshape(S, n_chunks, hop_width)
        lane_arg = jnp.argmin(cd, axis=1)                     # [S, hop_width]
        rt_d = jnp.take_along_axis(cd, lane_arg[:, None, :], axis=1)[:, 0]
        rt_ids = jnp.take_along_axis(ci, lane_arg[:, None, :], axis=1)[:, 0]
        if hop_width < width:  # pad R_temp to R width
            pad = width - hop_width
            rt_d = jnp.concatenate([rt_d, jnp.full((S, pad), INF)], axis=1)
            rt_ids = jnp.concatenate(
                [rt_ids, jnp.full((S, pad), N, jnp.int32)], axis=1)

        rt_d_s, rt_ids_s = HP.rank_merge(rt_d, rt_ids, keep=width,
                                         backend=backend)
        if visited == "hash":
            # the filter already guarantees R_temp ids are distinct AND
            # absent from R_ij (every id enters a ranking at most once per
            # search), so the paper path's O(width²) dup scans and its
            # re-rank of the deduped half collapse into plain merges
            if exact_merge:
                new_d, new_ids = HP.rank_merge(
                    jnp.concatenate([rij_d, rt_d_s], axis=1),
                    jnp.concatenate([rij_ids, rt_ids_s], axis=1),
                    keep=width, backend=backend)
                improved = jnp.any(new_d < rij_d, axis=1)
            else:
                improved = jnp.any(rt_d_s[:, :half] < rij_d[:, half:],
                                   axis=1)
                new_d, new_ids = HP.rank_merge(
                    jnp.concatenate([rij_d[:, :half], rt_d_s[:, :half]],
                                    axis=1),
                    jnp.concatenate([rij_ids[:, :half], rt_ids_s[:, :half]],
                                    axis=1),
                    keep=width, backend=backend)
            new_u = rt_ids_s[:, 0]
            rij_d = jnp.where(active[:, None], new_d, rij_d)
            rij_ids = jnp.where(active[:, None], new_ids, rij_ids)
            u = jnp.where(active, new_u, u)
            active = active & improved
            return (u, rij_ids, rij_d, active, vtab_new), None
        # dedup R_temp by id: a node reached through two edges (duplicate
        # graph lanes, bridge splices) must not occupy two ranking slots.
        # The (dist, id) sort puts equal-id copies first-is-best, so "equal
        # to some earlier entry" keeps the best copy; dropped lanes become
        # (INF, N) sentinels instead of keep-masked (INF, id) lanes that
        # could shadow a real entry in the final id-dedup merge.
        dup_rt = jnp.any((rt_ids_s[:, :, None] == rt_ids_s[:, None, :])
                         & tril_w[None], axis=2) & (rt_ids_s < N)

        if exact_merge:  # beyond-paper: exact top-`width` of the union
            in_rij = jnp.any((rt_ids_s[:, :, None] == rij_ids[:, None, :])
                             & (rij_d[:, None, :] < INF), axis=2)
            drop = dup_rt | in_rij
            cat_d = jnp.concatenate(
                [rij_d, jnp.where(drop, INF, rt_d_s)], axis=1)
            cat_i = jnp.concatenate(
                [rij_ids, jnp.where(drop, N, rt_ids_s)], axis=1)
            new_d, new_ids = HP.rank_merge(cat_d, cat_i, keep=width,
                                           backend=backend)
            improved = jnp.any(new_d < rij_d, axis=1)
        else:  # paper: best half of R_temp replaces worst half of R_ij
            # also drop candidates already present in the kept R_ij half
            # (they'd double up after the concat below), then re-rank so
            # the best `half` *distinct new* candidates fill the slots
            in_keep = jnp.any(
                (rt_ids_s[:, :, None] == rij_ids[:, None, :half])
                & (rij_d[:, None, :half] < INF), axis=2)
            drop = dup_rt | in_keep
            rt_u_d, rt_u_i = HP.rank_merge(
                jnp.where(drop, INF, rt_d_s), jnp.where(drop, N, rt_ids_s),
                keep=width, backend=backend)
            improved = jnp.any(rt_u_d[:, :half] < rij_d[:, half:], axis=1)
            merged_d = jnp.concatenate(
                [rij_d[:, :half], rt_u_d[:, :half]], axis=1)
            merged_i = jnp.concatenate(
                [rij_ids[:, :half], rt_u_i[:, :half]], axis=1)
            new_d, new_ids = HP.rank_merge(merged_d, merged_i, keep=width,
                                           backend=backend)

        new_u = rt_ids_s[:, 0]                                # closest in R_temp
        # frozen searches keep their state
        rij_d = jnp.where(active[:, None], new_d, rij_d)
        rij_ids = jnp.where(active[:, None], new_ids, rij_ids)
        u = jnp.where(active, new_u, u)
        active = active & improved
        return (u, rij_ids, rij_d, active), None

    if visited == "hash":
        state = (u, rij_ids, rij_d, jnp.ones((S,), bool), vtab)
        (u, rij_ids, rij_d, _, _), _ = jax.lax.scan(
            hop, state, None, length=hops, unroll=unroll)
    else:
        state = (u, rij_ids, rij_d, jnp.ones((S,), bool))
        (u, rij_ids, rij_d, _), _ = jax.lax.scan(
            hop, state, None, length=hops, unroll=unroll)

    # --- merge the t0 searches of each query (dedup + top-k) ---------------
    # (id, dist)-lexsorted so the dedup keeps the BEST copy of each id: a
    # plain stable id-sort keeps the first *column*, which can be an
    # INF-distance copy (λ-masked lane that entered a ranking array),
    # shadowing the real entry
    # packed layout: back to EXTERNAL ids BEFORE the dedup sort, so the
    # (id, dist) order — and hence which duplicate survives — matches the
    # unpacked baseline exactly
    cand_ids = _ext(rij_ids.reshape(B, t0 * width))
    cand_d = rij_d.reshape(B, t0 * width)
    o = jnp.lexsort((cand_d, cand_ids), axis=1)
    sid = jnp.take_along_axis(cand_ids, o, axis=1)
    sd2 = jnp.take_along_axis(cand_d, o, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((B, 1), bool), sid[:, 1:] == sid[:, :-1]], axis=1)
    keep_lane = ~dup & (sid < N)
    if alive is not None:  # a dead best-seed id can linger in slot 0
        keep_lane = keep_lane & alive[jnp.clip(sid, 0, N - 1)]
    if codes is None:
        out_d, out_ids = HP.rank_merge(sd2, sid, keep=k,
                                       mask=keep_lane, backend=backend)
        return out_ids.astype(jnp.int32), out_d
    # exact fp32 re-rank: keep the best rerank_mult*k distinct survivors
    # of the approximate search, re-score them against the fp32 rows
    # (one narrow gather — the only fp32 row traffic of the whole query),
    # then take the true top-k.  Keep-masked lanes come back INF from the
    # merge, so they stay masked through the re-score and can't resurface.
    rerank = min(max(rerank_mult, 1) * k, sd2.shape[1])
    rr_d, rr_ids = HP.rank_merge(sd2, sid, keep=rerank,
                                 mask=keep_lane, backend=backend)
    # rr_ids are external; the packed fp32 rows want internal indices
    # (INF-masked lanes gather a garbage row harmlessly)
    gi = rr_ids if perm is None else \
        jnp.where(rr_ids < N, inv[jnp.clip(rr_ids, 0, N - 1)], rr_ids)
    ed = HP.neighbor_distances(Q, X, gi, metric=metric,
                               mask=rr_d < INF, backend=backend,
                               gather_fused=gather_fused)
    out_d, out_ids = HP.rank_merge(ed, rr_ids, keep=k, backend=backend)
    return out_ids.astype(jnp.int32), out_d


def small_batch_search(*args, **kwargs):
    """Deprecated public seam — prefer ``repro.ann.Index.search`` (DESIGN.md
    §5), which dispatches to this procedure automatically for small batches.
    Thin shim over :func:`_small_batch_search`; identical results."""
    from repro.utils.deprecation import warn_once
    warn_once("repro.core.search_small.small_batch_search",
              "repro.ann.Index.search")
    return _small_batch_search(*args, **kwargs)
