"""Large-batch NN search — paper Algorithm 2, TPU adaptation.

One best-first search per query, advanced in lock-step across the batch
(the TPU analogue of one-thread-block-per-query).  The paper's three data
structures are kept with their exact hashed-segment layouts:

  R — top-`ef` ranking array, fixed size, Δ-relaxed termination
      ``m(u,q) > m(f,q) + Δ`` (f = furthest element of a full R);
  C — expansion queue: `m` segments (segment = id % m) of fixed width;
      insertion evicts the most distant entry of the segment, pop takes the
      global min over segment heads;
  V — visited table: `mv` circular unsorted segments (id % mv), lossy by
      design — only expanded nodes are recorded (paper: "only the nodes used
      in the expansion are pushed into V").

TPU adaptation (DESIGN.md §2): the CUDA motivation for *sorted* segments was
O(1) warp-wide pops; on TPU an [m x seg] masked argmin is a single vector op,
so segments are stored unsorted with validity masks — same behaviour (hash
placement, per-segment eviction), one less sort per hop.  R-merges dedup by
id (strictly better than the paper under a lossy V; noted in EXPERIMENTS).

The whole batch advances as one [B, ...] state (no vmap): the per-hop
neighbor evaluation is a single fused ``hotpath.neighbor_distances`` call
and every ranking update is a ``hotpath.rank_merge`` — the kernel-backend
seam (DESIGN.md §3) that lets the Pallas and XLA paths share this file.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import hotpath as HP
from repro.core.diversify import PackedGraph
from repro.utils.trace import scoped

INF = jnp.float32(3.4e38)


def _seg_merge(d3, i3, keep: int, backend: str):
    """Per-segment eviction merge: [B, m, W] -> keep smallest `keep` per
    segment (one rank_merge over the flattened segment rows)."""
    B, m, W = d3.shape
    dd, ii = HP.rank_merge(d3.reshape(B * m, W), i3.reshape(B * m, W),
                           keep=keep, backend=backend)
    return dd.reshape(B, m, keep), ii.reshape(B, m, keep)


@functools.partial(
    jax.jit,
    static_argnames=("k", "ef", "hops", "lambda_limit", "metric",
                     "n_seeds", "m_seg", "seg", "mv_seg", "segv",
                     "push_all_seeds", "unroll", "gather_limit",
                     "exact_visited", "backend", "gather_fused",
                     "rerank_mult", "visited"))
@scoped("search_large")
def _large_batch_search(X, graph: PackedGraph, Q, *, k: int = 10,
                       ef: int = 64, hops: int = 128, lambda_limit: int = 5,
                       metric: str = "l2", n_seeds: int = 32,
                       m_seg: int = 8, seg: int = 32, mv_seg: int = 8,
                       segv: int = 32, delta: float = 0.0, seed: int = 0,
                       seed_offset=0,
                       push_all_seeds: bool = True, unroll: bool = False,
                       gather_limit: int = 0, exact_visited: bool = False,
                       alive=None,
                       backend: str = "auto",
                       gather_fused: str | None = None,
                       codes=None, scales=None, rerank_mult: int = 0,
                       visited: str = "none"):
    """Returns (ids [B, k], dists [B, k]).

    `alive` (optional traced [N] bool) is the streaming tombstone mask
    (DESIGN.md §7): dead rows are dropped from the seed pool and from every
    expansion's neighbor admission, so they can never enter R or C.  ``None``
    (the default) traces exactly the frozen-index computation.

    `gather_limit` > 0 fetches only that many λ-sorted columns per row (the
    rows are λ-ascending, so this is the paper's dynamic-degree prefix
    pushed down into the gather itself — beyond-paper, see EXPERIMENTS §Perf).

    `exact_visited=True` (beyond-paper, EXPERIMENTS §Perf cell 3) replaces
    the paper's lossy circular V with an exact per-query byte table in HBM:
    every *evaluated* node is marked, so the per-hop membership tests
    collapse from three structure scans (V rows, C rows, R array) to one
    [M]-byte gather — the CUDA shared-memory capacity constraint that
    forced the lossy V does not exist on TPU.

    ``codes`` [N, d] int8 + ``scales`` [N] f32 (compressed residency,
    DESIGN.md §8): seed selection and every expansion score against the
    quantized rows in-kernel; the top ``rerank_mult * k`` of the final R
    are re-scored exactly against the fp32 ``X`` before the returned
    top-k — returned distances are exact.  ``codes=None`` traces the
    frozen fp32 computation bit-for-bit.

    ``graph.perm`` (locality-packed layout, DESIGN.md §10): X/codes rows
    and graph ids are then in packed (internal) order; seeds are drawn in
    EXTERNAL id space and mapped in, every id-hash placement (C segments,
    circular-V segments, the visited filter) keys on the external id, the
    ``alive`` mask is external, and R's ids are mapped back external
    before they leave — a packed index answers bitwise-identically to the
    unpacked baseline.

    ``visited="hash"`` (DESIGN.md §10) replaces the lossy circular V AND
    the three per-hop membership scans (V rows, C rows, R array) with one
    bucketed hash-set probe per neighbor lane
    (:func:`repro.core.hotpath.visited_filter`) — exact up to rare
    bucket-overflow *drops* (never duplicates).  Mutually exclusive with
    ``exact_visited``; ``"none"`` traces the frozen computation
    bit-for-bit.
    """
    N, d = X.shape
    B = Q.shape[0]
    if k > ef:
        raise ValueError(f"k={k} exceeds the ranking array size ef={ef}; "
                         "raise ef or lower k")
    if visited not in ("none", "hash"):
        raise ValueError(f"visited={visited!r} must be 'none' or 'hash'")
    if visited == "hash" and exact_visited:
        raise ValueError("visited='hash' replaces the visited structures; "
                         "it cannot combine with exact_visited=True")
    perm = graph.perm
    if perm is not None:
        if gather_limit:
            raise ValueError(
                "packed layouts re-sort neighbor rows by id, destroying "
                f"the λ-ascending prefix gather_limit={gather_limit} "
                "relies on")
        inv = jnp.zeros((N,), jnp.int32).at[perm].set(
            jnp.arange(N, dtype=jnp.int32))
        alive_int = None if alive is None else alive[perm]
    else:
        inv = None
        alive_int = alive

    def _ext(ids):  # internal -> external (hash keys, output ids)
        if perm is None:
            return ids
        return jnp.where(ids < N, perm[jnp.clip(ids, 0, N - 1)], ids)

    def _ext_hash(ids):  # hash key for CLIPPED ids (always < N)
        return ids if perm is None else perm[ids]
    key = jax.random.key(seed)
    # per-row keys: row i's seeds depend only on (seed, seed_offset + i),
    # never on B, so padded batches (serving shape buckets) match unpadded
    # calls bitwise.  `seed_offset` may be traced — the mesh execution plane
    # passes each model column's global row offset so a query's search is
    # seeded by its GLOBAL batch row, making model-sharded execution
    # bitwise-identical to the single-device plane (DESIGN.md §6).
    row_keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(B) + seed_offset)
    seeds = jax.vmap(
        lambda rk: jax.random.randint(rk, (n_seeds,), 0, N, jnp.int32))(
        row_keys)                                             # [B, n_seeds]
    if perm is not None:  # draws are EXTERNAL ids (seed parity) -> map in
        seeds = inv[seeds]
    if graph.hubs is not None:
        nh = graph.hubs.shape[0]
        hub_pick = jax.vmap(
            lambda rk: jax.random.randint(jax.random.fold_in(rk, 1),
                                          (n_seeds // 2,), 0, nh))(row_keys)
        # hubs hold internal ids at layout-invariant POSITIONS
        seeds = seeds.at[:, : n_seeds // 2].set(graph.hubs[hub_pick])

    nbrs_all, lams_all = graph.neighbors, graph.lambdas
    if gather_limit and gather_limit < nbrs_all.shape[1]:
        nbrs_all = nbrs_all[:, :gather_limit]
        lams_all = lams_all[:, :gather_limit]
    Mdeg = nbrs_all.shape[1]
    rows = jnp.arange(B)

    # ---- init: distance + masked top-k over the seeds (one fused call);
    # repeated seed ids are deduped via the keep-mask so they can't occupy
    # several R slots ------------------------------------------------------
    so = jnp.argsort(seeds, axis=1)
    ss_ids = jnp.take_along_axis(seeds, so, axis=1)
    dupm = jnp.concatenate([jnp.zeros((B, 1), bool),
                            ss_ids[:, 1:] == ss_ids[:, :-1]], axis=1)
    seed_keep = ~dupm if alive is None else ~dupm & alive_int[ss_ids]
    X_score = X if codes is None else codes  # int8 codes when quantized
    init_d, sids = HP.seed_select(Q, X_score, ss_ids, metric=metric,
                                  k=n_seeds, mask=seed_keep, backend=backend,
                                  gather_fused=gather_fused, scales=scales)
    if not push_all_seeds:
        # keep only the best seed (paper: R = C = {u}); sorted, so column 0
        first = jnp.arange(n_seeds)[None, :] == 0
        init_d = jnp.where(first, init_d, INF)
    init_ids = jnp.where(init_d < INF, sids, N)

    R_ids = jnp.full((B, ef), N, jnp.int32)
    R_d = jnp.full((B, ef), INF)
    n_init = min(ef, n_seeds)
    R_ids = R_ids.at[:, :n_init].set(init_ids[:, :n_init])
    R_d = R_d.at[:, :n_init].set(init_d[:, :n_init])
    # C: hashed-segment batch insert of the seeds
    C_ids = jnp.full((B, m_seg, seg), N, jnp.int32)
    C_d = jnp.full((B, m_seg, seg), INF)
    # hash placements key on the EXTERNAL id so packed/unpacked layouts
    # fill identical structures (sentinel lanes are masked via smask)
    seg_of = _ext_hash(jnp.clip(init_ids, 0, N - 1)) % m_seg
    smask = (init_d < INF)[:, None, :] \
        & (seg_of[:, None, :] == jnp.arange(m_seg)[None, :, None])
    cd = jnp.where(smask, init_d[:, None, :], INF)
    ci = jnp.where(smask, init_ids[:, None, :], N)
    C_d, C_ids = _seg_merge(jnp.concatenate([C_d, cd], axis=2),
                            jnp.concatenate([C_ids, ci], axis=2),
                            seg, backend)
    if visited == "hash":
        # the hash set subsumes V *and* the per-hop C/R membership scans;
        # V_ptr is unused.  Seeds are inserted up front (they are already
        # in R and C, so a neighbor lane hitting a seed must not be fresh).
        V, _ = HP.visited_filter(
            HP.visited_table(B, n_seeds + hops * Mdeg),
            _ext(init_ids), valid=init_d < INF, backend=backend)
        V_ptr = jnp.zeros((B, 1), jnp.int32)
    elif exact_visited:
        # mark the evaluated seeds; V_ptr is unused in this mode.  Marks are
        # monotone (never unset), so `.max` keeps duplicate-index scatters
        # (INF lanes clip onto node N-1) deterministic
        V = jnp.zeros((B, N), jnp.uint8).at[
            rows[:, None], jnp.clip(init_ids, 0, N - 1)].max(
            jnp.where(init_d < INF, 1, 0).astype(jnp.uint8))
        V_ptr = jnp.zeros((B, 1), jnp.int32)
    else:
        V = jnp.full((B, mv_seg, segv), N, jnp.int32)
        V_ptr = jnp.zeros((B, mv_seg), jnp.int32)

    tril = jnp.tril(jnp.ones((Mdeg, Mdeg), bool), k=-1)

    def step(state, _):
        R_ids, R_d, C_ids, C_d, V, V_ptr, done = state

        # ---- pop global min from C (argmin over m x seg lanes) -------
        flat_d = C_d.reshape(B, -1)
        flat_i = C_ids.reshape(B, -1)
        pidx = jnp.argmin(flat_d, axis=1)
        u_d = jnp.take_along_axis(flat_d, pidx[:, None], axis=1)[:, 0]
        u = jnp.take_along_axis(flat_i, pidx[:, None], axis=1)[:, 0]
        empty = u_d >= INF
        C_d2 = flat_d.at[rows, pidx].set(INF).reshape(B, m_seg, seg)
        C_ids2 = flat_i.at[rows, pidx].set(N).reshape(B, m_seg, seg)

        # ---- Δ-relaxed termination (only once R is full) -------------
        r_full = R_d[:, ef - 1] < INF
        worst = jnp.where(r_full, R_d[:, ef - 1], INF)
        terminate = empty | (r_full & (u_d > worst + delta))
        now_done = done | terminate
        u_safe = jnp.clip(u, 0, N - 1)

        # ---- neighbors of u, λ-prefix masked --------------------------
        e = nbrs_all[u_safe]                               # [B, M]
        lam = lams_all[u_safe]
        ok = (lam < lambda_limit) & (e < N) & ~now_done[:, None]
        e_safe = jnp.clip(e, 0, N - 1)
        if alive is not None:  # tombstoned neighbors never enter R or C
            ok = ok & alive_int[e_safe]
        # drop repeats within this neighbor list (bridge splicing can
        # duplicate an existing edge) — keep the first occurrence
        dup_here = jnp.any(
            (e_safe[:, :, None] == e_safe[:, None, :]) & tril[None],
            axis=2)

        if visited == "hash":
            # one probe-and-insert answers "seen before?" for V, C, and R
            # at once (every id that ever entered a ranking structure went
            # through the filter first) and subsumes dup_here: duplicate
            # lanes of one hop can't both be fresh.  `ok` already excludes
            # done rows, so frozen rows never mutate their table.
            V2, fresh = HP.visited_filter(V, _ext(e), valid=ok,
                                          backend=backend)
            new = fresh
            V_ptr2 = V_ptr
        elif exact_visited:
            # one byte-gather replaces all three membership scans;
            # evaluated nodes are marked immediately below (`.max` so a
            # duplicate edge's no-op lane can't erase its twin's fresh mark)
            v_here = jnp.take_along_axis(V, e_safe, axis=1)
            in_any = v_here == 1
            new = ok & ~in_any & ~dup_here
            V2 = V.at[rows[:, None], e_safe].max(
                jnp.where(new, 1, 0).astype(jnp.uint8))
            V_ptr2 = V_ptr
        else:
            # ---- V.add(u) (circular segment insert, paper Alg.2) -----
            vs = _ext_hash(u_safe) % mv_seg
            slot = jnp.take_along_axis(V_ptr, vs[:, None], axis=1)[:, 0] \
                % segv
            V2 = V.at[rows, vs, slot].set(u_safe)
            V_ptr2 = V_ptr.at[rows, vs].add(1)
            V2 = jnp.where(now_done[:, None, None], V, V2)
            V_ptr2 = jnp.where(now_done[:, None], V_ptr, V_ptr2)
            # membership tests: e ∉ V and e ∉ C (paper line 15); the
            # [B, M, seg] segment gathers of V and C are named in traces
            with jax.named_scope("membership_scan"):
                in_V = jnp.any(V2[rows[:, None], _ext_hash(e_safe) % mv_seg]
                               == e_safe[:, :, None], axis=2)
                c_seg = _ext_hash(e_safe) % m_seg
                c_rows_ids = C_ids2[rows[:, None], c_seg]       # [B, M, seg]
                c_rows_d = C_d2[rows[:, None], c_seg]
                in_C = jnp.any((c_rows_ids == e_safe[:, :, None])
                               & (c_rows_d < INF), axis=2)
                in_R = jnp.any((R_ids[:, None, :] == e_safe[:, :, None])
                               & (R_d[:, None, :] < INF), axis=2)
            new = ok & ~in_V & ~in_C & ~in_R & ~dup_here

        # ---- distances for new candidates: ONE fused gather+GEMM+mask
        # block for the whole batch (the per-hop hot spot) --------------
        ed = HP.neighbor_distances(Q, X_score, e_safe, metric=metric,
                                   mask=new, backend=backend,
                                   gather_fused=gather_fused, scales=scales)
        admit = (ed < worst[:, None]) | ~r_full[:, None]   # paper line 17
        ed = jnp.where(admit, ed, INF)

        # ---- push into R: merge candidates, keep ef smallest ----------
        cat_d = jnp.concatenate([R_d, ed], axis=1)
        cat_i = jnp.concatenate([R_ids, jnp.where(ed < INF, e, N)], axis=1)
        R_d3, R_ids3 = HP.rank_merge(cat_d, cat_i, keep=ef, backend=backend)

        # ---- push into C: per-segment insert, evict most distant ------
        with jax.named_scope("c_queue_evict"):
            seg_of_e = _ext_hash(e_safe) % m_seg
            cand_mask = (ed < INF)[:, None, :] \
                & (seg_of_e[:, None, :] == jnp.arange(m_seg)[None, :, None])
            cand_d = jnp.where(cand_mask, ed[:, None, :], INF)  # [B, m, M]
            cand_i = jnp.where(cand_mask, e[:, None, :], N)
            C_d3, C_ids3 = _seg_merge(
                jnp.concatenate([C_d2, cand_d], axis=2),
                jnp.concatenate([C_ids2, cand_i], axis=2), seg, backend)

        R_d4 = jnp.where(now_done[:, None], R_d, R_d3)
        R_ids4 = jnp.where(now_done[:, None], R_ids, R_ids3)
        C_d4 = jnp.where(now_done[:, None, None], C_d, C_d3)
        C_ids4 = jnp.where(now_done[:, None, None], C_ids, C_ids3)
        return (R_ids4, R_d4, C_ids4, C_d4, V2, V_ptr2, now_done), None

    state = (R_ids, R_d, C_ids, C_d, V, V_ptr, jnp.zeros((B,), bool))
    (R_ids, R_d, *_), _ = jax.lax.scan(step, state, None, length=hops,
                                       unroll=unroll)
    if codes is None:
        return _ext(R_ids[:, :k]).astype(jnp.int32), R_d[:, :k]
    # exact fp32 re-rank of the best rerank_mult*k survivors (R is already
    # (dist, id)-sorted and id-deduped, so a prefix slice is the top pool).
    # INF lanes (unfilled R slots carrying sentinel id N) stay masked
    # through the re-score, so they cannot displace real survivors.
    rerank = min(max(rerank_mult, 1) * k, ef)
    rr_ids = R_ids[:, :rerank]       # internal: indexes the packed fp32 rows
    rr_d = R_d[:, :rerank]
    ed = HP.neighbor_distances(Q, X, rr_ids, metric=metric,
                               mask=rr_d < INF, backend=backend,
                               gather_fused=gather_fused)
    # external BEFORE the merge so its (dist, id) tie order matches the
    # unpacked baseline
    out_d, out_ids = HP.rank_merge(ed, _ext(rr_ids), keep=k,
                                   backend=backend)
    return out_ids.astype(jnp.int32), out_d


def large_batch_search(*args, **kwargs):
    """Deprecated public seam — prefer ``repro.ann.Index.search`` (DESIGN.md
    §5), which dispatches to this procedure automatically for large batches.
    Thin shim over :func:`_large_batch_search`; identical results."""
    from repro.utils.deprecation import warn_once
    warn_once("repro.core.search_large.large_batch_search",
              "repro.ann.Index.search")
    return _large_batch_search(*args, **kwargs)
