"""Distributed TSDG: sharded index build + 2-D parallel search (shard_map).

Production layout (DESIGN.md §6): the database (vectors + packed graph) is
sharded over the ``data`` axis (and ``pod`` when multi-pod) — each shard owns
an independent TSDG sub-index over its slice, built with zero cross-shard
traffic (the paper's batched-GPU build, pod-scaled).  Queries are sharded
over the ``model`` axis.  A query visits every DB shard's sub-index in
parallel and the per-shard top-k are merged with one all-gather over the DB
axes — k·shards ids/dists per query, the only collective in the hot path.

This is the standard sharded-ANN serving architecture (sub-linear per-shard
search, embarrassingly parallel scale-out); the paper is single-GPU, so this
layer is our extension for the 1000+-node deployment target.

Determinism contract (new with the execution-plane refactor): every search
row is seeded by its GLOBAL index — the large regime passes each model
column's row offset as ``seed_offset``, the small regime places each
column's slice of the t0 population with ``t0_offset``/``t0_total``.  On a
mesh with a single DB shard the union of the columns' searches is therefore
*exactly* the single-device search population, and the merged answers are
bitwise-identical to the single-device plane (asserted in
``tests/test_mesh_plane.py``).  With several DB shards the per-shard
sub-indexes genuinely differ from a global index, so only recall — not
bitwise identity — is comparable.

The callable returned by :func:`make_search_fn` is consumed by
:class:`repro.serve.plane.MeshPlane`, which owns the mesh, the operand
shardings, and the serving engine integration (AOT cache, donation, stats).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ANNConfig
from repro.core.diversify import PackedGraph
from repro.core.search_large import _large_batch_search
from repro.core.search_small import _small_batch_search

PAD_ID = jnp.int32(-1)
INF = jnp.float32(3.4e38)


def db_axes(mesh: Mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def query_axes(mesh: Mesh) -> tuple:
    return tuple(a for a in ("model",) if a in mesh.axis_names)


def axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def n_db_shards(mesh: Mesh) -> int:
    sizes = axis_sizes(mesh)
    out = 1
    for a in db_axes(mesh):
        out *= sizes[a]
    return out


def n_query_shards(mesh: Mesh) -> int:
    sizes = axis_sizes(mesh)
    out = 1
    for a in query_axes(mesh):
        out *= sizes[a]
    return out


def graph_pspec(mesh: Mesh):
    d = db_axes(mesh)
    return PackedGraph(
        neighbors=P(d, None), lambdas=P(d, None), degrees=P(d),
        hubs=P(None))


def make_build_fn(mesh: Mesh, cfg: ANNConfig):
    """shard_map'd index build: each DB shard builds its own TSDG.

    The "layout" stage (DESIGN.md §10) is a host-side BFS and cannot run
    under the shard_map trace; it is stripped here and applied per shard
    afterwards by :meth:`repro.serve.plane.MeshPlane._host_layout`."""
    d_ax = db_axes(mesh)
    pipeline = tuple(getattr(cfg, "build_pipeline", ()) or ())
    if "layout" in pipeline:
        import dataclasses
        cfg = dataclasses.replace(
            cfg, build_pipeline=tuple(p for p in pipeline if p != "layout"))

    def local_build(X_shard):
        from repro.ann.pipeline import build_graph
        g = build_graph(X_shard, cfg)
        return g.neighbors, g.lambdas, g.degrees, \
            (g.hubs if g.hubs is not None else jnp.zeros((0,), jnp.int32))

    fn = jax.shard_map(
        local_build, mesh=mesh,
        in_specs=(P(d_ax, None),),
        out_specs=(P(d_ax, None), P(d_ax, None), P(d_ax), P(d_ax)),
        check_vma=False)
    return jax.jit(fn)


def merge_topk(all_ids, all_d, k: int):
    """Dedup-top-k merge of per-shard candidate lists — THE cross-shard
    collective's reduction, extracted so it is testable against an
    explicit-set oracle (``tests/test_mesh_plane.py``).

    ``all_ids`` [B, n_cand] carries *global* ids with ``PAD_ID`` (-1) for
    invalid lanes, ``all_d`` the matching distances (PAD lanes hold INF).
    Different searches (other shards, other t0 columns) may surface the same
    global id; duplicates must occupy exactly ONE output slot, keeping the
    best (equal-valued — same query, same vector, same arithmetic) copy.

    Returns (ids [B, k], dists [B, k]) ascending by distance; rows with
    fewer than k distinct valid candidates are padded with (PAD_ID, INF).
    This is also the streaming base+delta fuse (DESIGN.md §7), where the
    edge cases are routine rather than exotic: pools narrower than ``k``
    (tiny delta shard), rows whose candidates are ALL invalid (every shard
    tombstoned), and the same id surfacing from several pools.  Any
    negative id — not just ``PAD_ID`` — counts as invalid, and invalid
    lanes are INF-demoted *before* the top-k so they can never shadow a
    real candidate (oracle-fuzzed in ``tests/test_streaming.py``).
    """
    if k <= 0:
        raise ValueError(f"k must be >= 1, got {k}")
    if k > all_ids.shape[1]:  # fewer candidates than k: pad the pool
        pad = k - all_ids.shape[1]
        all_ids = jnp.pad(all_ids, ((0, 0), (0, pad)),
                          constant_values=PAD_ID)
        all_d = jnp.pad(all_d, ((0, 0), (0, pad)), constant_values=INF)
    # (id, dist)-lexsorted so the dedup keeps the BEST copy of each id
    # (mirrors the single-device t0-merge in search_small; a plain stable
    # id-sort would keep whichever copy arrived first)
    o = jnp.lexsort((all_d, all_ids), axis=1)
    sid = jnp.take_along_axis(all_ids, o, axis=1)
    sd = jnp.take_along_axis(all_d, o, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((sid.shape[0], 1), bool),
         sid[:, 1:] == sid[:, :-1]], axis=1)
    sd = jnp.where(dup | (sid < 0), INF, sd)
    neg, pos = jax.lax.top_k(-sd, k)
    out_ids = jnp.take_along_axis(sid, pos, axis=1)
    return jnp.where(-neg < INF, out_ids, PAD_ID), -neg


def _pool_merge(ids, dists, offsets, n_rows, k: int):
    """jit body of :func:`merge_shard_results`: stacked per-shard pools
    [P, B, k] -> merged global (ids, dists) [B, k]."""
    valid = (ids >= 0) & (ids < n_rows[:, None, None]) & (dists < INF)
    gids = jnp.where(valid, ids + offsets[:, None, None], PAD_ID)
    gd = jnp.where(valid, dists, INF)
    # shard-major column order, exactly the mesh plane's all_gather layout
    all_ids = jnp.moveaxis(gids, 0, 1).reshape(gids.shape[1], -1)
    all_d = jnp.moveaxis(gd, 0, 1).reshape(gd.shape[1], -1)
    return merge_topk(all_ids, all_d, k)


def merge_shard_results(results, offsets, n_rows, *, k: int,
                        batch: int | None = None):
    """Host-side counterpart of the mesh plane's cross-shard merge, used by
    the request router's sharded mode (:mod:`repro.serve.router`).

    ``results`` is one (ids [B, k'], dists [B, k']) pair per surviving
    shard — shard-LOCAL ids from independent single-device engines.  Each
    shard's ids are offset by its global row start (``offsets``) after
    masking invalid lanes (negative / ``>= n_rows[i]`` sentinel ids, INF
    distances — the same validity rule the streaming fuse applies), then
    the pools are concatenated shard-major and reduced with
    :func:`merge_topk` — so a router over P equal row slices answers
    bitwise-identically to a P-DB-shard mesh plane.

    ``batch`` sizes the all-PAD answer when ``results`` is empty (every
    shard failed); otherwise it is inferred.  Returns numpy arrays.
    """
    import numpy as np
    if not results:
        if batch is None:
            raise ValueError("batch= is required when no shard survived")
        return (np.full((batch, k), int(PAD_ID), np.int32),
                np.full((batch, k), float(INF), np.float32))
    ids = jnp.stack([jnp.asarray(i) for i, _ in results])
    dists = jnp.stack([jnp.asarray(d) for _, d in results])
    gi, gd = jax.jit(_pool_merge, static_argnums=(4,))(
        ids, dists,
        jnp.asarray(list(offsets), jnp.int32),
        jnp.asarray(list(n_rows), jnp.int32), k)
    return np.asarray(gi), np.asarray(gd)


def make_search_fn(mesh: Mesh, cfg: ANNConfig, *, kind: str = "large",
                   k: int = 10, batch: int | None = None,
                   stream: bool = False):
    """Returns jit(search)(X, neighbors, lambdas, degrees, hubs, Q) ->
    (global ids [B, k], dists [B, k]).

    Layouts mirror the paper's two regimes:
      * large batch — queries sharded over `model` (one best-first search
        per query, thousands in flight), DB sharded over `data`(+`pod`);
        each column seeds its rows by GLOBAL batch index (`seed_offset`),
        so column placement is bit-invisible;
      * small batch — queries REPLICATED; the paper's `t0` independent
        greedy searches are split across the `model` axis (that is the
        small-batch parallelism unit, §4.1) via `t0_offset`/`t0_total`
        global placement, results merged with the same dedup-top-k that
        merges the DB shards.

    ``stream=True`` is the mutable-index form (DESIGN.md §7): the callable
    takes three extra operands before Q — ``alive`` ([N] bool, row-sharded
    like ``degrees``: the tombstone mask over the base corpus, threaded
    into each shard's in-kernel keep-mask) and the replicated delta shard
    ``delta_X`` [cap, d] / ``delta_alive`` [cap].  Every shard scores the
    delta brute-force (``hotpath.scan_distances``) against its own query
    slice and splices the candidates — at global ids ``N_total + slot`` —
    into the same dedup-top-k that merges the DB shards, so base+delta
    fusion is bitwise the single-device streaming path's merge.
    """
    d_ax = db_axes(mesh)
    q_ax = query_axes(mesh)
    sizes = axis_sizes(mesh)
    n_db = n_db_shards(mesh)
    n_q = n_query_shards(mesh)
    unroll = getattr(cfg, "unroll_scans", False)
    backend = getattr(cfg, "kernel_backend", "auto")
    gather_fused = getattr(cfg, "gather_fused", None)
    quantized = getattr(cfg, "quantization", "none") == "int8"
    rerank_mult = getattr(cfg, "rerank_mult", 4)
    visited = getattr(cfg, "visited_filter", "none")
    has_layout = "layout" in tuple(getattr(cfg, "build_pipeline", ()) or ())

    def local_search(X_s, nbrs_s, lams_s, degs_s, hubs_s, *rest):
        rest = list(rest)
        codes_s = scales_s = None
        if quantized:  # row-sharded codes ride right after the fp32 parts
            codes_s, scales_s = rest[0], rest[1]
            rest = rest[2:]
        perm_s = None
        if has_layout:  # shard-local locality perm rides after the codes
            perm_s = rest[0]
            rest = rest[1:]
        d_codes = d_scales = None
        if stream:
            alive_s, delta_X, delta_alive = rest[0], rest[1], rest[2]
            rest = rest[3:]
            if quantized:
                d_codes, d_scales = rest[0], rest[1]
                rest = rest[2:]
        else:
            alive_s, delta_X, delta_alive = None, None, None
        (Q_s,) = rest
        n_local = X_s.shape[0]
        quant_kw = dict(codes=codes_s, scales=scales_s,
                        rerank_mult=rerank_mult) if quantized else {}
        if getattr(cfg, "db_bf16", False):  # beyond-paper: bf16 database
            X_s = X_s.astype(jnp.bfloat16)
        graph = PackedGraph(neighbors=nbrs_s, lambdas=lams_s,
                            degrees=degs_s,
                            hubs=hubs_s if hubs_s.shape[0] else None,
                            perm=perm_s)
        # shard index along the DB axes -> global id offset
        idx = 0
        for a in d_ax:
            idx = idx * sizes[a] + jax.lax.axis_index(a)
        offset = (idx * n_local).astype(jnp.int32)
        # query-shard index along the model axes -> global row / t0 offset
        q_idx = 0
        for a in q_ax:
            q_idx = q_idx * sizes[a] + jax.lax.axis_index(a)
        if kind == "small":
            # this model-column runs its slice of the t0 searches, placed at
            # its GLOBAL position inside the population so the union over
            # columns reproduces the single-device searches exactly
            t0_local = max(1, cfg.small_t0 // max(1, n_q))
            ids, dist = _small_batch_search(
                X_s, graph, Q_s, k=k, t0=t0_local, hops=cfg.small_hops,
                hop_width=cfg.hop_width, n_seeds=cfg.n_seeds,
                lambda_limit=10, metric=cfg.metric, unroll=unroll,
                t0_offset=q_idx * t0_local, t0_total=t0_local * n_q,
                alive=alive_s, visited=visited,
                backend=backend, gather_fused=gather_fused, **quant_kw)
        else:
            ids, dist = _large_batch_search(
                X_s, graph, Q_s, k=k, ef=cfg.large_ef, hops=cfg.large_hops,
                lambda_limit=5, metric=cfg.metric,
                n_seeds=getattr(cfg, "large_n_seeds", cfg.n_seeds),
                m_seg=cfg.queue_segments, seg=cfg.segment_size,
                mv_seg=cfg.visited_segments, delta=cfg.delta,
                seed_offset=q_idx * Q_s.shape[0],
                unroll=unroll,
                gather_limit=getattr(cfg, "gather_limit", 0),
                exact_visited=getattr(cfg, "exact_visited", False),
                alive=alive_s, visited=visited,
                backend=backend, gather_fused=gather_fused, **quant_kw)
        gids = jnp.where(ids < n_local, ids + offset, PAD_ID)
        dist = jnp.where(ids < n_local, dist, INF)
        # merge across DB shards (and search shards in the small regime)
        merge_ax = d_ax + q_ax if kind == "small" else d_ax
        n_merge = n_db * (n_q if kind == "small" else 1)
        all_ids = jax.lax.all_gather(gids, merge_ax, tiled=False)
        all_d = jax.lax.all_gather(dist, merge_ax, tiled=False)
        all_ids = jnp.moveaxis(all_ids.reshape(n_merge, *gids.shape),
                               0, 1).reshape(gids.shape[0], -1)
        all_d = jnp.moveaxis(all_d.reshape(n_merge, *dist.shape),
                             0, 1).reshape(dist.shape[0], -1)
        if stream:
            # delta shard: replicated, scored once per shard against this
            # shard's own query slice; global ids start past the base rows
            from repro.core import hotpath as HP
            cap = delta_X.shape[0]
            n_total = n_local * n_db
            if quantized:
                # approx scan over int8 delta codes, then exact fp32
                # re-rank of the surviving slots — bitwise the single
                # plane's quantized delta pipeline (replicated operands,
                # so every shard computes identical candidates)
                dd = HP.scan_distances(Q_s, d_codes, metric=cfg.metric,
                                       mask=delta_alive, backend=backend,
                                       scales=d_scales)
                r = min(max(rerank_mult, 1) * k, cap)
                slots = jnp.broadcast_to(
                    jnp.arange(cap, dtype=jnp.int32)[None], dd.shape)
                sd, ss = HP.rank_merge(dd, slots, keep=r, backend=backend)
                ed = HP.neighbor_distances(
                    Q_s, delta_X, ss, metric=cfg.metric, mask=sd < INF,
                    backend=backend, gather_fused=gather_fused)
                d_gids = jnp.where(ed < INF, n_total + ss, PAD_ID)
                all_ids = jnp.concatenate([all_ids, d_gids], axis=1)
                all_d = jnp.concatenate([all_d, ed], axis=1)
            else:
                dd = HP.scan_distances(Q_s, delta_X, metric=cfg.metric,
                                       mask=delta_alive, backend=backend)
                d_gids = jnp.where(
                    delta_alive,
                    n_total + jnp.arange(cap, dtype=jnp.int32), PAD_ID)
                all_ids = jnp.concatenate(
                    [all_ids, jnp.broadcast_to(d_gids[None], dd.shape)],
                    axis=1)
                all_d = jnp.concatenate(
                    [all_d, jnp.where(delta_alive[None], dd, INF)], axis=1)
        return merge_topk(all_ids, all_d, k)

    q_spec = P(None, None) if kind == "small" else P(q_ax, None)
    out_spec = P(None, None) if kind == "small" else P(q_ax, None)
    in_specs = (P(d_ax, None), P(d_ax, None), P(d_ax, None), P(d_ax),
                P(d_ax))
    if quantized:  # row-sharded int8 codes + per-row scales
        in_specs = in_specs + (P(d_ax, None), P(d_ax))
    if has_layout:  # shard-local locality perm, row-sharded
        in_specs = in_specs + (P(d_ax),)
    if stream:
        in_specs = in_specs + (P(d_ax), P(None, None), P(None))
        if quantized:  # replicated delta codes + scales
            in_specs = in_specs + (P(None, None), P(None))
    fn = jax.shard_map(
        local_search, mesh=mesh,
        in_specs=in_specs + (q_spec,),
        out_specs=(out_spec, out_spec),
        check_vma=False)
    return jax.jit(fn)
