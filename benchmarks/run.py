"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (derived = the figure's quality
axis: recall / avg-degree / dominant roofline term).  Sizes are scaled to
CPU (the TPU target numbers come from the dry-run roofline artifacts, which
`roofline_table` re-emits at the end).

  PYTHONPATH=src python -m benchmarks.run            # full
  REPRO_BENCH_QUICK=1 PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

QUICK = bool(os.environ.get("REPRO_BENCH_QUICK"))

ROWS: list = []


def emit(name: str, us: float, derived: str):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}", flush=True)


def _timeit(fn, *args, repeat: int = 3):
    fn(*args)  # compile / warm
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeat * 1e6, out


def _dataset(n=None, d=32, nq=128):
    from repro.data.synthetic import make_clustered

    n = n or (4000 if QUICK else 20000)
    return make_clustered(n=n, d=d, n_queries=nq, n_clusters=64, noise=0.6,
                          seed=0)


def _cfg(**kw):
    from repro.configs import get_arch

    base = dict(k_graph=24, max_degree=32, lambda0=8, bridge_hubs=128,
                bridge_k=8)
    base.update(kw)
    return dataclasses.replace(get_arch("tsdg-paper"), **base)


# ==========================================================================
# Table 2: graph diversification time
# ==========================================================================

def table2_diversification_time():
    from repro.ann.pipeline import build_graph
    from repro.core.diversify import (append_reverse, build_gd_baseline,
                                      relaxed_gd, soft_gd)
    from repro.core.knn_build import exact_knn

    ds = _dataset()
    X = jnp.asarray(ds.X)
    ids, dists = exact_knn(X, 24)
    jax.block_until_ready(ids)

    cfg = _cfg()

    def tsdg():
        g = build_graph(X, cfg, knn_ids=ids, knn_dists=dists)
        jax.block_until_ready(g.neighbors)
        return g

    def gd():
        g = build_gd_baseline(X, cfg, knn_ids=ids, knn_dists=dists)
        jax.block_until_ready(g.neighbors)
        return g

    def soft_only():  # DPG-like: stage 2 applied directly to the k-NN graph
        adj_i, adj_d = append_reverse(X, ids, dists,
                                      jnp.ones(ids.shape, bool),
                                      rev_cap=24, metric="l2")
        out = soft_gd(X, adj_i, adj_d, lambda0=cfg.lambda0,
                      max_degree=cfg.max_degree, metric="l2")
        jax.block_until_ready(out[0])
        return out

    us, g = _timeit(tsdg)
    emit("table2/tsdg_build", us, f"avg_degree={g.avg_degree():.1f}")
    us, g2 = _timeit(gd)
    emit("table2/gd_build", us, f"avg_degree={g2.avg_degree():.1f}")
    us, _ = _timeit(soft_only)
    emit("table2/softonly_build_dpg_like", us, "stage2_only")


# ==========================================================================
# Fig 4: CPU search (reference best-first) recall vs throughput
# ==========================================================================

def fig4_cpu_search():
    from repro.core import search_ref
    from repro.ann.pipeline import build_graph
    from repro.core.diversify import build_gd_baseline
    from repro.core.knn_build import exact_knn
    from repro.data.synthetic import recall_at_k

    ds = _dataset(n=3000 if QUICK else 8000, nq=32)
    X = jnp.asarray(ds.X)
    ids, dists = exact_knn(X, 24)
    cfg = _cfg()
    graphs = {
        "tsdg": build_graph(X, cfg, knn_ids=ids, knn_dists=dists),
        "gd": build_gd_baseline(X, cfg, knn_ids=ids, knn_dists=dists),
    }
    for name, g in graphs.items():
        for ef in ((32,) if QUICK else (32, 64, 128)):
            t0 = time.perf_counter()
            out, _ = search_ref.search_batch(ds.X, g, ds.Q, k=10, ef=ef)
            dt = time.perf_counter() - t0
            r = recall_at_k(out, ds.gt, 10)
            emit(f"fig4/cpu_{name}_ef{ef}", dt / len(ds.Q) * 1e6,
                 f"recall@10={r:.3f}")


# ==========================================================================
# Fig 5: degree / λ-limit sweep (one graph, many operating points)
# ==========================================================================

def fig5_degree_sweep():
    from repro.ann.pipeline import build_graph
    from repro.core.knn_build import exact_knn
    from repro.core.search_small import \
        _small_batch_search as small_batch_search
    from repro.data.synthetic import recall_at_k

    ds = _dataset(nq=64)
    X = jnp.asarray(ds.X)
    ids, dists = exact_knn(X, 24)
    g = build_graph(X, _cfg(), knn_ids=ids, knn_dists=dists)
    Q = jnp.asarray(ds.Q)
    for lam_limit in (2, 5, 10):
        fn = lambda: small_batch_search(X, g, Q, k=10, t0=16, hops=6,
                                        lambda_limit=lam_limit)[0]
        us, out = _timeit(fn)
        r = recall_at_k(np.asarray(out), ds.gt, 10)
        emit(f"fig5/lambda_limit_{lam_limit}", us / len(ds.Q),
             f"recall@10={r:.3f}")


# ==========================================================================
# Figs 6-9: small-batch search on accelerator (batch 1 / 10 / 100)
# ==========================================================================

def fig6_small_batch():
    from repro.ann.pipeline import build_graph
    from repro.core.knn_build import exact_knn
    from repro.core.search_small import \
        _small_batch_search as small_batch_search
    from repro.data.synthetic import recall_at_k

    ds = _dataset(nq=100)
    X = jnp.asarray(ds.X)
    ids, dists = exact_knn(X, 24)
    g = build_graph(X, _cfg(), knn_ids=ids, knn_dists=dists)
    for B in ((1, 10) if QUICK else (1, 10, 100)):
        Q = jnp.asarray(ds.Q[:B])
        gt = ds.gt[:B]
        for k in (10, 100):
            fn = lambda: small_batch_search(X, g, Q, k=k, t0=32, hops=6)[0]
            us, out = _timeit(fn)
            r = recall_at_k(np.asarray(out), ds.gt[:B], k)
            emit(f"fig6-9/small_bs{B}_k{k}", us / B, f"recall@{k}={r:.3f}")


# ==========================================================================
# Figs 10-11: large-batch search (scaled 10k regime)
# ==========================================================================

def fig10_large_batch():
    from repro.ann.pipeline import build_graph
    from repro.core.knn_build import exact_knn
    from repro.core.search_large import \
        _large_batch_search as large_batch_search
    from repro.data.synthetic import make_clustered, recall_at_k

    ds = make_clustered(n=4000 if QUICK else 20000, d=32,
                        n_queries=256 if QUICK else 1024, n_clusters=64,
                        noise=0.6, seed=0)
    X = jnp.asarray(ds.X)
    ids, dists = exact_knn(X, 24)
    g = build_graph(X, _cfg(), knn_ids=ids, knn_dists=dists)
    Q = jnp.asarray(ds.Q)
    for k, ef, ns in ((10, 64, 32), (10, 64, 128), (100, 128, 128)):
        fn = lambda: large_batch_search(X, g, Q, k=k, ef=ef, hops=128,
                                        lambda_limit=5, n_seeds=ns)[0]
        us, out = _timeit(fn, repeat=2)
        r = recall_at_k(np.asarray(out), ds.gt, k)
        emit(f"fig10-11/large_bs{Q.shape[0]}_k{k}_seeds{ns}",
             us / Q.shape[0], f"recall@{k}={r:.3f}")


# ==========================================================================
# ablations: the paper's two diversification knobs (α, λ0)
# ==========================================================================

def ablation_alpha_lambda():
    from repro.ann.pipeline import build_graph
    from repro.core.knn_build import exact_knn
    from repro.core.search_large import \
        _large_batch_search as large_batch_search
    from repro.data.synthetic import recall_at_k

    ds = _dataset(n=3000 if QUICK else 8000, nq=64)
    X = jnp.asarray(ds.X)
    ids, dists = exact_knn(X, 24)
    Q = jnp.asarray(ds.Q)
    for alpha in ((1.0, 1.2) if QUICK else (1.0, 1.1, 1.2, 1.4)):
        cfg = _cfg(alpha=alpha)
        g = build_graph(X, cfg, knn_ids=ids, knn_dists=dists)
        out, _ = large_batch_search(X, g, Q, k=10, ef=64, hops=96)
        r = recall_at_k(np.asarray(out), ds.gt, 10)
        emit(f"ablation/alpha_{alpha}", 0.0,
             f"avg_degree={g.avg_degree():.1f};recall@10={r:.3f}")
    for lam0 in ((2, 8) if QUICK else (0, 2, 8, 16)):
        cfg = _cfg(lambda0=lam0)
        g = build_graph(X, cfg, knn_ids=ids, knn_dists=dists)
        out, _ = large_batch_search(X, g, Q, k=10, ef=64, hops=96)
        r = recall_at_k(np.asarray(out), ds.gt, 10)
        emit(f"ablation/lambda0_{lam0}", 0.0,
             f"avg_degree={g.avg_degree():.1f};recall@10={r:.3f}")


# ==========================================================================
# serving engine: regime dispatch end-to-end
# ==========================================================================

def serve_engine_mixed():
    from repro.ann import Index
    from repro.data.synthetic import recall_at_k

    ds = _dataset(nq=128)
    eng = Index.build(ds.X, _cfg(), k=10)
    rng = np.random.default_rng(0)
    hits, total = 0.0, 0
    t0 = time.perf_counter()
    for _ in range(4 if QUICK else 12):
        B = int(rng.choice([1, 4, 16, 128]))
        sel = rng.integers(0, len(ds.Q), B)
        ids, _ = eng.search(ds.Q[sel])
        hits += recall_at_k(ids, ds.gt[sel], 10) * B
        total += B
    dt = time.perf_counter() - t0
    emit("serve/mixed_batches", dt / total * 1e6,
         f"recall@10={hits / total:.3f};small={eng.stats.small_batches};"
         f"large={eng.stats.large_batches}")


def serve_bucketed_vs_raw():
    """Mixed-batch-size stream: shape-bucketed engine (compiles once per
    (regime, bucket), steady state never re-traces) vs calling the search
    kernels directly on raw shapes (every distinct B re-traces/compiles)."""
    from repro.core.search_large import \
        _large_batch_search as large_batch_search
    from repro.core.search_small import \
        _small_batch_search as small_batch_search
    from repro.ann import Index

    ds = _dataset(nq=600)
    cfg = _cfg(serve_buckets=(8, 32, 128, 512),
               large_hops=32 if QUICK else 64)
    eng = Index.build(ds.X, cfg, k=10)
    X, graph = eng.X, eng.graph
    rng = np.random.default_rng(0)
    # bursty traffic over many *distinct* batch sizes — the serving reality
    # the bucket ladder exists for
    sizes = [1, 7, 33, 100, 513] if not QUICK else [1, 7, 33]
    stream = []
    for rep in range(3 if QUICK else 6):
        for B in sizes:
            B_jit = min(max(1, B + int(rng.integers(-3, 4))), len(ds.Q))
            stream.append(rng.integers(0, len(ds.Q), B_jit))

    def raw_call(Q):
        Q = jnp.asarray(Q)
        if eng.regime(Q.shape[0]) == "small":
            out = small_batch_search(
                X, graph, Q, k=10, t0=cfg.small_t0, hops=cfg.small_hops,
                hop_width=cfg.hop_width, n_seeds=cfg.n_seeds,
                lambda_limit=10, metric=cfg.metric)
        else:
            out = large_batch_search(
                X, graph, Q, k=10, ef=cfg.large_ef, hops=cfg.large_hops,
                lambda_limit=5, metric=cfg.metric, n_seeds=cfg.large_n_seeds,
                m_seg=cfg.queue_segments, seg=cfg.segment_size,
                mv_seg=cfg.visited_segments, delta=cfg.delta)
        jax.block_until_ready(out[0])
        return out

    # raw path: each distinct (regime, B) pays its own trace+compile
    t0 = time.perf_counter()
    n_raw = 0
    for sel in stream:
        raw_call(ds.Q[sel])
        n_raw += len(sel)
    raw_us = (time.perf_counter() - t0) / n_raw * 1e6
    emit("serve/raw_shapes_stream", raw_us,
         f"distinct_shapes={len({len(s) for s in stream})}")

    # bucketed engine: same stream; steady-state excludes the few warmups
    for sel in stream:
        eng.search(ds.Q[sel])
    st = eng.stats
    eng_us = 1e6 / max(st.qps, 1e-9)
    emit("serve/bucketed_engine_steady", eng_us,
         f"compiles={st.compiles};hit_rate={st.bucket_hit_rate:.2f};"
         f"speedup_vs_raw={raw_us / max(eng_us, 1e-9):.1f}x")


def serve_aot_reload():
    """Cold start vs artifact restart: warmup compile sweep from scratch
    against Index.load priming the persisted AOT executables (zero
    compiles).  The row value is the restart's time-to-first-steady-query."""
    import shutil
    import tempfile

    from repro.ann import Index

    ds = _dataset(n=2000 if QUICK else 6000, nq=32)
    cfg = _cfg(serve_buckets=(8, 32), large_hops=16 if QUICK else 32)
    index = Index.build(ds.X, cfg, k=10)
    t0 = time.perf_counter()
    n_cold = index.warmup()
    cold_s = time.perf_counter() - t0
    emit("serve/cold_warmup_sweep", cold_s * 1e6, f"compiles={n_cold}")

    td = tempfile.mkdtemp(prefix="repro_aot_bench_")
    try:
        index.save(td)
        t0 = time.perf_counter()
        loaded = Index.load(td)
        loaded.search(ds.Q[:4])           # first real query, steady-state
        warm_s = time.perf_counter() - t0
        emit("serve/aot_reload_first_query", warm_s * 1e6,
             f"compiles={loaded.stats.compiles};"
             f"aot_primed={loaded.stats.aot_primed};"
             f"speedup_vs_cold={cold_s / max(warm_s, 1e-9):.1f}x")
    finally:
        shutil.rmtree(td, ignore_errors=True)


def streaming_ingest():
    """Streaming mutability (DESIGN.md §7) vs the frozen baseline.

    Four serving phases over the same corpus and query stream, each row
    reporting steady QPS + p50/p99 batch latency:

    * ``frozen``  — the untouched generation-0 index (baseline);
    * ``add_heavy``    — interleaved add / search (delta brute-force fused
      into every answer);
    * ``delete_heavy`` — interleaved delete / search (tombstone mask
      threaded through the kernels);
    * ``compact_concurrent`` — searches racing a background compaction,
      timed across the generation hot-swap (the row's derived field shows
      compiles across the swap — 0 when shapes are preserved).
    """
    import threading

    from repro.ann import Index

    ds = _dataset(n=2000 if QUICK else 6000, nq=128)
    cfg = _cfg(serve_buckets=(8, 32), large_hops=16 if QUICK else 32,
               delta_min_cap=256)
    B, reps = 8, (6 if QUICK else 20)
    rng = np.random.default_rng(0)

    def _phase(index, mutate=None):
        lat = []
        index.search(ds.Q[:B])                       # warm / compile
        for r in range(reps):
            if mutate is not None:
                mutate(r)
            sel = rng.integers(0, len(ds.Q), B)
            t0 = time.perf_counter()
            index.search(ds.Q[sel])
            lat.append(time.perf_counter() - t0)
        lat = np.asarray(lat)
        qps = B / max(float(lat.mean()), 1e-9)
        return qps, float(np.percentile(lat, 50)) * 1e3, \
            float(np.percentile(lat, 99)) * 1e3

    index = Index.build(ds.X, cfg, k=10)
    qps, p50, p99 = _phase(index)
    emit("streaming/frozen_baseline", 1e6 / qps,
         f"qps={qps:.0f};p50_ms={p50:.2f};p99_ms={p99:.2f}")

    qps, p50, p99 = _phase(index, mutate=lambda r: index.add(
        ds.Q[rng.integers(0, len(ds.Q), 4)]))
    emit("streaming/add_heavy", 1e6 / qps,
         f"qps={qps:.0f};p50_ms={p50:.2f};p99_ms={p99:.2f};"
         f"n_added={index.stats.n_added}")

    added = index.stats.n_added
    victims = iter(range(ds.X.shape[0], ds.X.shape[0] + added))
    qps, p50, p99 = _phase(index, mutate=lambda r: index.delete(
        [next(victims), next(victims)]))
    emit("streaming/delete_heavy", 1e6 / qps,
         f"qps={qps:.0f};p50_ms={p50:.2f};p99_ms={p99:.2f};"
         f"n_deleted={index.stats.n_deleted}")

    compiles_before = index.stats.compiles
    bg = threading.Thread(target=index.compact, daemon=True)
    bg.start()
    qps, p50, p99 = _phase(index)
    bg.join(timeout=600)
    emit("streaming/compact_concurrent", 1e6 / qps,
         f"qps={qps:.0f};p50_ms={p50:.2f};p99_ms={p99:.2f};"
         f"generation={index.stats.generation};"
         f"swap_compiles={index.stats.compiles - compiles_before}")


# ==========================================================================
# mesh execution plane: single-device vs 2/4/8-shard host meshes
# ==========================================================================

def _steady_us(index, Q, B, repeat=3):
    """Per-query steady-state latency for batch B via the engine cache
    (first call may compile; timed calls are all bucket hits)."""
    index.search(Q[:B])                      # warm / compile
    t0 = time.perf_counter()
    for _ in range(repeat):
        index.search(Q[:B])
    return (time.perf_counter() - t0) / (repeat * B) * 1e6


def mesh_serve():
    """Both regimes served through the mesh plane at 2/4/8 DB shards vs the
    single-device plane — same engine machinery (buckets, AOT cache,
    stats), only the execution plane differs.  Requires a multi-device
    process (CI runs this tier under
    XLA_FLAGS=--xla_force_host_platform_device_count=8); with fewer
    devices the missing rows are emitted as skips, never silently
    dropped."""
    from repro.ann import Index
    from repro.data.synthetic import recall_at_k

    ds = _dataset(n=4096 if QUICK else 16384, d=32, nq=256)
    cfg = _cfg(serve_buckets=(8, 64, 256),
               large_hops=32 if QUICK else 64)
    B_small, B_large = 8, 256
    single = Index.build(ds.X, cfg, k=10)
    for regime, B in (("small", B_small), ("large", B_large)):
        us = _steady_us(single, ds.Q, B)
        r = recall_at_k(single.search(ds.Q[:B])[0], ds.gt[:B], 10)
        emit(f"mesh_serve/single_{regime}_B{B}", us,
             f"plane=single;recall@10={r:.3f}")
    for shards in (2, 4, 8):
        if jax.device_count() < shards:
            emit(f"mesh_serve/shards{shards}_SKIPPED", 0.0,
                 f"needs {shards} devices, have {jax.device_count()} "
                 "(set XLA_FLAGS=--xla_force_host_platform_device_count)")
            continue
        mesh = jax.make_mesh((shards,), ("data",))
        mi = Index.build(ds.X, cfg, k=10, mesh=mesh)
        for regime, B in (("small", B_small), ("large", B_large)):
            us = _steady_us(mi, ds.Q, B)
            r = recall_at_k(mi.search(ds.Q[:B])[0], ds.gt[:B], 10)
            emit(f"mesh_serve/shards{shards}_{regime}_B{B}", us,
                 f"plane=mesh;db_shards={shards};recall@10={r:.3f};"
                 f"compiles={mi.stats.compiles}")


def router_serve():
    """Concurrent serving throughput through the request router (DESIGN.md
    §9): a replicated router (N queues, shared plane + compile cache) vs
    one micro-batcher, and the sharded router's fan-out + host merge vs
    the in-collective mesh merge it mirrors."""
    from concurrent.futures import wait

    from repro.ann import Index
    from repro.serve.router import RouterConfig

    ds = _dataset(n=4096 if QUICK else 16384, d=32, nq=256)
    cfg = _cfg(serve_buckets=(8, 64), large_hops=32 if QUICK else 64)
    idx = Index.build(ds.X, cfg, k=10)
    idx.warmup()
    n_req = 64 if QUICK else 256

    def pump(front):
        futs = [front.submit(ds.Q[i % ds.Q.shape[0]]) for i in range(n_req)]
        wait(futs, timeout=600)
        return [f.result() for f in futs]

    with idx.serve(max_wait_ms=1.0) as mb:
        pump(mb)  # warm
        t0 = time.perf_counter()
        pump(mb)
        us = (time.perf_counter() - t0) / n_req * 1e6
    emit("router_serve/queue_1x", us, "front=microbatcher")

    for n in (2, 4):
        rc = RouterConfig(mode="replicated", replicas=n,
                          health_interval_s=0.0)
        with idx.serve(router=rc, max_wait_ms=1.0) as r:
            pump(r)  # warm
            t0 = time.perf_counter()
            pump(r)
            us = (time.perf_counter() - t0) / n_req * 1e6
            agg = r.snapshot()["aggregate"]
        emit(f"router_serve/replicated_{n}x", us,
             f"compiles={agg['compiles']};qps={agg['qps']:.0f}")

    rc = RouterConfig(mode="sharded", replicas=2, health_interval_s=0.0)
    with idx.serve(router=rc, max_wait_ms=1.0) as r:
        pump(r)  # warm
        t0 = time.perf_counter()
        pump(r)
        us = (time.perf_counter() - t0) / n_req * 1e6
    emit("router_serve/sharded_2x", us, "merge=host;shards=2")


def mesh_aot_reload():
    """Sharded cold start vs sharded artifact restart: the mesh plane's
    warmup compile sweep from scratch against Index.load(mesh=) priming
    the persisted shard-mapped executables.  The derived column asserts
    the acceptance criterion: compiles == 0 after a sharded load."""
    import shutil
    import tempfile

    from repro.ann import Index

    shards = 2
    if jax.device_count() < shards:
        emit("mesh_serve/aot_reload_SKIPPED", 0.0,
             f"needs {shards} devices, have {jax.device_count()}")
        return
    ds = _dataset(n=2048 if QUICK else 8192, d=32, nq=64)
    cfg = _cfg(serve_buckets=(8, 64), large_hops=16 if QUICK else 32)
    mesh = jax.make_mesh((shards, 1), ("data", "model"))
    index = Index.build(ds.X, cfg, k=10, mesh=mesh)
    t0 = time.perf_counter()
    n_cold = index.warmup()
    cold_s = time.perf_counter() - t0
    emit("mesh_serve/cold_warmup_sweep", cold_s * 1e6,
         f"compiles={n_cold};db_shards={shards}")
    td = tempfile.mkdtemp(prefix="repro_mesh_aot_bench_")
    try:
        index.save(td)
        t0 = time.perf_counter()
        loaded = Index.load(td, mesh=mesh)
        loaded.search(ds.Q[:4])          # first real query, steady-state
        warm_s = time.perf_counter() - t0
        assert loaded.stats.compiles == 0, loaded.stats.compiles
        emit("mesh_serve/aot_reload_first_query", warm_s * 1e6,
             f"compiles={loaded.stats.compiles};"
             f"aot_primed={loaded.stats.aot_primed};"
             f"speedup_vs_cold={cold_s / max(warm_s, 1e-9):.1f}x")
    finally:
        shutil.rmtree(td, ignore_errors=True)


# ==========================================================================
# compressed residency: int8 scoring vs fp32, recall gate (DESIGN.md §8)
# ==========================================================================

def quantization_recall():
    """fp32 vs int8-resident scoring through the serving engine, both
    regimes, with (rerank_mult=4) and without (rerank_mult=1) the exact
    fp32 re-rank.  Rows report steady per-query latency + recall@10; the
    analytic row restates the residency win (bytes DMA'd per candidate
    tile at the paper's d=960 shape, itemsize 4 vs 1).

    This bench is also the regression gate the CI quick tier runs: if the
    re-ranked int8 recall@10 drops more than 0.01 below fp32 in either
    regime, the process exits non-zero (SystemExit deliberately bypasses
    the harness's per-bench try/except)."""
    from repro.ann import Index
    from repro.data.synthetic import recall_at_k
    from repro.kernels.l2dist import _gather_tile_bytes

    ds = _dataset(n=4000 if QUICK else 12000, nq=256)
    cfg = _cfg(serve_buckets=(8, 64, 256), large_hops=32 if QUICK else 64)
    B_small, B_large = 8, 256
    recalls: dict = {}
    variants = [("fp32", dict(quantization="none")),
                ("int8_rerank", dict(quantization="int8", rerank_mult=4)),
                ("int8_raw", dict(quantization="int8", rerank_mult=1))]
    for name, kw in variants:
        index = Index.build(ds.X, dataclasses.replace(cfg, **kw), k=10)
        for regime, B in (("small", B_small), ("large", B_large)):
            us = _steady_us(index, ds.Q, B)
            r = recall_at_k(index.search(ds.Q[:B])[0], ds.gt[:B], 10)
            recalls[(name, regime)] = r
            emit(f"quantization/{name}_{regime}_B{B}", us,
                 f"recall@10={r:.3f}")
    d960 = _gather_tile_bytes(1, 1024, 960, self_q=False, itemsize=4) / \
        _gather_tile_bytes(1, 1024, 960, self_q=False, itemsize=1)
    emit("quantization/dma_bytes_ratio_d960", 0.0,
         f"fp32_over_int8={d960:.2f}x")
    for regime in ("small", "large"):
        fp, q = recalls[("fp32", regime)], recalls[("int8_rerank", regime)]
        ok = q >= fp - 0.01
        emit(f"quantization/recall_gate_{regime}", 0.0,
             f"fp32={fp:.3f};int8_rerank={q:.3f};pass={ok}")
        if not ok:
            raise SystemExit(
                f"quantization recall gate failed ({regime}): "
                f"int8_rerank={q:.3f} < fp32={fp:.3f} - 0.01")


# ==========================================================================
# locality-packed layout + visited filter (DESIGN.md §10)
# ==========================================================================

def layout_packing():
    """The "layout" build stage + hash visited filter, measured end to end.

    Rows: span coalescing of the adjacency before/after packing (host
    mirror of the kernel's grouped-DMA rule, at the kernel group width
    and the finer G=2/4 sub-widths the ROADMAP names), the DMA copy
    counts those spans collapse, the per-hop merge work the visited
    filter removes (static shapes), and steady per-query latency through
    the serving engine for plain / packed / packed+hash in both regimes.

    On CPU the latency rows are directional only: the hash filter's win
    is structural (it deletes the O(width²) dedup scans + re-rank merge
    the TPU bitonic path pays), but the XLA-CPU emulation pays the
    probe scans without that saving, so expect hash rows slower here
    and read the DMA/merge accounting rows for the TPU story.

    This bench is also a CI quick-tier regression gate: the packed
    graph's rows-per-copy must exceed 1.0 (the layout stage actually
    coalesces) and packed results must stay bitwise-identical to
    unpacked — either failure exits non-zero."""
    from repro.ann import Index
    from repro.ann import layout as LY
    from repro.serve.plane import SMALL_WIDTH

    ds = _dataset(n=2048 if QUICK else 8192, nq=256)
    cfg = _cfg(max_degree=16, k_graph=24, serve_buckets=(8, 64),
               large_hops=24 if QUICK else 48)
    packed_pipe = ("knn", "diversify", "bridges", "layout")
    variants = [
        ("plain", dict()),
        ("packed", dict(build_pipeline=packed_pipe)),
        ("packed_hash", dict(build_pipeline=packed_pipe,
                             visited_filter="hash")),
    ]
    built = {}
    for name, kw in variants:
        built[name] = Index.build(ds.X, dataclasses.replace(cfg, **kw),
                                  k=10)

    # -- span coalescing: host mirror of the kernel's grouped-DMA rule --
    nb_plain = np.asarray(built["plain"].graph.neighbors)
    nb_packed = np.asarray(built["packed"].graph.neighbors)
    stats = {}
    for tag, nb in (("before", nb_plain), ("after", nb_packed)):
        st = LY.span_stats(nb)
        stats[tag] = st
        emit(f"layout/span_{tag}", 0.0,
             f"group={st['group']};rows_per_copy={st['rows_per_copy']:.3f}"
             f";frac_coalesced={st['frac_coalesced']:.3f}"
             f";dma_copies={st['dma_copies']}")
    # sub-group histogram: how much coalescing finer span widths would see
    hist = ";".join(
        f"G{g}={LY.span_stats(nb_packed, group=g)['frac_coalesced']:.3f}"
        for g in (2, 4, 8))
    emit("layout/span_histogram", 0.0, hist)

    # -- merge work the visited filter removes (static shapes) --
    W = SMALL_WIDTH  # the small regime's compiled ranking width
    emit("layout/visited_merge_width", 0.0,
         f"dedup_path=scan{W}x{W}+rerank_merge{2 * W}"
         f";hash_path=merge{W};probes_per_lane=8")

    # -- steady-state serving, packed vs plain, both regimes --
    qps = {}
    for name, _ in variants:
        for regime, B in (("small", 8), ("large", 64)):
            us = _steady_us(built[name], ds.Q, B)
            qps[(name, regime)] = us
            emit(f"layout/{name}_{regime}_B{B}", us,
                 f"qps={1e6 / us:.0f}")

    # -- gates --
    rpc = stats["after"]["rows_per_copy"]
    ok_rpc = rpc > 1.0
    bitwise = all(
        np.array_equal(built["plain"].search(ds.Q[:B])[i],
                       built["packed"].search(ds.Q[:B])[i])
        for B in (8, 64) for i in (0, 1))
    emit("layout/gate", 0.0,
         f"rows_per_copy={rpc:.3f};pass={ok_rpc}"
         f";packed_bitwise={bitwise}")
    if not ok_rpc:
        raise SystemExit(
            f"layout gate failed: packed rows-per-copy {rpc:.3f} <= 1.0 "
            "(layout stage coalesced nothing)")
    if not bitwise:
        raise SystemExit(
            "layout gate failed: packed results diverge from unpacked")


# ==========================================================================
# kernel microbenches — Pallas timed alongside the XLA refs
# ==========================================================================

def _pallas_tag():
    """On CPU the Pallas kernels run in interpret mode; say so in the row."""
    return ("backend=pallas" if jax.default_backend() == "tpu"
            else "backend=pallas_interpret")


def kernel_micro():
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    Q = jnp.asarray(rng.normal(size=(256, 128)).astype(np.float32))
    X = jnp.asarray(rng.normal(size=(8192, 128)).astype(np.float32))
    f = jax.jit(lambda a, b: ref.distance_matrix_ref(a, b, metric="l2"))
    us, _ = _timeit(f, Q, X)
    emit("kernel/l2dist_256x8192x128", us, "backend=xla_ref")
    f = jax.jit(lambda a, b: ops.distance_matrix(a, b, metric="l2"))
    us, _ = _timeit(f, Q, X)
    emit("kernel/l2dist_256x8192x128", us, _pallas_tag())

    d = jnp.asarray(rng.normal(size=(2048, 64)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 1 << 20, size=(2048, 64))
                      .astype(np.int32))
    f = jax.jit(lambda a, b: ref.sort_ref(a, b))
    us, _ = _timeit(f, d, ids)
    emit("kernel/bitonic_sort_2048x64", us, "backend=xla_ref")
    f = jax.jit(lambda a, b: ops.bitonic_sort(a, b))
    us, _ = _timeit(f, d, ids)
    emit("kernel/bitonic_sort_2048x64", us, _pallas_tag())

    q = jnp.asarray(rng.normal(size=(2, 512, 8, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 512, 2, 64)).astype(np.float32))
    f = jax.jit(lambda a, b, c: ref.attention_ref(a, b, c, window=256))
    us, _ = _timeit(f, q, k, k)
    emit("kernel/flash_attn_512_gqa", us, "backend=xla_ref")
    f = jax.jit(lambda a, b, c: ops.flash_attention(a, b, c, window=256))
    us, _ = _timeit(f, q, k, k)
    emit("kernel/flash_attn_512_gqa", us, _pallas_tag())


# ==========================================================================
# hot-path primitives + end-to-end search: pallas vs xla backend
# ==========================================================================

def hotpath_micro():
    """The three hotpath primitives, timed under both backends."""
    import functools

    from repro.core import hotpath as HP

    rng = np.random.default_rng(0)
    S, C, d_dim, N = (512, 32, 64, 100_000)
    X = jnp.asarray(rng.normal(size=(N, d_dim)).astype(np.float32))
    Q = jnp.asarray(rng.normal(size=(S, d_dim)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, N, size=(S, C)).astype(np.int32))
    mask = jnp.asarray(rng.random((S, C)) > 0.1)
    dists = jnp.asarray(rng.normal(size=(S, 96)).astype(np.float32))
    mids = jnp.asarray(rng.integers(0, N, size=(S, 96)).astype(np.int32))

    for backend in ("xla", "pallas"):
        tag = _pallas_tag() if backend == "pallas" else "backend=xla"
        f = jax.jit(lambda q, x, i, m, _b=backend: HP.neighbor_distances(
            q, x, i, metric="l2", mask=m, backend=_b))
        us, _ = _timeit(f, Q, X, idx, mask)
        emit(f"hotpath/neighbor_distances_{S}x{C}x{d_dim}", us, tag)
        f = jax.jit(functools.partial(
            HP.rank_merge, keep=64, backend=backend))
        us, _ = _timeit(f, dists, mids)
        emit(f"hotpath/rank_merge_{S}x96_keep64", us, tag)
        f = jax.jit(functools.partial(
            HP.seed_select, metric="l2", k=1, backend=backend))
        us, _ = _timeit(f, Q, X, idx)
        emit(f"hotpath/seed_select_{S}x{C}", us, tag)

    # gather placement within the Pallas backend: in-kernel scalar-prefetch
    # DMA gather (gather_fused) vs the XLA-gather-then-block path — the
    # ROADMAP "In-kernel neighbor gather" item's measured comparison.  On
    # CPU the fused path runs its DMAs in interpret mode (tagged as such);
    # on TPU this row is the [S, C, d]-buffer-elision win.
    Sf, Cf = (64, 16) if QUICK else (256, 32)
    Qf, idxf, maskf = Q[:Sf], idx[:Sf, :Cf], mask[:Sf, :Cf]
    times = {}
    for variant, gf in (("gather_then_block", "off"), ("gather_fused", "on")):
        f = jax.jit(lambda q, x, i, m, _g=gf: HP.neighbor_distances(
            q, x, i, metric="l2", mask=m, backend="pallas",
            gather_fused=_g))
        us, _ = _timeit(f, Qf, X, idxf, maskf)
        times[variant] = us
        emit(f"hotpath/neighbor_distances_{Sf}x{Cf}x{d_dim}", us,
             f"{_pallas_tag()};variant={variant}")
    emit(f"hotpath/neighbor_distances_fused_vs_gather_{Sf}x{Cf}x{d_dim}",
         0.0,
         f"fused_us={times['gather_fused']:.1f};"
         f"gather_us={times['gather_then_block']:.1f};"
         f"fused_speedup={times['gather_then_block'] / max(times['gather_fused'], 1e-9):.2f}x")


def search_backend_compare():
    """Both search regimes end-to-end under kernel_backend pallas vs xla —
    same graph, same queries; rows also record cross-backend id parity."""
    from repro.ann.pipeline import build_graph
    from repro.core.knn_build import exact_knn
    from repro.core.search_large import \
        _large_batch_search as large_batch_search
    from repro.core.search_small import \
        _small_batch_search as small_batch_search
    from repro.data.synthetic import recall_at_k

    ds = _dataset(n=2000 if QUICK else 6000, nq=32)
    X = jnp.asarray(ds.X)
    ids, dists = exact_knn(X, 24)
    g = build_graph(X, _cfg(), knn_ids=ids, knn_dists=dists)
    Q = jnp.asarray(ds.Q)
    outs = {"small": {}, "large": {}}
    for backend in ("xla", "pallas"):
        tag = _pallas_tag() if backend == "pallas" else "backend=xla"
        fn = lambda: small_batch_search(X, g, Q, k=10, t0=8, hops=6,
                                        backend=backend)[0]
        us, out = _timeit(fn)
        outs["small"][backend] = np.asarray(out)
        r = recall_at_k(outs["small"][backend], ds.gt, 10)
        emit(f"hotpath/small_batch_e2e_{backend}", us / len(ds.Q),
             f"{tag};recall@10={r:.3f}")
        fn = lambda: large_batch_search(X, g, Q, k=10, ef=64,
                                        hops=32 if QUICK else 64,
                                        backend=backend)[0]
        us, out = _timeit(fn, repeat=2)
        outs["large"][backend] = np.asarray(out)
        r = recall_at_k(outs["large"][backend], ds.gt, 10)
        emit(f"hotpath/large_batch_e2e_{backend}", us / len(ds.Q),
             f"{tag};recall@10={r:.3f}")
    for regime, o in outs.items():
        match = bool((o["xla"] == o["pallas"]).all())
        emit(f"hotpath/{regime}_backend_parity", 0.0,
             f"ids_identical={match}")


# ==========================================================================
# roofline table from the dry-run artifacts
# ==========================================================================

def roofline_table():
    art = os.path.join(os.path.dirname(__file__), "artifacts", "dryrun")
    for path in sorted(glob.glob(os.path.join(art, "*__single.json"))):
        with open(path) as f:
            rec = json.load(f)
        r = rec.get("roofline", {})
        if not r:
            continue
        name = f"roofline/{rec['arch']}__{rec['shape']}"
        t_dom = max(r["t_compute_s"], r["t_memory_s"], r["t_collective_s"])
        emit(name, t_dom * 1e6,
             f"dominant={r['dominant']};flops={r['flops']:.2e};"
             f"coll={r['coll_bytes']:.2e}")


BENCHES = [table2_diversification_time, fig4_cpu_search, fig5_degree_sweep,
           fig6_small_batch, fig10_large_batch, ablation_alpha_lambda,
           serve_engine_mixed, serve_bucketed_vs_raw, serve_aot_reload,
           streaming_ingest,
           mesh_serve, router_serve, mesh_aot_reload,
           quantization_recall, layout_packing,
           kernel_micro,
           hotpath_micro, search_backend_compare, roofline_table]


def _persist_rows(tier: str, dev: dict) -> str:
    """Append this run's rows to ``BENCH_<tier>.json`` at the repo root —
    a timestamped history so regressions are diffable across commits
    (bounded to the last 50 runs per tier).  Returns the file path."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, f"BENCH_{tier}.json")
    history = {"tier": tier, "runs": []}
    if os.path.isfile(path):
        try:
            with open(path) as f:
                history = json.load(f)
        except ValueError:
            pass  # corrupt history: start fresh rather than fail the run
    history.setdefault("runs", []).append({
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "quick": QUICK,
        "device": dev,
        "rows": [{"name": n, "us_per_call": us, "derived": d}
                 for n, us, d in ROWS],
    })
    history["runs"] = history["runs"][-50:]
    with open(path, "w") as f:
        json.dump(history, f, indent=1)
    return path


def device() -> dict:
    """The device the rows were measured on, as JAX reports it."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main() -> int:
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    # REPRO_BENCH_ONLY=serve runs just the benches whose name contains the
    # substring (the CI serving smoke uses this)
    only = os.environ.get("REPRO_BENCH_ONLY", "")
    dev = device()
    print(f"# device platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}")
    print("name,us_per_call,derived")
    failed = []
    for bench in BENCHES:
        if only and only not in bench.__name__:
            continue
        try:
            bench()
        except Exception as e:  # noqa: BLE001
            failed.append(bench.__name__)
            emit(f"{bench.__name__}/ERROR", -1.0, repr(e)[:120])
    print(f"# {len(ROWS)} rows", flush=True)
    path = _persist_rows(only or "all", dev)
    print(f"# rows persisted to {path}", flush=True)
    if failed:
        print(f"# FAILED benches: {', '.join(failed)}", flush=True)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
