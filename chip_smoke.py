#!/usr/bin/env python3
"""Bring-up smoke test of the TSDG search service on one TPU chip.

Drives the service's normal path once at a real size: a seeded clustered
l2 corpus of 1,048,576 x 128 is built into an index through
``repro.ann.Index.build`` on the Pallas kernels (``tsdg-paper`` config),
then served at B = 1 and 10 (small-batch regime) and B = 10240 (large-batch
regime).  It checks recall@10 against exact top-10 computed on the chip,
that nothing compiles inside the serving window, that no kernel ran in
interpret mode, and that the XLA kernel backend gives the same answers on
the same graph.

With ``--four-chips`` it runs only the sharded path instead: the same
corpus served through the mesh plane on a 4-way ``data`` mesh, compared
with a one-chip index of the same corpus.

    python3 chip_smoke.py [--seed 0] [--four-chips]

Everything is generated from ``--seed``; nothing is read from disk.  It
exits non-zero, printing no result line, when JAX finds no TPU or any phase
fails.  The last line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

N, D, K = 1 << 20, 128, 10          # ANN_SHAPES build_1m / search_* (n, d)
SMALL_B, LARGE_B = (1, 10), 10240   # search_small / search_large batches
# 64 clusters, as in the repository's own synthetic sets: the 256 hub
# bridges of the config then reach every cluster (at 1024 clusters most
# clusters had no hub, random seeds missed the query's cluster, and
# recall@10 fell to 0.2 on both backends)
N_CLUSTERS, SUBSPACE = 64, 16
# recall@10 the smoke demands of either backend: far below what a working
# index reaches on this corpus, far above what a broken kernel returns
RECALL_FLOOR = 0.5
RECALL_SLACK = 0.01   # a path may trail its reference (xla; one chip) by this
SMALL_RECALL_QUERIES = 200          # small-regime recall: 20 batches of 10


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(*a) -> None:
    print(*a, flush=True)


# --------------------------------------------------------------------------
# data and exact answers, made on the chip
# --------------------------------------------------------------------------

def make_corpus(seed: int, n: int, n_queries: int):
    """Clustered l2 corpus and queries: cluster centres plus a displacement
    in a shared 16-dimensional subspace (so the data has a low intrinsic
    dimension, like SIFT) plus small isotropic noise."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gen(key):
        kc, kb, kx, kq = jax.random.split(key, 4)
        centers = jax.random.normal(kc, (N_CLUSTERS, D), jnp.float32)
        basis = jax.random.normal(kb, (SUBSPACE, D), jnp.float32) \
            / jnp.sqrt(jnp.float32(SUBSPACE))

        def draw(k, m):
            ka, kz, ke = jax.random.split(k, 3)
            a = jax.random.randint(ka, (m,), 0, N_CLUSTERS)
            z = jax.random.normal(kz, (m, SUBSPACE), jnp.float32)
            e = jax.random.normal(ke, (m, D), jnp.float32)
            return centers[a] + 0.5 * (z @ basis) + 0.05 * e
        return draw(kx, n), draw(kq, n_queries)

    return gen(jax.random.key(seed))


def exact_top_k(X, Q, k: int, tile: int = 256):
    """Exact top-k ids of Q against X, in query tiles, full fp32 matmul
    precision (the serving kernels' precision is what is under test)."""
    import jax
    import jax.numpy as jnp

    from repro.core import metrics as M

    B = Q.shape[0]
    n_tiles = -(-B // tile)
    Qp = jnp.pad(Q, ((0, n_tiles * tile - B), (0, 0)))

    @jax.jit
    def run(X, Qp):
        def one(i):
            q = jax.lax.dynamic_slice_in_dim(Qp, i * tile, tile, 0)
            with jax.default_matmul_precision("highest"):
                dist = M.pairwise(q, X, "l2")
            return jax.lax.top_k(-dist, k)[1].astype(jnp.int32)
        return jax.lax.map(one, jnp.arange(n_tiles)).reshape(-1, k)

    return run(X, Qp)[:B]


def recall(found, gt) -> float:
    import numpy as np

    found, gt = np.asarray(found)[:, :K], np.asarray(gt)[:, :K]
    hits = sum(len(set(f.tolist()) & set(g.tolist()))
               for f, g in zip(found, gt))
    return hits / (gt.shape[0] * K)


def agreement(a, b) -> dict:
    """Share of (query, rank) ids that agree, share of ids found by both,
    and the largest distance difference where the ids agree."""
    import numpy as np

    (ia, da), (ib, db) = a, b
    same = ia == ib
    overlap = np.mean([len(set(x.tolist()) & set(y.tolist())) / ia.shape[1]
                       for x, y in zip(ia, ib)])
    diff = np.abs(da.astype(np.float64) - db.astype(np.float64))[same]
    return {"ids_equal_share": float(np.mean(same)),
            "id_set_overlap": float(overlap),
            "max_abs_dist_diff": float(diff.max()) if diff.size else 0.0,
            "bitwise": bool(np.array_equal(ia, ib)
                            and np.array_equal(da.view(np.uint32),
                                               db.view(np.uint32)))}


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def device_check(n_chips: int):
    import jax

    devs = jax.devices()
    dev = devs[0]
    check(dev.platform == "tpu",
          f"JAX found no TPU (platform {dev.platform!r}); this smoke runs "
          "on the chip only")
    check(len(devs) >= n_chips,
          f"{n_chips} chips needed, {len(devs)} visible")
    say(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    return dev


def serving_config(backend: str):
    from repro.configs.base import get_arch

    return dataclasses.replace(get_arch("tsdg-paper"),
                               kernel_backend=backend)


def build_phase(X, cfg, dev, **kw):
    from repro.ann import Index

    t0 = time.perf_counter()
    index = Index.build(X, cfg, k=K, **kw)
    dt = time.perf_counter() - t0
    stats = dev.memory_stats() or {}
    say(f"[build] {index!r} total_s={dt:.3f} "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    return index


def timed_search(index, Qh, reps: int):
    """Search ``reps`` times; returns (last answer, per-call seconds)."""
    lat = []
    out = None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = index.search(Qh)
        lat.append(time.perf_counter() - t0)
    return out, lat


def serve_phase(index, Qh, gt, label: str) -> dict:
    """Warm every served shape, then a serving window with no compiles:
    B = 1 and 10 (small regime), B = 10240 (large regime)."""
    import numpy as np

    batches = SMALL_B + (LARGE_B,)
    t0 = time.perf_counter()
    for B in batches:
        index.search(Qh[:B])
    say(f"[{label}] warmup compiles={index.stats.compiles} "
        f"seconds={time.perf_counter() - t0:.3f}")

    before = index.stats.compiles
    res = {}
    for B in batches:
        reps = 2 if B == LARGE_B else 10
        (ids, dists), lat = timed_search(index, Qh[:B], reps)
        res[B] = {"regime": index.regime(B), "ids": ids, "dists": dists,
                  "lat_ms": [x * 1e3 for x in lat]}
    # small-regime recall over more queries than one batch holds
    small = [index.search(Qh[i:i + 10])
             for i in range(0, SMALL_RECALL_QUERIES, 10)]
    res["small_all"] = (np.concatenate([s[0] for s in small]),
                        np.concatenate([s[1] for s in small]))
    with index.serve() as mb:            # the micro-batching front
        fut_ids, _ = mb.submit(Qh[:10]).result()
    check(np.array_equal(fut_ids, res[10]["ids"]),
          f"{label}: Index.serve answer differs from Index.search")
    compiles = index.stats.compiles - before
    check(compiles == 0, f"{label}: {compiles} compiles in the serving "
          "window")

    res["recall_small"] = recall(res["small_all"][0],
                                 gt[:SMALL_RECALL_QUERIES])
    res["recall_large"] = recall(res[LARGE_B]["ids"], gt[:LARGE_B])
    for B in batches:
        r = res[B]
        lat = np.asarray(r["lat_ms"])
        rec = recall(r["ids"], gt[:B])
        say(f"[{label}] B={B} regime={r['regime']} "
            f"latency_ms_median={np.median(lat):.3f} "
            f"latency_ms_min={lat.min():.3f} recall@10={rec:.4f}")
    say(f"[{label}] recall@10 small_regime({SMALL_RECALL_QUERIES} queries)="
        f"{res['recall_small']:.4f} large_regime({LARGE_B} queries)="
        f"{res['recall_large']:.4f} compiles_in_window={compiles}")
    return res


def paths_phase(index) -> None:
    """Which path each primitive took at these shapes; no kernel may have
    run in interpret mode."""
    from repro.core import hotpath as HP

    for (prim, path, interp), n in sorted(HP.PATHS.items()):
        say(f"[paths] {prim}: {path} interpret={interp} traces={n}")
    check(HP.PATHS, "no Pallas primitive was traced")
    interp = [k for k in HP.PATHS if k[2]]
    check(not interp, f"kernels ran in interpret mode: {interp}")
    route = getattr(index.plane, "stage_route", None) \
        or f"none (the {index.plane.name} plane places each batch itself)"
    say(f"[paths] query staging: {route}")


def one_chip(seed: int) -> None:
    import numpy as np

    dev = device_check(1)
    t0 = time.perf_counter()
    X, Q = make_corpus(seed, N, LARGE_B)
    gt = np.asarray(exact_top_k(X, Q, K))
    Qh = np.asarray(Q)
    say(f"[data] n={N} d={D} queries={LARGE_B} seed={seed} "
        f"(corpus + exact top-{K} on chip) seconds="
        f"{time.perf_counter() - t0:.3f}")

    index = build_phase(X, serving_config("pallas"), dev)
    check(index.backend == "pallas", f"backend is {index.backend!r}")
    pal = serve_phase(index, Qh, gt, "pallas")
    paths_phase(index)

    from repro.ann import Index

    xla = serve_phase(Index(X, serving_config("xla"), k=K,
                            graph=index.graph), Qh, gt, "xla")
    for regime, key in (("small", 10), ("large", LARGE_B)):
        ag = agreement((pal[key]["ids"], pal[key]["dists"]),
                       (xla[key]["ids"], xla[key]["dists"]))
        say(f"[parity] {regime} B={key} pallas vs xla: "
            + " ".join(f"{k}={v}" for k, v in ag.items()))
        rp, rx = pal[f"recall_{regime}"], xla[f"recall_{regime}"]
        check(rp >= rx - RECALL_SLACK,
              f"{regime}: pallas recall {rp:.4f} < xla {rx:.4f} - "
              f"{RECALL_SLACK}")
        check(min(rp, rx) >= RECALL_FLOOR,
              f"{regime}: recall {min(rp, rx):.4f} below the floor "
              f"{RECALL_FLOOR}")


def four_chips(seed: int) -> None:
    import jax
    import numpy as np

    dev = device_check(4)
    X, Q = make_corpus(seed, N, LARGE_B)
    gt = np.asarray(exact_top_k(X, Q, K))
    Qh = np.asarray(Q)
    cfg = serving_config("pallas")

    single = build_phase(X, cfg, dev)
    mesh = jax.make_mesh((4,), ("data",))
    sharded = build_phase(X, cfg, dev, mesh=mesh)
    shards = [(s.device.id, tuple(s.data.shape))
              for s in sharded.X.addressable_shards]
    say(f"[mesh] corpus shards (device, shape): {shards}")
    check(len({d for d, _ in shards}) == 4
          and all(shape == (N // 4, D) for _, shape in shards),
          "the corpus is not split over all four chips")
    for d in jax.devices():
        st = d.memory_stats() or {}
        say(f"[mesh] device {d.id} bytes_in_use={st.get('bytes_in_use')} "
            f"peak_bytes_in_use={st.get('peak_bytes_in_use')}")

    one = serve_phase(single, Qh, gt, "one-chip")
    four = serve_phase(sharded, Qh, gt, "mesh4")
    paths_phase(sharded)
    for regime, key in (("small", 10), ("large", LARGE_B)):
        ag = agreement((four[key]["ids"], four[key]["dists"]),
                       (one[key]["ids"], one[key]["dists"]))
        say(f"[parity] {regime} B={key} mesh4 vs one-chip: "
            + " ".join(f"{k}={v}" for k, v in ag.items()))
        r4, r1 = four[f"recall_{regime}"], one[f"recall_{regime}"]
        check(r4 >= r1 - RECALL_SLACK,
              f"{regime}: mesh recall {r4:.4f} < one-chip {r1:.4f} - "
              f"{RECALL_SLACK}")
        check(min(r4, r1) >= RECALL_FLOOR,
              f"{regime}: recall {min(r4, r1):.4f} below the floor "
              f"{RECALL_FLOOR}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="serve the corpus sharded over a 4-chip data mesh "
                         "and compare with one chip (this path only)")
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from repro.utils.compile_cache import use_compile_cache

    handler = logging.StreamHandler(sys.stdout)
    handler.setFormatter(logging.Formatter("[%(name)s] %(message)s"))
    logging.getLogger("repro").addHandler(handler)
    logging.getLogger("repro").setLevel(logging.INFO)
    say(f"[cache] {use_compile_cache(ROOT)}")
    t0 = time.perf_counter()
    try:
        (four_chips if args.four_chips else one_chip)(args.seed)
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    devs = jax.devices()
    say(f"[smoke] passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
