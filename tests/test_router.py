"""Request router (DESIGN.md §9): replicated/sharded dispatch, bitwise
parity, failover under replica kills, health-check eject/readmit, and
aggregated stats.

In-process tests drive real :class:`ANNEngine` endpoints.  The sharded
router <-> mesh plane parity acceptance runs in a subprocess with two
emulated devices (device count is locked at jax init), mirroring
``tests/test_mesh_plane``.  The regime threshold is pinned static in every
parity test — dispatch must agree across endpoints for the comparison to
be meaningful (the pod-plane caveat in ``repro/serve/pod.py`` applies to
routers the same way).
"""
import dataclasses
import os
import subprocess
import sys
import time
from concurrent.futures import wait

import numpy as np
import pytest

from repro.ann import Index
from repro.configs import get_arch
from repro.core.distributed import merge_shard_results
from repro.data.synthetic import make_clustered, recall_at_k
from repro.serve.router import (NoHealthyReplicas, PartialResultError,
                                ReplicaDead, Router, RouterConfig,
                                parse_router_spec, replicate_engine,
                                shard_engines)

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(code: str, devices: int = 2):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def _bitwise(a, b):
    return (bool(np.array_equal(a[0], b[0]))
            and bool(np.array_equal(np.asarray(a[1]).view(np.uint32),
                                    np.asarray(b[1]).view(np.uint32))))


@pytest.fixture(scope="module")
def ds():
    return make_clustered(n=1024, d=16, n_queries=64, n_clusters=16,
                          noise=0.6, seed=0)


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(get_arch("tsdg-paper"), k_graph=8,
                               max_degree=12, lambda0=4, bridge_hubs=16,
                               bridge_k=4, large_ef=32, large_hops=16,
                               serve_buckets=(8, 64))


@pytest.fixture(scope="module")
def thresh(cfg):
    # population rule B*t0 < 4*thr: B < 32 -> small, B >= 32 -> large
    return 8.0 * cfg.small_t0


@pytest.fixture(scope="module")
def idx(ds, cfg, thresh):
    index = Index.build(ds.X, cfg, k=10, threshold=thresh)
    index.warmup()
    return index


# ----------------------------------------------------------------------
# config + construction validation
# ----------------------------------------------------------------------

def test_router_config_did_you_mean():
    with pytest.raises(ValueError, match="did you mean 'replicated'"):
        RouterConfig(mode="replcated")
    with pytest.raises(ValueError, match="did you mean 'least_loaded'"):
        RouterConfig(policy="least_loded")
    with pytest.raises(ValueError, match="replicas"):
        RouterConfig(replicas=0)
    with pytest.raises(ValueError, match="endpoint_names"):
        RouterConfig(replicas=2, endpoint_names=("lonely",))
    with pytest.raises(ValueError, match="readmit_probes"):
        RouterConfig(readmit_probes=0)
    with pytest.raises(ValueError, match="probe_timeout_s"):
        RouterConfig(probe_timeout_s=0.0)


def test_parse_router_spec():
    rc = parse_router_spec("replicated:3")
    assert rc.mode == "replicated" and rc.replicas == 3
    assert parse_router_spec("sharded:2").mode == "sharded"
    assert parse_router_spec("replicated:2",
                             health_interval_s=0.5).health_interval_s == 0.5
    with pytest.raises(ValueError, match="did you mean 'sharded'"):
        parse_router_spec("shardd:2")
    with pytest.raises(ValueError, match="MODE:N"):
        parse_router_spec("replicated")
    with pytest.raises(ValueError, match="positive int"):
        parse_router_spec("replicated:0")


def test_router_endpoint_validation(idx):
    eps = replicate_engine(idx.engine, 2)
    try:
        with pytest.raises(ValueError, match="replicas=3"):
            Router(eps, RouterConfig(replicas=3, health_interval_s=0.0))
    finally:
        for e in eps:
            e.close()
    with pytest.raises(ValueError, match="at least one endpoint"):
        Router([], RouterConfig(replicas=1))
    eps = replicate_engine(idx.engine, 2, names=("twin", "twin"))
    try:
        with pytest.raises(ValueError, match="unique"):
            Router(eps, RouterConfig(replicas=2, health_interval_s=0.0))
    finally:
        for e in eps:
            e.close()


def test_shard_engines_requires_equal_cut(cfg):
    X = np.zeros((10, 4), np.float32)
    with pytest.raises(ValueError, match="do not split evenly"):
        shard_engines(X, cfg, shards=3)


# ----------------------------------------------------------------------
# replicated mode: parity, shared cache, policies
# ----------------------------------------------------------------------

def test_replicated_bitwise_parity_both_regimes(ds, idx):
    """Acceptance: a replicated router answers bitwise-identically to a
    single directly-queried replica (= the donor index), both regimes."""
    rc = RouterConfig(mode="replicated", replicas=2, health_interval_s=0.0)
    with idx.serve(router=rc) as r:
        for B in (5, 64):
            ref = idx.search(ds.Q[:B])
            assert _bitwise(r.query(ds.Q[:B]), ref), B
        # single-vector convenience strips the leading axis
        gi, gd = r.query(ds.Q[0])
        ref = idx.search(ds.Q[:1])
        assert gi.shape == (10,)
        assert np.array_equal(gi, ref[0][0])
        assert np.array_equal(np.asarray(gd).view(np.uint32),
                              np.asarray(ref[1][0]).view(np.uint32))


def test_replicated_shared_cache_zero_compiles(ds, idx):
    """Replicas share the donor's plane AND compile cache: a router over a
    warmed index serves with aggregated compiles == 0, and the snapshot
    sums per-replica engine/queue counters consistently."""
    rc = RouterConfig(mode="replicated", replicas=3, policy="round_robin",
                      health_interval_s=0.0)
    # max_batch caps coalesced groups at the largest warmed bucket —
    # otherwise a 64-row submit coalesced with a 5-row one lands on a
    # (large, 128) shape the warmup sweep never compiled
    with idx.serve(router=rc, max_batch=64) as r:
        futs = [r.submit(ds.Q[:5]) for _ in range(6)]
        futs.append(r.submit(ds.Q[:64]))
        done, not_done = wait(futs, timeout=120)
        assert not not_done
        assert all(f.exception() is None for f in futs)
        snap = r.snapshot()
    agg, reps, rt = snap["aggregate"], snap["replicas"], snap["router"]
    assert agg["compiles"] == 0
    assert agg["n_replicas"] == 3 and agg["healthy_replicas"] == 3
    assert rt["n_requests"] == 7 and rt["n_dispatches"] == 7
    assert rt["retries"] == 0 and rt["lost_futures"] == 0
    assert agg["n_queries"] == sum(v["engine"]["n_queries"]
                                   for v in reps.values())
    # round-robin spreads the stream across every endpoint
    assert all(v["dispatches"] >= 2 for v in reps.values())
    assert agg["large_p50_ms"] > 0.0


def test_snapshot_aggregates_queue_wait_and_service(ds, idx):
    """The aggregate sums each replica batcher's wait and service time and
    gives their means per dispatched request."""
    with idx.serve(router="replicated:2", max_batch=64) as r:
        futs = [r.submit(ds.Q[i]) for i in range(6)]
        done, not_done = wait(futs, timeout=120)
        assert not not_done
    snap = r.snapshot()    # closed: every dispatch has counted its service
    agg, queues = snap["aggregate"], [v["queue"] for v in
                                      snap["replicas"].values()]
    assert sum(q["n_requests"] for q in queues) == 6
    assert agg["wait_s"] == pytest.approx(sum(q["wait_s"] for q in queues))
    assert agg["serve_s"] == pytest.approx(sum(q["serve_s"] for q in queues))
    assert agg["serve_s"] > 0.0 and agg["wait_s"] >= 0.0
    assert agg["service_ms"] == pytest.approx(agg["serve_s"] / 6 * 1e3)
    assert agg["queue_wait_ms"] == pytest.approx(agg["wait_s"] / 6 * 1e3)


def test_serve_router_accepts_spec_string(ds, idx):
    with idx.serve(router="replicated:2", max_wait_ms=0.5) as r:
        assert r.cfg.mode == "replicated" and r.cfg.replicas == 2
        ids, _ = r.query(ds.Q[:3])
        assert np.array_equal(ids, idx.search(ds.Q[:3])[0])


# ----------------------------------------------------------------------
# replicated mode: failure handling (acceptance: zero lost futures)
# ----------------------------------------------------------------------

def test_kill_replica_mid_stream_zero_lost_futures(ds, idx):
    """Acceptance: a replica killed under live traffic loses ZERO futures
    — every request (including ones already coalesced into the victim's
    queue) fails over to the healthy peer and answers bitwise-correctly."""
    rc = RouterConfig(mode="replicated", replicas=2, health_interval_s=0.0,
                      max_retries=2, backoff_s=0.001)
    with idx.serve(router=rc) as r:
        futs = []
        for i in range(30):
            futs.append(r.submit(ds.Q[:5]))
            if i == 10:
                r.endpoints[0].kill()
        done, not_done = wait(futs, timeout=120)
        assert not not_done
        for f in futs:
            assert f.exception() is None
            ids, dists = f.result()
            # coalesced requests sit at varying row offsets in the merged
            # batch, so per-row seeding makes answers offset-dependent —
            # assert quality, not bitwise identity (that's the
            # uncoalesced parity test's job)
            assert np.asarray(ids).shape == (5, 10)
            assert recall_at_k(np.asarray(ids), ds.gt[:5], 10) > 0.5
        snap = r.snapshot()
    rt = snap["router"]
    assert rt["lost_futures"] == 0
    assert rt["ejects"] == 1
    assert rt["retries"] >= 1
    assert snap["replicas"]["r0"]["healthy"] is False
    assert snap["aggregate"]["healthy_replicas"] == 1


def test_all_replicas_dead_fails_request(ds, idx):
    rc = RouterConfig(mode="replicated", replicas=2, health_interval_s=0.0,
                      max_retries=1, backoff_s=0.0)
    with idx.serve(router=rc) as r:
        for e in r.endpoints:
            e.kill()
        fut = r.submit(ds.Q[:5])
        with pytest.raises(ReplicaDead):
            fut.result(timeout=60)
        # both ejected now: the next request fails fast, no healthy pool
        fut2 = r.submit(ds.Q[:5])
        with pytest.raises(NoHealthyReplicas):
            fut2.result(timeout=60)
        snap = r.snapshot()
    assert snap["router"]["lost_futures"] == 2
    assert snap["router"]["ejects"] == 2
    assert snap["aggregate"]["healthy_replicas"] == 0


def test_user_error_propagates_without_retry(ds, idx):
    """Malformed requests are the caller's bug: they raise (synchronously
    for shape errors, through the future for engine validation) and never
    burn the retry budget or eject a replica."""
    rc = RouterConfig(mode="replicated", replicas=2, health_interval_s=0.0)
    with idx.serve(router=rc) as r:
        with pytest.raises(ValueError, match="Q must be"):
            r.submit(np.zeros((0, 16), np.float32))
        with pytest.raises(ValueError, match="Q must be"):
            r.submit(np.zeros((2, 7), np.float32))
        fut = r.submit(ds.Q[:2], k=10 ** 6)
        with pytest.raises(ValueError):
            fut.result(timeout=60)
        snap = r.snapshot()
    assert snap["router"]["retries"] == 0
    assert snap["router"]["lost_futures"] == 0
    assert snap["router"]["ejects"] == 0
    assert snap["aggregate"]["healthy_replicas"] == 2


def test_health_probe_eject_and_readmit(idx):
    """The prober ejects a dead replica within one probe interval (plus
    scheduling slack) and readmits it after ``readmit_probes`` consecutive
    successful probes; RouterStats reflects both transitions."""
    rc = RouterConfig(mode="replicated", replicas=2, health_interval_s=0.05,
                      probe_timeout_s=30.0, readmit_probes=2)
    with idx.serve(router=rc) as r:
        r.endpoints[0].kill()
        t0 = time.monotonic()
        while "r0" in r.healthy_replicas():
            assert time.monotonic() - t0 < 10, "probe failed to eject"
            time.sleep(0.005)
        r.endpoints[0].revive()
        t0 = time.monotonic()
        while "r0" not in r.healthy_replicas():
            assert time.monotonic() - t0 < 10, "probe failed to readmit"
            time.sleep(0.005)
        snap = r.snapshot()
    rt = snap["router"]
    assert rt["ejects"] >= 1 and rt["readmits"] >= 1
    assert rt["probes"] >= 2 and rt["probe_failures"] >= 1
    assert snap["aggregate"]["healthy_replicas"] == 2


def test_router_close_is_idempotent(ds, idx):
    rc = RouterConfig(mode="replicated", replicas=2, health_interval_s=0.0)
    r = idx.serve(router=rc)
    assert np.asarray(r.query(ds.Q[:3])[0]).shape == (3, 10)
    r.close()
    r.close()  # second close returns immediately, no re-drain
    with pytest.raises(RuntimeError, match="closed"):
        r.submit(ds.Q[:3])


# ----------------------------------------------------------------------
# sharded mode: merge semantics + partial results
# ----------------------------------------------------------------------

def test_sharded_router_merges_shards(ds, idx):
    """The routed answer is exactly merge_shard_results over the per-shard
    engines' raw answers (global ids, best-copy dedup, (dist, id) order)."""
    rc = RouterConfig(mode="sharded", replicas=2, health_interval_s=0.0)
    with idx.serve(router=rc) as r:
        got = r.query(ds.Q[:5])
        pools, offsets, n_rows = [], [], []
        for e in r.endpoints:
            ids, dists = e.engine.query(ds.Q[:5])
            pools.append((np.asarray(ids), np.asarray(dists)))
            offsets.append(e.id_offset)
            n_rows.append(e.n_rows)
    ref = merge_shard_results(pools, offsets, n_rows, k=10, batch=5)
    assert _bitwise(got, ref)
    # shard endpoints really are row slices with global offsets
    assert offsets == [0, 512] and n_rows == [512, 512]


def test_sharded_partial_result_error(ds, idx):
    """Acceptance: a killed shard (no peer holds its rows) fails the
    request with a typed PartialResultError carrying the SURVIVING shards'
    merged top-k."""
    rc = RouterConfig(mode="sharded", replicas=2, health_interval_s=0.0,
                      max_retries=1, backoff_s=0.001)
    with idx.serve(router=rc) as r:
        survivor = r.endpoints[0]
        r.endpoints[1].kill()
        fut = r.submit(ds.Q[:5])
        with pytest.raises(PartialResultError) as ei:
            fut.result(timeout=60)
        err = ei.value
        assert err.failed == ("s1",) and err.survivors == ("s0",)
        sids, sdists = survivor.engine.query(ds.Q[:5])
        ref = merge_shard_results(
            [(np.asarray(sids), np.asarray(sdists))],
            [survivor.id_offset], [survivor.n_rows], k=10, batch=5)
        assert np.array_equal(err.ids, ref[0])
        assert np.array_equal(np.asarray(err.dists).view(np.uint32),
                              np.asarray(ref[1]).view(np.uint32))
        snap = r.snapshot()
    rt = snap["router"]
    assert rt["partial_results"] == 1
    assert rt["lost_futures"] == 0     # a partial is an answer, not a loss
    assert rt["retries"] >= 1          # the same shard was retried first
    assert snap["replicas"]["s1"]["healthy"] is False


def test_sharded_all_shards_dead(ds, idx):
    rc = RouterConfig(mode="sharded", replicas=2, health_interval_s=0.0,
                      max_retries=0, backoff_s=0.0)
    with idx.serve(router=rc) as r:
        for e in r.endpoints:
            e.kill()
        fut = r.submit(ds.Q[:2])
        with pytest.raises(PartialResultError) as ei:
            fut.result(timeout=60)
        # nothing survived: the carried top-k is all-PAD
        assert ei.value.survivors == ()
        assert (np.asarray(ei.value.dists) >= np.float32(3.4e38)).all()


# ----------------------------------------------------------------------
# sharded router <-> mesh plane parity (2-device subprocess)
# ----------------------------------------------------------------------

_SETUP = """
import dataclasses, numpy as np, jax
from repro.ann import Index
from repro.configs import get_arch
from repro.data.synthetic import make_clustered
ds = make_clustered(n=1024, d=16, n_queries=64, n_clusters=16, noise=0.6,
                    seed=0)
cfg = dataclasses.replace(get_arch('tsdg-paper'), k_graph=8, max_degree=12,
                          lambda0=4, bridge_hubs=16, bridge_k=4, large_ef=32,
                          large_hops=16, serve_buckets=(8, 64))
THR = 8.0 * cfg.small_t0
def bitwise(a, b):
    return (np.array_equal(a[0], b[0])
            and np.array_equal(np.asarray(a[1]).view(np.uint32),
                               np.asarray(b[1]).view(np.uint32)))
"""


def test_sharded_router_matches_mesh():
    """THE sharded acceptance criterion: a router over P equal row slices
    answers bitwise-identically to a P-DB-shard mesh plane over the
    concatenated corpus, both regimes — the host-side
    merge_shard_results mirrors the mesh's in-collective merge_topk
    exactly (same validity mask, same global-id mapping, same
    (dist, id) dedup order)."""
    out = _run(_SETUP + """
from repro.serve.router import Router, RouterConfig, shard_engines
mesh = jax.make_mesh((2,), ('data',))
mi = Index.build(ds.X, cfg, k=10, mesh=mesh, threshold=THR)
eps = shard_engines(ds.X, cfg, shards=2, k=10, threshold=THR)
r = Router(eps, RouterConfig(mode='sharded', replicas=2,
                             health_interval_s=0.0))
try:
    for B, regime in ((5, 'small'), (64, 'large')):
        assert mi.regime(B) == regime, (B, mi.regime(B))
        got = r.query(ds.Q[:B], timeout=300)
        ref = mi.search(ds.Q[:B])
        assert bitwise(got, ref), (B, regime)
finally:
    r.close()
print('SHARDED PARITY OK')
""")
    assert "SHARDED PARITY OK" in out
