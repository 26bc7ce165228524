"""The serving path's own host spans (``repro.*`` on the profiler's clock)
and the batcher's per-request wait and service counters."""
import dataclasses
import glob
import os
import time

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.serve.queue import MicroBatcher


def _traced(tmp_path, fn) -> list:
    """Run ``fn`` under the profiler; its ``repro.*`` host events as
    ``(name, start_ns, end_ns, stats, line)``, in start order; a line is
    one thread's."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats), (plane.name, i))
                       for e in line.events if e.name.startswith("repro."))
    return sorted(out, key=lambda e: e[1])


def _inside(child, parent) -> bool:
    return (child[4] == parent[4] and parent[1] <= child[1]
            and child[2] <= parent[2])


def _named(events, name) -> list:
    return [e for e in events if e[0] == name]


class _SleepyEngine:
    """Engine stand-in whose every dispatch takes ``delay_s``."""

    def __init__(self, delay_s: float, d: int = 4):
        self.X = np.zeros((16, d), np.float32)
        self.cfg = dataclasses.replace(get_arch("tsdg-paper"),
                                       queue_max_wait_ms=1.0,
                                       queue_max_batch=64)
        self.delay_s = delay_s

    def query(self, Q, k=None):
        time.sleep(self.delay_s)
        B = Q.shape[0]
        return np.zeros((B, 3), np.int32), np.zeros((B, 3), np.float32)


@pytest.fixture(scope="module")
def engine():
    from repro.data.synthetic import make_clustered
    from repro.serve.engine import ANNEngine

    ds = make_clustered(n=400, d=8, n_queries=8, n_clusters=8, noise=0.5,
                        seed=1)
    cfg = dataclasses.replace(get_arch("tsdg-paper"), k_graph=8,
                              max_degree=8, bridge_hubs=0, large_hops=8,
                              serve_buckets=(8,))
    return ANNEngine(ds.X, cfg, k=5), ds.Q


def test_engine_spans_nest_with_their_metadata(tmp_path, engine):
    eng, Q = engine
    # the first call compiles (a miss), the second finds the executable
    ev = _traced(tmp_path, lambda: [eng.query(Q[:3]) for _ in range(2)])
    calls = _named(ev, "repro.engine.query")
    assert len(calls) == 2
    for call in calls:
        assert call[3] == {"regime": "small", "bucket": 8, "queries": 3}
        phases = [e[0] for e in ev if e is not call and _inside(e, call)]
        assert phases == (["repro.engine.prepare", "repro.plane.stage",
                           "repro.engine.compile", "repro.engine.execute",
                           "repro.engine.fetch"] if call is calls[0] else
                          ["repro.engine.prepare", "repro.plane.stage",
                           "repro.engine.execute", "repro.engine.fetch"])
    compile_, = _named(ev, "repro.engine.compile")
    assert compile_[3] == {"regime": "small", "bucket": 8, "k": 5}


@pytest.mark.parametrize("kind, scopes", [
    ("small", ["search_small"]),
    ("large", ["search_large", "membership_scan", "c_queue_evict"]),
])
def test_search_programs_carry_their_named_scopes(engine, kind, scopes):
    plane = engine[0].plane
    text = jax.jit(plane._flat_search(kind, 5)).lower(
        *plane._op_specs(), plane._qspec(8)).as_text(debug_info=True)
    assert [s for s in scopes if f"{s}/" in text] == scopes


def test_batcher_spans_wrap_the_dispatch_and_its_engine_call(tmp_path):
    eng = _SleepyEngine(delay_s=0.02)
    mb = MicroBatcher(eng, max_wait_ms=5.0, max_batch=64)

    def serve():
        with mb:
            futs = [mb.submit(np.zeros(4, np.float32)) for _ in range(3)]
            for f in futs:
                f.result(timeout=30)

    ev = _traced(tmp_path, serve)
    dispatch = _named(ev, "repro.batcher.dispatch")
    assert [d[3]["dispatch"] for d in dispatch] == \
        list(range(1, len(dispatch) + 1))
    assert sum(d[3]["requests"] for d in dispatch) == 3
    assert sum(d[3]["queries"] for d in dispatch) == 3
    for d in dispatch:
        resolve = [e for e in _named(ev, "repro.batcher.resolve")
                   if _inside(e, d)]
        assert len(resolve) == 1
        assert resolve[0][2] - d[1] >= 0.02 * 1e9   # after the engine call
    assert _named(ev, "repro.batcher.idle")
    assert len(_named(ev, "repro.batcher.coalesce")) == len(dispatch)


def test_batcher_counts_wait_and_service_per_request():
    # A is dispatched at once and served in `delay`; B, sent while A's
    # dispatch runs, waits for it to end and is then served in `delay`
    delay = 0.1
    lat = []

    def send(mb):
        t = time.perf_counter()
        mb.submit(np.zeros(4, np.float32)).add_done_callback(
            lambda _: lat.append(time.perf_counter() - t))
        return t

    with MicroBatcher(_SleepyEngine(delay_s=delay), max_wait_ms=1.0,
                      max_batch=64) as mb:
        t_a = send(mb)
        time.sleep(0.02)
        t_b = send(mb)
    snap = mb.stats.snapshot()
    assert snap["n_requests"] == 2 and snap["n_dispatches"] == 2
    assert 0.0 <= snap["wait_s"] - (delay - (t_b - t_a)) < 0.05
    assert 0.0 <= snap["serve_s"] - 2 * delay < 0.05
    # seen from the caller, each request took its wait plus its service
    assert len(lat) == 2
    assert abs(sum(lat) - snap["wait_s"] - snap["serve_s"]) < 5e-3


def test_failed_dispatch_still_counts_its_service():
    class Broken(_SleepyEngine):
        def query(self, Q, k=None):
            time.sleep(self.delay_s)
            raise RuntimeError("device lost")

    with MicroBatcher(Broken(delay_s=0.03), max_wait_ms=1.0) as mb:
        f = mb.submit(np.zeros(4, np.float32))
        with pytest.raises(RuntimeError, match="device lost"):
            f.result(timeout=30)
    snap = mb.stats.snapshot()
    assert snap["n_requests"] == 1
    assert 0.03 <= snap["serve_s"] < 0.08
