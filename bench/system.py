"""The system under test, seen only through the program's public objects:
``repro.ann.Index`` (build, search, serve), its ``ServeStats`` and the
micro-batcher's ``BatcherStats``, with the index configuration it is built
from.  And what may stand in its place: the control (the reference search,
one precision below the configuration's), and the program with a fault
planted under its timed path (a search cut short, a merge that loses the
best candidate), which ``correct`` has to refuse.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import re
import threading
import time
from concurrent.futures import Future


class StageLog(logging.Handler):
    """Collects the build pipeline's own stage times from its INFO log
    (``build stage <name>: <seconds> s``)."""

    PATTERN = re.compile(r"build stage (\S+): ([0-9.]+) s")

    def __init__(self):
        super().__init__(logging.INFO)
        self.stages: dict = {}

    def emit(self, record):
        m = self.PATTERN.search(record.getMessage())
        if m:
            self.stages[m.group(1)] = float(m.group(2))


@functools.lru_cache(maxsize=None)
def index_config(name: str):
    """The program's index configuration called ``name``: a registered one
    (``tsdg-paper``) or the reduced variant of one (``tsdg-reduced``)."""
    from repro.configs.base import get_arch, get_reduced, list_archs

    if name in list_archs():
        return get_arch(name)
    for arch in list_archs():
        cfg = get_reduced(arch)
        if getattr(cfg, "name", None) == name:
            return cfg
    raise LookupError(f"no index configuration {name!r}")


class Program:
    """The index of the configuration, built from the corpus ``X``."""

    def __init__(self, X, conf: dict, *, k: int, stage_log: bool = False):
        import jax

        from repro.ann import Index

        cfg = self.index_cfg(conf)
        self.stage_log = None
        if stage_log:
            # the pipeline blocks after each stage only when INFO is on
            self.stage_log = StageLog()
            lg = logging.getLogger("repro.ann.pipeline")
            lg.addHandler(self.stage_log)
            lg.setLevel(logging.INFO)
        t0 = time.perf_counter()
        self.index = Index.build(X, cfg, k=k)
        g = self.index.graph
        jax.block_until_ready((g.neighbors, g.lambdas, g.degrees))
        self.build_s = time.perf_counter() - t0
        self.stages = {}
        if stage_log:
            self.stages = dict(self.stage_log.stages)
            logging.getLogger("repro.ann.pipeline").removeHandler(
                self.stage_log)
        self.d = int(X.shape[1])

    @staticmethod
    def index_cfg(conf: dict):
        return index_config(conf["index_config"])

    @property
    def cfg(self):
        return self.index.cfg

    def search(self, Qh):
        return self.index.search(Qh)

    def serve(self):
        return self.index.serve()

    def warm(self, pool_h, sizes, *, coalesced: bool):
        """Compile and run each (regime, bucket) that requests of ``sizes``
        queries reach, once, on rows of the pool.  ``coalesced``: requests
        go through the micro-batcher, whose dispatches take any size up to
        ``queue_max_batch`` (or the largest request)."""
        import numpy as np

        if coalesced:
            sizes = range(1, max(max(sizes), self.cfg.queue_max_batch) + 1)
        eng, seen = self.index.engine, set()
        for b in sizes:
            key = (eng.regime(b), eng.bucket_for(b))
            if key not in seen:
                seen.add(key)
                self.index.search(np.resize(pool_h, (b, self.d)))

    def counters(self) -> dict:
        st = self.index.stats
        return {"queries": st.n_queries, "padded": st.padded_queries,
                "compiles": st.compiles}

    @staticmethod
    def batcher_counters(server) -> dict:
        snap = server.stats.snapshot()
        return {"dispatches": snap["n_dispatches"],
                "queries": snap["n_queries"]}

    def paths(self) -> list:
        from repro.core import hotpath

        return sorted(f"{p}:{path}:interpret={i}"
                      for (p, path, i) in hotpath.PATHS)


class ShortSearch(Program):
    """A planted fault: the search stops after an eighth of its hops, and
    the small regime after one.  Ids and distances still agree with each
    other; only recall shows it."""

    @staticmethod
    def index_cfg(conf: dict):
        cfg = index_config(conf["index_config"])
        return dataclasses.replace(cfg, large_hops=cfg.large_hops // 8,
                                   small_hops=1)


class DropBest(Program):
    """A planted fault: every answer loses its best candidate and takes the
    next one in, sorted and without repeats, as a rank merge that drops
    rank 0 would give."""

    def __init__(self, X, conf: dict, *, k: int, stage_log: bool = False):
        super().__init__(X, conf, k=k, stage_log=stage_log)
        eng = self.index.engine
        query = eng.query

        def dropped(Q, *, k=None, **kw):
            ids, dists = query(Q, k=(k or eng.k) + 1, **kw)
            return ids[:, 1:], dists[:, 1:]
        eng.query = dropped


class Control:
    """The reference search in the program's place, computed in bfloat16
    (the nearest precision below the configuration's float32): brute-force
    top-k over the whole corpus, with the distances it ranked by."""

    def __init__(self, X, conf: dict, *, k: int, stage_log: bool = False):
        self.X, self.k = X, k
        self.build_s = 0.0
        self.stages = {}
        self.d = int(X.shape[1])
        self._n = 0

    def search(self, Qh):
        from bench import reference as ref

        import jax.numpy as jnp

        self._n += len(Qh)
        return ref.top_k(self.X, jnp.asarray(Qh), self.k, dtype="bfloat16")

    def serve(self):
        return _SyncServer(self)

    def warm(self, pool_h, sizes, *, coalesced: bool):
        self.search(pool_h[:1] if coalesced else pool_h[:min(sizes)])

    def counters(self) -> dict:
        return {"queries": self._n, "padded": 0, "compiles": 0}

    @staticmethod
    def batcher_counters(server) -> dict:
        return {"dispatches": server.n, "queries": server.n}

    def paths(self) -> list:
        return ["control:bf16_brute_force"]


class _SyncServer:
    """The control's serving front: each submit (one query or a batch)
    answered on one worker thread, in order."""

    def __init__(self, system):
        import queue

        self.system, self.n = system, 0
        self._q = queue.Queue()
        self._t = threading.Thread(target=self._loop, daemon=True)
        self._t.start()

    def submit(self, q) -> Future:
        f = Future()
        self._q.put((q, f))
        return f

    def _loop(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            q, f = item
            ids, d = self.system.search(q.reshape(-1, q.shape[-1]))
            self.n += 1
            f.set_result((ids, d) if q.ndim == 2 else (ids[0], d[0]))

    def close(self):
        self._q.put(None)
        self._t.join()
