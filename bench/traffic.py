"""The one traffic generator: it reads a mix's parameters and drives the
system for the measured window.  A new mix is a new data file in
``bench/traffic/``; this module is not edited for it.

A mix's file names its ``kind`` and its parameters:

* ``closed`` — one request in flight at a time, each a batch of ``batch``
  queries drawn from the query pool in an order of the seed's
  (ANN-Benchmarks' batch mode).  Requests start until ``seconds`` have
  passed; the window ends when the last one is answered.
* ``open`` — requests of ``size`` queries (default 1) at a mean of
  ``rate_qps`` requests per second for ``seconds``, sent on a schedule
  whatever the system does (independent users).  The arrival times follow
  a Poisson process held to exactly ``round(rate * seconds)`` requests
  (uniform draws, sorted, so every seed offers the same work in another
  order).  An optional ``profile``, ``[[seconds, relative rate], ...]``,
  repeats through the window and shapes the rate over time at the same
  mean (bursts).  A request's latency runs from its due time to its answer.

``batch`` and ``size`` are a whole number, or a pair ``[lo, hi]``: sizes
spread log-uniformly over that range, both ends included.  Every seed gets
the same set of sizes (evenly spaced quantiles) in another order.

Host spans ``bench.<what>`` mark what the client is doing, on the
profiler's clock, for the traced run.
"""
from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np

ANSWER_WAIT_S = 60.0   # how long past the window's close an answer may come
SIZE_CYCLE = 64        # sizes of a closed mix's ranged batch, then repeated
PRIME_REQUESTS = 8     # requests an open mix sends in set-up, before timing


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(f"bench.{name}")


@dataclasses.dataclass
class Window:
    """What the window produced, per query: its pool row, its request, the
    request's due and answer times (seconds from the window's start; NaN
    where none came), and its answer."""
    qidx: np.ndarray                 # [R] pool row of each query
    due: np.ndarray                  # [R]
    done: np.ndarray                 # [R]
    ids: np.ndarray                  # [R, k] (-1 where no answer)
    dists: np.ndarray                # [R, k] (NaN where no answer)
    errors: int = 0                  # requests that raised
    late_s: np.ndarray | None = None  # open loop: how late each was sent
    req: np.ndarray | None = None    # [R] request of each query (None: one
    #                                  query per request)


class NoTrace:
    """The window's hooks where nothing is traced: ``begin`` just before
    the window starts (``end_at``: trace the part of the window just before
    that time, not its start), ``tick(t)`` after each batch or send, ``end``
    once every request is sent."""

    def begin(self, end_at=None):
        pass

    def tick(self, t: float):
        pass

    def end(self):
        pass


def sizes(spec, count: int, seed: int, stream: int) -> np.ndarray:
    """``count`` request sizes for ``spec`` (a whole number, or ``[lo, hi]``
    spread log-uniformly): the same quantiles for every seed, in an order
    of the seed's."""
    if np.ndim(spec) == 0:
        return np.full(count, int(spec), np.int64)
    lo, hi = (int(v) for v in spec)
    q = np.arange(count) / max(count - 1, 1)
    s = np.clip(np.round(lo * (hi / lo) ** q), lo, hi).astype(np.int64)
    return np.random.default_rng([seed, stream]).permutation(s)


def arrivals(mix: dict, seconds: float, n: int, rng) -> np.ndarray:
    """``n`` sorted due times in [0, seconds): uniform under the mix's
    rate ``profile`` (constant without one)."""
    u = np.sort(rng.uniform(0.0, 1.0, n))
    prof = mix.get("profile")
    if not prof:
        return u * seconds
    dur = np.array([p[0] for p in prof], float)
    rel = np.array([p[1] for p in prof], float)
    cycles = int(np.ceil(seconds / dur.sum())) + 1
    t = np.concatenate([[0.0], np.cumsum(np.tile(dur, cycles))])
    mass = np.concatenate([[0.0], np.cumsum(np.tile(dur * rel, cycles))])
    end = np.interp(seconds, t, mass)
    return np.minimum(np.interp(u * end, mass, t), np.nextafter(seconds, 0))


def schedule(mix: dict, seconds: float, pool: int, seed: int):
    """(due times [R], pool rows of every query, request sizes [R]) of an
    open-loop mix; request ``i`` asks for the next ``sizes[i]`` rows."""
    rng = np.random.default_rng([seed, 1])
    n = int(round(mix["rate_qps"] * seconds))
    due = arrivals(mix, seconds, n, rng)
    size = sizes(mix.get("size", 1), n, seed, 3)
    return due, rng.integers(0, pool, int(size.sum())), size


def batch_size(mix: dict, seed: int, i: int) -> int:
    """Queries in the ``i``-th request of a closed mix (``i = -1``: the
    set-up's warm-up batch)."""
    return int(sizes(mix["batch"], SIZE_CYCLE, seed, 4)[i % SIZE_CYCLE])


def batch_order(mix: dict, pool: int, seed: int, i: int) -> np.ndarray:
    """Pool rows of the ``i``-th batch of a closed mix (``i = -1``: the
    set-up's warm-up batch, drawn apart from the window's)."""
    rng = np.random.default_rng([seed, 2, i + 1])
    b = batch_size(mix, seed, i)
    return np.resize(rng.permutation(pool), b)


def warm(system, pool_h, mix: dict, seed: int):
    """Set-up for the mix: compile and run every shape it will send, and
    for an open mix start the system's serving front and send it a few
    requests.  Returns the front (``None`` for a closed mix)."""
    if mix["kind"] == "closed":
        sz = sizes(mix["batch"], SIZE_CYCLE, seed, 4)
        system.warm(pool_h, sorted(set(sz.tolist())), coalesced=False)
        return None
    if mix["kind"] != "open":
        raise ValueError(f"traffic kind {mix['kind']!r}: closed or open")
    top = int(np.max(mix.get("size", 1)))
    system.warm(pool_h, list(range(1, top + 1)), coalesced=True)
    server = system.serve()
    for f in [server.submit(pool_h[i]) for i in range(PRIME_REQUESTS)]:
        f.result()
    return server


def run(system, server, pool_h, mix: dict, seconds: float, seed: int, *,
        k: int, tracer=NoTrace()) -> Window:
    """The measured window: ``mix`` for ``seconds`` against ``system`` (a
    closed mix) or its serving front ``server`` (an open mix, closed once
    every answer is in)."""
    if mix["kind"] == "closed":
        return _run_closed(system, pool_h, mix, seconds, seed, tracer)
    try:
        return _run_open(server, pool_h, mix, seconds, seed, k, tracer)
    finally:
        server.close()


def _run_closed(system, pool_h, mix, seconds, seed, tracer):
    rows, due, done, ids, dists, req = [], [], [], [], [], []
    tracer.begin()
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < seconds:
        qi = batch_order(mix, len(pool_h), seed, i)
        t_due = time.perf_counter() - t0
        with span("search"):
            a, d = system.search(pool_h[qi])
        t_done = time.perf_counter() - t0
        rows.append(qi)
        due.append(np.full(len(qi), t_due))
        done.append(np.full(len(qi), t_done))
        req.append(np.full(len(qi), i))
        ids.append(a)
        dists.append(d)
        i += 1
        tracer.tick(t_done)
    tracer.end()
    return Window(qidx=np.concatenate(rows), due=np.concatenate(due),
                  done=np.concatenate(done), ids=np.concatenate(ids),
                  dists=np.concatenate(dists), req=np.concatenate(req))


def _run_open(server, pool_h, mix, seconds, seed, k, tracer):
    due_r, qidx, size = schedule(mix, seconds, len(pool_h), seed)
    R = len(due_r)
    start = np.concatenate([[0], np.cumsum(size)])
    done_r = np.full(R, np.nan)
    late = np.zeros(R)
    futs = []
    lock = threading.Lock()
    tracer.begin(end_at=seconds)    # the settled end of the sends
    t0 = time.perf_counter()

    def mark(i):
        def cb(_):
            t = time.perf_counter() - t0
            with lock:
                done_r[i] = t
        return cb

    for i in range(R):
        wait = due_r[i] - (time.perf_counter() - t0)
        if wait > 0:
            with span("sleep"):
                time.sleep(wait)
        now = time.perf_counter() - t0
        late[i] = max(0.0, now - due_r[i])
        tracer.tick(now)
        q = qidx[start[i]:start[i + 1]]
        with span("submit"):
            f = server.submit(pool_h[q[0]] if size[i] == 1 else pool_h[q])
        f.add_done_callback(mark(i))
        futs.append(f)
    tracer.end()
    m = int(start[-1])
    ids = np.full((m, k), -1, np.int64)
    dists = np.full((m, k), np.nan)
    answered = np.zeros(R, bool)
    errors = 0
    limit = t0 + seconds + ANSWER_WAIT_S
    for i, f in enumerate(futs):
        try:
            a, d = f.result(timeout=max(0.0, limit - time.perf_counter()))
        except Exception:  # noqa: BLE001 — a failed request
            errors += 1
            continue
        s = slice(start[i], start[i + 1])
        ids[s], dists[s] = np.reshape(a, (-1, k)), np.reshape(d, (-1, k))
        answered[i] = True
    with lock:
        done_r = np.where(answered, done_r, np.nan)
    req = np.repeat(np.arange(R), size)
    return Window(qidx=qidx, due=due_r[req], done=done_r[req], ids=ids,
                  dists=dists, errors=errors, late_s=late, req=req)
