"""ANN serving engine: regime dispatch + shape-bucketed compile cache.

The paper's empirical split  (a·SMs + b) / d  decides which procedure a
batch takes; our TPU analogue compares the batch's *search population*
(B·t0 for the small procedure) against the device's matmul occupancy target
(`cfg.small_batch_threshold`, per DB shard) — or, with
``cfg.regime_calibration="probe"``, against a threshold *fitted* from timed
probe batches at engine init (:func:`repro.ann.dispatch.calibrate`, the
paper's per-GPU fit).  One engine, one graph — the λ-prefix trick means
both procedures share the index (paper §3.3).

Serving additions on top of the paper:

* **Shape buckets** — an incoming batch of B queries is padded up to the
  smallest bucket in ``cfg.serve_buckets`` that fits (edge-replicated rows),
  searched at the bucket shape, and sliced back to B rows.  Each
  (regime, bucket, k) triple is AOT-lowered and compiled exactly once and
  the executable is kept for the life of the engine, so steady-state
  traffic never re-traces or re-compiles.  Both search kernels derive their
  randomness per row (``fold_in`` by row index), so the padded call is
  bitwise-identical to the unpadded one on the real rows — padding is free
  in ids/recall, it only rounds up compute.
* **Execution planes** — the engine is device-layout agnostic: every
  lowering, operand, and fingerprint goes through an
  :class:`~repro.serve.plane.ExecutionPlane`.  The default
  ``SingleDevicePlane`` serves one resident database; pass ``mesh=`` and a
  ``MeshPlane`` shards the database + sub-indexes over the mesh
  (DESIGN.md §6) behind the same ``query()`` API — and, because the bucket
  ladder / compile cache / donation / stats all thread through the plane,
  a mesh engine gets per-(regime, bucket, k) cached executables, padded
  donated batches, AOT persistence and percentile stats for free.
* **Stats v2** — per-regime latency records (percentiles),
  compile and bucket-hit counters, and warmup (compile-triggering) batches
  excluded from steady-state QPS.
* **Streaming mutability (DESIGN.md §7)** — :meth:`add` appends vectors to
  a brute-force delta shard searched alongside the graph, :meth:`delete`
  tombstones ids via a persistent alive-mask threaded into the in-kernel
  keep-masks, and ``Index.compact()`` (:mod:`repro.ann.compaction`) folds
  both back into a fresh generation that hot-swaps under live traffic.
  The engine owns the host-side :class:`~repro.ann.delta.StreamState` and
  pushes device views to the plane; executables bind to operand *snapshots*
  so a same-shape generation swap re-uses every cached compile
  (``stats.compiles == 0`` across the swap), while a shape-changing swap
  surfaces as :class:`~repro.serve.plane.StaleGeneration` and ``query()``
  transparently re-dispatches.

This engine is the internal serving layer behind the :class:`repro.ann.Index`
facade (DESIGN.md §5): ``Index.search`` dispatches through ``query()``,
``Index.serve`` wires the engine to the micro-batching queue, and
``Index.save``/``Index.load`` persist the compile cache across processes via
:meth:`ANNEngine.export_executable` / :meth:`ANNEngine.prime_executable`.

Thread-safety: ``query()`` may be called from many threads (the
micro-batching queue in :mod:`repro.serve.queue` does); the compile cache
and stats are lock-protected.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ann.dispatch import regime_for
from repro.configs.base import ANNConfig
from repro.serve.plane import (MeshPlane, SingleDevicePlane, SMALL_WIDTH,
                               StaleGeneration)
from repro.utils.trace import span

# back-compat alias (pre-plane revisions defined the ranking width here)
_SMALL_WIDTH = SMALL_WIDTH


@dataclasses.dataclass
class RegimeStats:
    """Latency/throughput record for one regime, warmup split out."""

    n_batches: int = 0
    n_queries: int = 0
    total_s: float = 0.0            # steady-state wall time
    warmup_batches: int = 0
    warmup_s: float = 0.0           # compile-triggering calls (excluded)
    # bounded window of recent batch latencies (long-running engines must
    # not grow memory per request); totals above cover the full history
    latencies_s: collections.deque = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=8192))

    def record(self, n: int, dt: float, *, warmup: bool) -> None:
        if warmup:
            self.warmup_batches += 1
            self.warmup_s += dt
            return
        self.n_batches += 1
        self.n_queries += n
        self.total_s += dt
        self.latencies_s.append(dt)

    def percentiles(self, qs=(50, 90, 99)) -> dict:
        if not self.latencies_s:
            return {f"p{q}": float("nan") for q in qs}
        arr = np.asarray(self.latencies_s)
        return {f"p{q}": float(np.percentile(arr, q)) for q in qs}


@dataclasses.dataclass
class ServeStats:
    n_queries: int = 0              # all queries, warmup included
    n_batches: int = 0
    small_batches: int = 0
    large_batches: int = 0
    total_s: float = 0.0            # steady-state wall time (both regimes)
    steady_queries: int = 0
    compiles: int = 0
    aot_primed: int = 0             # executables restored from a saved index
    bucket_hits: int = 0            # calls served by a cached executable
    bucket_misses: int = 0          # calls that had to compile
    padded_queries: int = 0         # wasted rows added by bucketing
    # streaming mutability (DESIGN.md §7)
    generation: int = 0             # completed compactions since build/load
    n_added: int = 0                # vectors appended via add()
    n_deleted: int = 0              # ids tombstoned via delete()
    compactions: int = 0
    stream_batches: int = 0         # batches answered by a streaming exe
    # pinned-host H2D staging (single plane): batches moved through the
    # plane's staging route, and how many reused an already-built route
    # (the proof the pinned bounce buffer is reused in steady state)
    h2d_staged: int = 0
    h2d_stage_reuses: int = 0
    per_regime: dict = dataclasses.field(
        default_factory=lambda: {"small": RegimeStats(),
                                 "large": RegimeStats()})

    @property
    def qps(self) -> float:
        """Steady-state queries/s — warmup (compile) batches excluded."""
        return self.steady_queries / max(self.total_s, 1e-9)

    @property
    def bucket_hit_rate(self) -> float:
        total = self.bucket_hits + self.bucket_misses
        return self.bucket_hits / max(total, 1)

    def snapshot(self) -> dict:
        out = {
            "n_queries": self.n_queries, "n_batches": self.n_batches,
            "small_batches": self.small_batches,
            "large_batches": self.large_batches,
            "qps": self.qps, "compiles": self.compiles,
            "aot_primed": self.aot_primed,
            "bucket_hit_rate": self.bucket_hit_rate,
            "padded_queries": self.padded_queries,
            "generation": self.generation, "n_added": self.n_added,
            "n_deleted": self.n_deleted, "compactions": self.compactions,
            "stream_batches": self.stream_batches,
            "h2d_staged": self.h2d_staged,
            "h2d_stage_reuses": self.h2d_stage_reuses,
        }
        for name, reg in self.per_regime.items():
            for key, val in reg.percentiles().items():
                out[f"{name}_{key}_ms"] = val * 1e3
        return out


class ANNEngine:
    """In-process serving: build once, answer batches of queries.

    Single-device by default; pass ``mesh=`` to shard the database over the
    mesh's ``data``(+``pod``) axes and fan queries/searches over ``model``
    (see :mod:`repro.core.distributed`), or ``plane=`` to inject any
    prebuilt :class:`~repro.serve.plane.ExecutionPlane`.  Everything above
    the plane — bucket ladder, compile cache, warmup, donation hand-off,
    stats — is identical for every plane.

    ``threshold=`` overrides the regime split (a float compared against the
    same ``B·t0 < 4·threshold`` rule as ``cfg.small_batch_threshold``);
    with ``cfg.regime_calibration="probe"`` and no explicit override the
    threshold is fitted from timed probe batches at init
    (:func:`repro.ann.dispatch.calibrate`) and recorded in
    ``self.calibration``.
    """

    def __init__(self, X, cfg: ANNConfig | None = None, *, k: int = 10,
                 graph=None, mesh=None, plane=None,
                 threshold: float | None = None,
                 quant: tuple | None = None, cache_from=None,
                 packed: bool = False):
        self.cfg = cfg or ANNConfig()
        self.k = k
        self.stats = ServeStats()
        self._lock = threading.Lock()
        # host-side mutation log (tombstones + delta shard); None while the
        # index is frozen — created lazily by the first add()/delete()
        self.stream = None
        self._mutlock = threading.Lock()   # serializes add/delete/compact
        # (regime, bucket, k, backend, gather_fused, quantization,
        #  plane shape token, stream token) -> executable
        self._compiled: dict = {}
        self.buckets = tuple(sorted(self.cfg.serve_buckets))
        if plane is not None:
            if mesh is not None or graph is not None or quant is not None:
                raise ValueError("plane= already fixes the device layout; "
                                 "mesh=/graph=/quant= only apply when the "
                                 "engine builds its own plane")
            self.plane = plane
        elif mesh is None:
            self.plane = SingleDevicePlane(X, self.cfg, graph=graph,
                                           quant=quant, packed=packed)
        else:
            if graph is not None or quant is not None:
                raise ValueError("mesh mode builds its own sharded graph "
                                 "(and codes); graph=/quant= are only for "
                                 "single-device engines")
            self.plane = MeshPlane(X, self.cfg, mesh)
        self.mesh = getattr(self.plane, "mesh", None)
        if cache_from is not None:
            # serving-replica mode (repro.serve.router): share the donor's
            # compile cache (and its lock — entries are keyed purely on
            # plane-derived state, identical across engines over one plane)
            # so an AOT-primed donor makes every replica start steady-state;
            # stats stay per-engine
            if cache_from.plane is not self.plane:
                raise ValueError(
                    "cache_from shares compiled executables, which bind to "
                    "the plane's operand snapshots; it requires plane= set "
                    "to the donor's own plane")
            self._compiled = cache_from._compiled
            self._lock = cache_from._lock
        self.calibration = None
        self.threshold = threshold
        if (threshold is None
                and getattr(self.cfg, "regime_calibration",
                            "static") == "probe"):
            from repro.ann.dispatch import calibrate
            self.calibration = calibrate(self.plane, self.cfg, k=k)
            self.threshold = self.calibration.threshold

    # -- plane delegation (the engine's device-layout view) -----------------

    @property
    def X(self):
        return self.plane.X

    @property
    def graph(self):
        return self.plane.graph

    @property
    def backend(self) -> str:
        return self.plane.backend

    @property
    def gather_fused(self) -> str:
        return self.plane.gather_fused

    @property
    def _donate(self) -> bool:
        return self.plane.donate

    # -- regime & buckets ---------------------------------------------------

    def regime(self, batch: int) -> str:
        """Paper §4's division threshold — owned by the facade
        (:func:`repro.ann.dispatch.regime_for`) so engine, ``Index``, and
        benchmarks can never disagree on the split.  A calibrated/override
        threshold (see class docstring) replaces the static config value.
        A live delta shard adds its brute-force population to the estimate
        (every query scores every delta row), nudging borderline batches
        into the large regime."""
        return regime_for(self.cfg, batch, threshold=self.threshold,
                          n_delta=self._n_delta())

    def _n_delta(self) -> int:
        stream = self.stream
        return 0 if stream is None else stream.delta.n_alive()

    def bucket_for(self, batch: int) -> int:
        """Smallest ladder bucket >= batch; beyond the ladder, the next
        multiple of the largest bucket (bounded shape variety either way).
        No ladder -> raw batch size (one cache entry per distinct B).
        Rounded up to the plane's batch multiple (a mesh plane splits
        large batches over its query shards)."""
        if not self.buckets:
            bucket = batch
        else:
            bucket = next((b for b in self.buckets if b >= batch), None)
            if bucket is None:
                top = self.buckets[-1]
                bucket = -(-batch // top) * top
        s = self.plane.batch_multiple()
        if s > 1:
            bucket = -(-bucket // s) * s
        return bucket

    def _validate_k(self, k, kind: str) -> int:
        if k is None:
            k = self.k
        if not isinstance(k, int) or isinstance(k, bool) or k <= 0:
            raise ValueError(f"k must be a positive int, got {k!r}")
        if kind == "large" and k > self.cfg.large_ef:
            raise ValueError(
                f"k={k} exceeds large-batch ranking size ef="
                f"{self.cfg.large_ef}; raise cfg.large_ef or lower k")
        if kind == "small" and k > self.cfg.small_t0 * SMALL_WIDTH:
            raise ValueError(
                f"k={k} exceeds small-batch candidate pool t0*width="
                f"{self.cfg.small_t0 * SMALL_WIDTH}; raise cfg.small_t0 "
                "or lower k")
        return k

    # -- compile cache ------------------------------------------------------

    def _get_executable(self, kind: str, bucket: int, k: int,
                        streaming: bool = False):
        """Cached executable for (regime, bucket, k, backend, gather_fused,
        quantization, shape token, stream token); the plane compiles on
        miss.

        The plane's shape token keys the operand generation: a compaction
        that preserves operand shapes leaves the token — and therefore
        every cache entry — valid (zero recompiles across the swap), while
        a shape-changing one naturally misses.  Streaming executables key
        additionally on the delta shard's capacity, which grows
        geometrically, so recompiles are logarithmic in adds.

        Returns (callable taking the padded query batch, compiled_now)."""
        stream_tok = self.plane.stream_token() if streaming else None
        cache_key = (kind, bucket, k, self.backend, self.gather_fused,
                     getattr(self.cfg, "quantization", "none"),
                     self.plane.shape_token(), stream_tok)
        with self._lock:
            hit = self._compiled.get(cache_key)
        if hit is not None:
            return hit, False
        with span("engine.compile", regime=kind, bucket=bucket, k=k):
            if streaming:
                exe = self.plane.compile_stream(kind, bucket, k)
            else:
                exe = self.plane.compile(kind, bucket, k)
        with self._lock:
            # a racing thread may have compiled the same key; keep the first
            prior = self._compiled.get(cache_key)
            if prior is not None:
                return prior, False
            self._compiled[cache_key] = exe
            self.stats.compiles += 1
        return exe, True

    # -- serving ------------------------------------------------------------

    @staticmethod
    def _check_numeric(A, what: str):
        """Reject non-numeric inputs BEFORE jnp.asarray turns them into an
        opaque shape/dtype error deep inside the kernel call."""
        dt = getattr(A, "dtype", None)
        if dt is None:
            A = np.asarray(A)
            dt = A.dtype
        if np.dtype(dt).kind not in "fiu":
            raise ValueError(
                f"{what} must be numeric (float/int), got dtype {dt!r}")
        return A

    def query(self, Q, *, k: int | None = None):
        """Answer a batch: (ids [B, k], dists [B, k]) numpy arrays."""
        call = span("engine.query")
        with call:
            with span("engine.prepare"):
                Q, host, kind, k, bucket = self._prepare(Q, k)
            B = Q.shape[0]
            call.set_metadata(regime=kind, bucket=bucket, queries=B)
            ids, dists = self._execute(Q, host, kind, k, bucket)
            with span("engine.fetch"):
                # padded rows are discarded before any caller-visible
                # merge; slice on host: a mesh plane's outputs carry
                # explicit shardings, which refuse a plain device-side slice
                return np.asarray(ids)[:B], np.asarray(dists)[:B]

    def _prepare(self, Q, k):
        """Checks and shapes one batch: (Q, host, regime, k, bucket).
        ``host`` is the batch edge-padded to its bucket on the host, where
        the plane stages host batches (else None, and ``Q`` a device
        array)."""
        Q_in = Q
        Q = self._check_numeric(Q, "Q")
        host = None
        if (getattr(self.plane, "stage_query", None) is not None
                and not isinstance(Q_in, jax.Array)):
            # host-resident batch on a staging-capable plane: keep it on
            # host, pad there, and let the plane move it through its
            # reusable pinned-host bounce route (one H2D DMA per call)
            Q = host = np.ascontiguousarray(np.asarray(Q, np.float32))
        else:
            Q = (jnp.asarray(Q, jnp.float32) if Q is not Q_in
                 else jnp.asarray(Q))
            if Q.dtype != jnp.float32:
                Q = Q.astype(jnp.float32)
        if Q.ndim != 2 or Q.shape[1] != self.X.shape[1]:
            raise ValueError(
                f"Q must be [B, {self.X.shape[1]}], got {tuple(Q.shape)}")
        B = Q.shape[0]
        if B == 0:
            raise ValueError("empty query batch")
        kind = self.regime(B)
        k = self._validate_k(k, kind)
        bucket = self.bucket_for(B)
        if host is not None and bucket > B:
            # edge-pad on host (bitwise the jnp.pad of the device path:
            # row replication)
            host = np.pad(host, ((0, bucket - B), (0, 0)), mode="edge")
        return Q, host, kind, k, bucket

    def _execute(self, Q, host, kind: str, k: int, bucket: int):
        """Run the (regime, bucket, k) executable on the padded batch until
        its ids are ready, and count the call; returns device (ids, dists)
        of ``bucket`` rows."""
        B = Q.shape[0]
        # dispatch loop: a concurrent compaction/add can swap the plane's
        # generation between executable lookup and call — the stale binding
        # raises StaleGeneration and we re-dispatch against the new token
        # (bounded: generations move monotonically under _mutlock)
        for _ in range(3):
            streaming = self.plane.stream_active
            if host is not None:
                # one staged H2D transfer; the staged array is freshly
                # ours, safe to donate
                Qpad = self.plane.stage_query(host)
            elif bucket > B:
                Qpad = jnp.pad(Q, ((0, bucket - B), (0, 0)), mode="edge")
            elif self._donate:
                # the executable donates its input buffer; never hand it a
                # device array the caller still owns (or our retry reuses)
                Qpad = jnp.copy(Q)
            else:
                Qpad = Q
            exe, compiled_now = self._get_executable(kind, bucket, k,
                                                     streaming)
            t0 = time.perf_counter()
            try:
                with span("engine.execute"):
                    ids, dists = exe(Qpad)
                    ids.block_until_ready()
            except StaleGeneration:
                continue
            break
        else:
            raise RuntimeError(
                "query kept racing generation swaps; mutation rate "
                "outpaces dispatch")
        dt = time.perf_counter() - t0
        with self._lock:
            st = self.stats
            st.n_queries += B
            st.n_batches += 1
            st.padded_queries += bucket - B
            if kind == "small":
                st.small_batches += 1
            else:
                st.large_batches += 1
            if streaming:
                st.stream_batches += 1
            if host is not None:
                st.h2d_staged += 1
                st.h2d_stage_reuses = self.plane.stage_reuses
            if compiled_now:
                st.bucket_misses += 1
            else:
                st.bucket_hits += 1
                st.total_s += dt
                st.steady_queries += B
            st.per_regime[kind].record(B, dt, warmup=compiled_now)
        return ids, dists

    # -- streaming mutability (DESIGN.md §7) --------------------------------

    def add(self, V) -> np.ndarray:
        """Append vectors to the delta shard; returns their global ids
        (``n_base + slot`` — disjoint from every base id and stable until
        the next :func:`repro.ann.compaction.compact`).  Accepts [m, d] or
        a single [d] vector; numeric dtypes are cast to float32."""
        V = self._check_numeric(V, "vectors")
        V = np.asarray(V, np.float32)
        if V.ndim == 1:
            V = V[None]
        d = int(self.X.shape[1])
        if V.ndim != 2 or V.shape[1] != d:
            raise ValueError(
                f"vectors must be [m, {d}] (or a single [{d}] vector), "
                f"got {tuple(V.shape)}")
        if V.shape[0] == 0:
            raise ValueError("empty add batch")
        with self._mutlock:
            stream = self._ensure_stream()
            ids = stream.add(V)
            self._push_stream()
            with self._lock:
                self.stats.n_added += len(ids)
        return ids

    def delete(self, ids) -> int:
        """Tombstone ids (base or delta).  All-or-nothing: unknown,
        out-of-range, duplicate, or already-deleted ids raise KeyError and
        nothing is tombstoned.  Returns the number of ids removed."""
        with self._mutlock:
            stream = self._ensure_stream()
            n = stream.delete(ids)
            self._push_stream()
            with self._lock:
                self.stats.n_deleted += n
        return n

    def n_active(self) -> int:
        """Rows a search can currently return (base + delta − tombstones)."""
        stream = self.stream
        base = int(self.X.shape[0])
        return base if stream is None else stream.n_active()

    def _ensure_stream(self):
        """Lazily create the host-side mutation log (caller holds
        ``_mutlock``)."""
        if self.stream is None:
            from repro.ann.delta import StreamState
            self.stream = StreamState(
                int(self.X.shape[0]), int(self.X.shape[1]),
                min_cap=getattr(self.cfg, "delta_min_cap", 256))
        return self.stream

    def _push_stream(self) -> None:
        """Publish the host-side stream state as device operands (caller
        holds ``_mutlock``)."""
        self.plane.set_stream(*self.stream.device_view())

    def compact(self, *, tile: int = 2048) -> np.ndarray:
        """Fold streamed mutations into a fresh generation
        (:func:`repro.ann.compaction.compact`); returns the old->new id
        map."""
        from repro.ann.compaction import compact
        return compact(self, tile=tile)

    def _prune_stale_entries(self) -> None:
        """Drop cache entries bound to a superseded generation: their
        shape token can never match again (tokens move monotonically), so
        they would only raise StaleGeneration and hold dead arrays alive."""
        tok = self.plane.shape_token()
        with self._lock:
            stale = [key for key in self._compiled if key[6] != tok]
            for key in stale:
                del self._compiled[key]

    def restore_stream(self, base_alive, delta_X, delta_alive) -> None:
        """Re-attach persisted mutation state (artifact format v3 load)."""
        from repro.ann.delta import StreamState
        with self._mutlock:
            self.stream = StreamState.restore(
                base_alive, delta_X, delta_alive,
                min_cap=getattr(self.cfg, "delta_min_cap", 256))
            if self.stream.dirty:
                self._push_stream()
            else:
                self.stream = None

    def warmup_probes(self) -> list:
        """``[(regime, bucket, probe_batch)]`` covering every (regime,
        ladder bucket) pair a real request can reach.  A bucket can be
        reached by both regimes when the regime boundary falls inside its
        range, so each bucket is probed at its smallest and largest mapped
        batch.  This enumeration is shared by :meth:`warmup` and the
        facade's AOT artifact export (``repro.ann.artifact``), so a saved
        index persists exactly the executables warmup would compile."""
        probes, done, prev = [], set(), 0
        for b_raw in self.buckets or (1,):
            # record the bucket a request in this ladder step actually
            # compiles (plane batch-multiple rounding), but keep the probe
            # batches at the RAW ladder step — a rounded probe batch would
            # fall through to the next ladder rung and mislabel the entry
            b = self.bucket_for(b_raw)
            for probe in (prev + 1, b_raw):
                pair = (self.regime(probe), b)
                if pair not in done:
                    done.add(pair)
                    probes.append((pair[0], b, probe))
            prev = b_raw
        return probes

    def warmup(self, k: int | None = None) -> int:
        """Pre-compile every reachable (regime, ladder bucket, k) pair so
        the first real request is steady-state.  Returns the number of
        fresh compiles (0 when a loaded index primed them all)."""
        before = self.stats.compiles
        d = self.X.shape[1]
        for _, _, probe in self.warmup_probes():
            self.query(np.zeros((probe, d), np.float32), k=k)
        return self.stats.compiles - before

    # -- AOT persistence (repro.ann facade: Index.save / Index.load) --------

    def export_executable(self, kind: str, bucket: int,
                          k: int | None = None) -> bytes:
        """Serialize one (regime, bucket, k) serving computation with
        ``jax.export`` — the persistent form of a compile-cache entry.
        Delegates to the plane (each plane owns its export scheme; the mesh
        plane records shardings + device count in the module)."""
        k = self._validate_k(k, kind)
        return self.plane.export(kind, bucket, k)

    def aot_operands(self) -> tuple:
        """The exported modules' leading runtime arguments, in order:
        (X, neighbors, lambdas, degrees[, hubs][, codes, scales]) — the
        padded query batch is appended last by the caller."""
        return self.plane.operands()

    def prime_executable(self, kind: str, bucket: int, k: int,
                         call) -> None:
        """Install a restored executable into the compile cache.

        ``call`` must accept the bucket-padded query batch and return
        (ids, dists) — the same convention :meth:`_get_executable` caches.
        Primed entries count as bucket *hits* (no compile is recorded):
        a loaded index serves its first request steady-state.  AOT blobs
        persist only the frozen (non-streaming) form, so the stream slot of
        the key is always None here; the shape-token slot binds the entry
        to the generation that was saved.
        """
        key = (kind, bucket, k, self.backend, self.gather_fused,
               getattr(self.cfg, "quantization", "none"),
               self.plane.shape_token(), None)
        with self._lock:
            if key not in self._compiled:
                self._compiled[key] = call
                self.stats.aot_primed += 1
