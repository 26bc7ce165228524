"""Execution planes — the one seam between the serving engine and devices.

The serving engine (:mod:`repro.serve.engine`) owns everything that is
*traffic-shaped*: the shape-bucket ladder, the (regime, bucket, k) compile
cache, warmup enumeration, micro-batching, stats.  Everything that is
*device-shaped* — where the database lives, how a search computation is
lowered, what a persisted executable must be fingerprinted against — lives
behind the :class:`ExecutionPlane` protocol defined here, with two
registered implementations:

* :class:`SingleDevicePlane` — the default: database + packed graph resident
  on one device, searches lowered from the raw procedures.
* :class:`MeshPlane` — the sharded peer: database + per-shard sub-indexes
  laid out over a device mesh (DESIGN.md §6), searches lowered from the
  shard-mapped procedures of :mod:`repro.core.distributed`.  The mesh, the
  DB/query PartitionSpecs, and the global-id offset logic are owned here,
  so the engine above it is mesh-agnostic: a mesh engine gets per-(regime,
  bucket, k) cached executables, padded-batch donation, AOT persistence and
  percentile stats for free.

Both planes expose the same surface::

    compile(regime, bucket, k) -> executable     # padded Q -> (ids, dists)
    compile_stream(regime, bucket, k) -> executable  # + tombstones & delta
    operands() -> tuple                          # flat AOT runtime args
    fingerprint() -> dict                        # what executables bind to
    shardings() -> dict                          # operand placements
    export(regime, bucket, k) -> bytes           # jax.export serialization
    prime(exported, regime, bucket, k) -> executable   # deserialize + bind

plus ``X``, ``graph``, ``cfg``, ``backend``, ``gather_fused``, ``donate``,
``batch_multiple()`` (bucket divisibility constraint) and ``topology()``
(mesh shape; ``None`` on the single-device plane).  `register_plane()`
accepts third-party planes by name, mirroring the kernel-backend registry
(DESIGN.md §3): the `jax.distributed` pod plane (:mod:`repro.serve.pod`,
DESIGN.md §9) slots in through exactly this seam — registered lazily on
first ``get_plane("pod")`` so single-process imports never touch it.

**Generations & streaming (DESIGN.md §7).**  Every serving computation is
lowered with the database and graph as *runtime arguments* (never closed
over as compile-time constants) and the compiled module is wrapped in a
thin binding that reads the plane's current operand snapshot at call time.
The snapshot — ``(shape token, operand tuple, stream operands or None)`` —
is replaced atomically by :meth:`rebind` (compaction's generation hot-swap)
and :meth:`set_stream` (mutation pushes), so:

* a generation swap that preserves operand shapes re-binds every cached
  executable to the new arrays with ZERO recompiles (the acceptance bar
  ``ServeStats.compiles == 0`` across a swap);
* in-flight calls that already grabbed the old snapshot finish on the old
  immutable arrays — nothing is dropped;
* a swap that *changes* shapes makes stale executables raise
  :class:`StaleGeneration`, which the engine turns into a re-dispatch
  against the new shape token (lazy recompile, never a wrong answer).

``compile_stream`` lowers the mutable-index form: the frozen computation
plus the tombstone ``alive`` mask threaded into the in-kernel keep-masks
and the brute-force delta shard fused by ``distributed.merge_topk``.
Frozen and streaming executables coexist in the engine cache; AOT artifacts
persist only the frozen form (the streaming operands are serving state, not
index payload).
"""
from __future__ import annotations

from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.configs.base import ANNConfig
from repro.core import hotpath
from repro.core.diversify import PackedGraph
from repro.utils.trace import span


class StaleGeneration(RuntimeError):
    """A bound executable's operand shapes no longer match the plane's
    current generation (compaction swapped in a different-shaped corpus, or
    the delta shard grew); the engine re-dispatches against the new token."""


@runtime_checkable
class ExecutionPlane(Protocol):
    """Structural protocol for execution planes (see module docstring)."""

    name: str
    cfg: ANNConfig
    X: jax.Array
    graph: PackedGraph
    backend: str
    gather_fused: str
    donate: bool

    def compile(self, regime: str, bucket: int, k: int):
        """Compiled executable for one (regime, bucket, k): takes the
        bucket-padded query batch as its ONLY argument (donated when
        ``donate``) and returns (ids [bucket, k], dists [bucket, k])."""
        ...

    def operands(self) -> tuple:
        """Flat device-resident runtime arguments of exported modules, in
        order: (X, neighbors, lambdas, degrees[, hubs][, codes, scales]
        [, perm])."""
        ...

    def fingerprint(self) -> dict:
        """What persisted executables were lowered against; compared on
        artifact load (any mismatch -> recompile on demand)."""
        ...

    def shardings(self) -> dict:
        """Operand-name -> sharding placements ({} on a single device)."""
        ...


_PLANES: dict = {}


def register_plane(name: str, factory) -> None:
    """Register a plane factory ``(X, cfg, **kw) -> plane`` under ``name``."""
    _PLANES[name] = factory


def planes() -> tuple:
    return tuple(sorted(_PLANES))


def get_plane(name: str):
    if name == "pod" and name not in _PLANES:
        # the multi-process plane lives in its own module (it must not be
        # imported before jax.distributed is initialized); registering on
        # first lookup keeps single-process imports free of it
        import repro.serve.pod as _pod
        _pod.PodPlane  # noqa: B018 — lazy class build registers "pod"
    try:
        return _PLANES[name]
    except KeyError:
        raise KeyError(f"unknown execution plane {name!r}; "
                       f"registered: {planes()}") from None


def _runtime_fingerprint(plane) -> dict:
    dev = jax.devices()[0]
    return {
        "jax": jax.__version__,
        "platform": jax.default_backend(),
        "device_kind": dev.device_kind,
        "n_devices": jax.device_count(),
        "kernel_backend": plane.backend,
        "gather_fused": plane.gather_fused,
        "plane": plane.name,
        "quantization": getattr(plane.cfg, "quantization", "none"),
        # locality-packed layout + visited filter (DESIGN.md §10): both
        # change the lowered search trace, so persisted executables must
        # not be reused across a flip of either
        "layout": getattr(plane.graph, "perm", None) is not None,
        "visited_filter": getattr(plane.cfg, "visited_filter", "none"),
    }


def _token_of(ops) -> tuple:
    """Shape/dtype token of an operand tuple: equality means a compiled
    module lowered against one tuple can run against the other."""
    return tuple((tuple(a.shape), str(a.dtype)) for a in ops)


class _SnapshotPlane:
    """Shared generation-snapshot machinery for both planes.

    ``self._snap = (token, operands, stream_or_None)`` is the plane's whole
    mutable state, replaced wholesale (one attribute store — atomic under
    the GIL) so concurrent queries always read a coherent generation.
    """

    _snap: tuple

    # -- snapshot accessors -------------------------------------------------

    @property
    def quantized(self) -> bool:
        """Compressed residency on (DESIGN.md §8): the operand tuple and
        the stream tuple carry int8 codes + fp32 scales after the fp32
        arrays, and searches score/re-rank through them."""
        return getattr(self.cfg, "quantization", "none") == "int8"

    def operands(self) -> tuple:
        return self._snap[1]

    def shape_token(self) -> tuple:
        return self._snap[0]

    def stream_token(self):
        """Delta-shard capacity of the attached stream state (None when the
        index is frozen) — part of the engine's streaming cache key."""
        stream = self._snap[2]
        return None if stream is None else (int(stream[1].shape[0]),)

    @property
    def stream_active(self) -> bool:
        return self._snap[2] is not None

    def clear_stream(self) -> None:
        token, ops, _ = self._snap
        self._snap = (token, ops, None)

    # -- executable binding -------------------------------------------------

    def _place_query(self, Qb):
        """Hook: place the engine's (process-local) padded query batch where
        the compiled module expects it.  Identity for in-process planes; the
        multi-process pod plane lifts it into a global replicated array."""
        return Qb

    def _bind(self, raw, token, *, stream_cap=None):
        """Wrap a compiled module (over flat operand args + Q) into the
        engine-facing single-argument form.  The wrapper reads the CURRENT
        snapshot per call, so a same-shape generation swap re-binds every
        cached executable for free; shape drift raises StaleGeneration."""
        def call(Qb):
            tok, ops, stream = self._snap
            if tok != token:
                raise StaleGeneration(
                    "executable lowered for a previous generation's operand "
                    "shapes; re-dispatch against the new shape token")
            Qb = self._place_query(Qb)
            if stream_cap is None:
                return raw(*ops, Qb)
            if stream is None or int(stream[1].shape[0]) != stream_cap:
                raise StaleGeneration(
                    "stream operands detached or delta capacity changed; "
                    "re-dispatch")
            return raw(*ops, *stream, Qb)
        return call

    def _op_specs(self) -> tuple:
        return tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                     for a in self.operands())

    def _stream_specs(self) -> tuple:
        stream = self._snap[2]
        return tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in stream)

    def _require_stream(self):
        stream = self._snap[2]
        if stream is None:
            raise RuntimeError(
                "no stream state attached (set_stream() installs the "
                "tombstone mask + delta shard before compile_stream)")
        return stream


# ==========================================================================
# single-device plane
# ==========================================================================

# small_batch_search's compiled-in ranking width (its `width` kwarg
# default): the per-query candidate pool is t0 * width entries
SMALL_WIDTH = 32


class SingleDevicePlane(_SnapshotPlane):
    """Database + graph on one device; searches lowered from the raw
    procedures.  Mutation state (tombstones + delta shard) and generation
    swaps ride on the snapshot machinery of :class:`_SnapshotPlane`."""

    name = "single"

    def __init__(self, X, cfg: ANNConfig, *, graph: PackedGraph | None = None,
                 quant: tuple | None = None, packed: bool = False):
        self.cfg = cfg
        # reusable pinned-host H2D staging routes (see stage_query); the
        # route kind the last one took: "pinned_host" or "device_put"
        self._stage_puts = {}
        self.stage_reuses = 0
        self.stage_route = None
        # kernel backend resolved once per plane; part of the engine's AOT
        # cache key so an engine rebuilt with a different backend never
        # aliases entries
        self.backend = hotpath.resolve_backend(
            getattr(cfg, "kernel_backend", "auto"))
        self.gather_fused = getattr(cfg, "gather_fused", "auto")
        # donate the bucket-padded query buffer into each dispatch so steady
        # state reuses its HBM instead of re-allocating per call; skipped on
        # CPU where XLA cannot alias the input (it would warn every call)
        self.donate = jax.default_backend() != "cpu"
        X = jnp.asarray(X)
        if graph is None:
            from repro.ann.pipeline import build_graph
            graph = build_graph(X, cfg)
        self._install(X, graph, stream=None, quant=quant, packed=packed)

    def _install(self, X, graph, *, stream, quant=None,
                 packed: bool = False) -> None:
        """Swap in a generation.  ``X`` (and ``quant`` rows, if given)
        arrive in EXTERNAL row order and are packed here when the graph
        carries a locality permutation (DESIGN.md §10) — ``packed=True``
        (artifact load) says they are already in packed order."""
        perm = getattr(graph, "perm", None)
        if perm is not None and not packed:
            X = jnp.take(X, perm, axis=0)
            if quant is not None:
                quant = (jnp.take(jnp.asarray(quant[0]), perm, axis=0),
                         jnp.take(jnp.asarray(quant[1]), perm, axis=0))
        self.X = X
        self.graph = graph
        if self.quantized:
            if quant is None:  # build / compaction; artifact load passes it
                from repro.ann.quantize import quantize_rows
                quant = quantize_rows(X)
            self.codes, self.scales = (jnp.asarray(quant[0]),
                                       jnp.asarray(quant[1]))
        else:
            self.codes = self.scales = None
        ops = (X, graph.neighbors, graph.lambdas, graph.degrees)
        if graph.hubs is not None:
            ops = ops + (graph.hubs,)
        if self.quantized:
            ops = ops + (self.codes, self.scales)
        if perm is not None:
            ops = ops + (perm,)  # rides last; tokenized like any operand
        self._snap = (_token_of(ops), ops, stream)

    # -- generations & streaming -------------------------------------------

    def rebind(self, X, graph) -> None:
        """Hot-swap to a new generation's corpus + graph (compaction).
        Clears stream state; cached executables whose shapes still match
        keep serving against the new arrays with zero recompiles, and
        in-flight calls finish on the old (immutable) arrays.  A quantized
        plane re-quantizes the new generation's rows here."""
        self._install(jnp.asarray(X), graph, stream=None)

    def set_stream(self, alive, delta_X, delta_alive) -> None:
        """Attach/refresh the streaming operands: ``alive`` [N] bool
        (base-corpus tombstone mask), ``delta_X`` [cap, d] float32,
        ``delta_alive`` [cap] bool (unfilled/tombstoned delta slots).
        A quantized plane appends per-row int8 codes + scales of the delta
        shard (delta_X stays fp32 for the exact re-rank)."""
        token, ops, _ = self._snap
        stream = (jnp.asarray(alive), jnp.asarray(delta_X),
                  jnp.asarray(delta_alive))
        if self.quantized:
            from repro.ann.quantize import quantize_rows
            stream = stream + quantize_rows(stream[1])
        self._snap = (token, ops, stream)

    # -- engine-facing geometry --------------------------------------------

    def batch_multiple(self) -> int:
        return 1

    def topology(self) -> dict | None:
        return None

    def shardings(self) -> dict:
        return {}

    def fingerprint(self) -> dict:
        return _runtime_fingerprint(self)

    # -- H2D staging --------------------------------------------------------

    def _make_stage(self, shape, dtype):
        """Build the staging route for one (shape, dtype): host numpy ->
        pinned-host buffer -> one device DMA.  Falls back to a plain
        ``device_put`` where the runtime has no pinned-host memory space
        (CPU, interpret-mode test rigs)."""
        dev = jax.devices()[0]
        try:
            pin = jax.sharding.SingleDeviceSharding(
                dev, memory_kind="pinned_host")
            dst = jax.sharding.SingleDeviceSharding(dev)
            # probe: raises on runtimes without a pinned_host space
            jax.device_put(jnp.zeros((1,), dtype), pin).block_until_ready()

            def put(Qh):
                return jax.device_put(jax.device_put(Qh, pin), dst)
            self.stage_route = "pinned_host"
            return put
        except Exception:  # noqa: BLE001 — capability probe
            self.stage_route = "device_put"
            return lambda Qh: jax.device_put(jnp.asarray(Qh), dev)

    def stage_query(self, Qh):
        """Stage a host query batch onto the device through a reusable
        pinned-host bounce route (ROADMAP "H2D staging").  One route is
        kept per (shape, dtype) — the engine's bucket ladder makes repeats
        the steady state — and every re-hit increments ``stage_reuses``
        (surfaced as ``ServeStats.h2d_stage_reuses``, the proof that
        steady-state traffic reuses the staging buffer instead of setting
        up a fresh transfer path per call)."""
        with span("plane.stage"):
            key = (tuple(Qh.shape), str(Qh.dtype))
            put = self._stage_puts.get(key)
            if put is None:
                put = self._stage_puts[key] = self._make_stage(Qh.shape,
                                                               Qh.dtype)
            else:
                self.stage_reuses += 1
            return put(Qh)

    # -- lowering -----------------------------------------------------------

    def _search_args(self, kind: str, k: int):
        """(procedure, static kwargs) for one regime at one k."""
        from repro.core.search_large import _large_batch_search
        from repro.core.search_small import _small_batch_search

        cfg = self.cfg
        visited = getattr(cfg, "visited_filter", "none")
        if kind == "small":
            kwargs = dict(k=k, t0=cfg.small_t0, hops=cfg.small_hops,
                          hop_width=cfg.hop_width, n_seeds=cfg.n_seeds,
                          lambda_limit=10, metric=cfg.metric,
                          visited=visited,
                          backend=self.backend,
                          gather_fused=self.gather_fused)
            return _small_batch_search, kwargs
        kwargs = dict(k=k, ef=cfg.large_ef, hops=cfg.large_hops,
                      lambda_limit=5, metric=cfg.metric,
                      n_seeds=getattr(cfg, "large_n_seeds", cfg.n_seeds),
                      m_seg=cfg.queue_segments, seg=cfg.segment_size,
                      mv_seg=cfg.visited_segments, delta=cfg.delta,
                      visited=visited,
                      backend=self.backend,
                      gather_fused=self.gather_fused)
        return _large_batch_search, kwargs

    def _qspec(self, bucket: int):
        return jax.ShapeDtypeStruct((bucket, self.X.shape[1]), jnp.float32)

    def _flat_search(self, kind: str, k: int):
        """The operand-parameterized serving computation: flat array args
        ``(X, neighbors, lambdas, degrees[, hubs][, codes, scales], Qb)``
        -> (ids, dists).  The same trace :meth:`export` serializes, so
        primed and locally compiled executables answer identically
        (bitwise contract)."""
        fn, kwargs = self._search_args(kind, k)
        has_hubs = self.graph.hubs is not None
        has_perm = self.graph.perm is not None
        n_base = 5 if has_hubs else 4
        quantized = self.quantized
        i_perm = n_base + (2 if quantized else 0)  # perm rides last
        rerank_mult = getattr(self.cfg, "rerank_mult", 4)

        def call(*args):
            Xa, nbrs, lams, degs = args[:4]
            g = PackedGraph(neighbors=nbrs, lambdas=lams, degrees=degs,
                            hubs=args[4] if has_hubs else None,
                            perm=args[i_perm] if has_perm else None)
            extra = dict(codes=args[n_base], scales=args[n_base + 1],
                         rerank_mult=rerank_mult) if quantized else {}
            return fn(Xa, g, args[-1], **kwargs, **extra)
        return call

    def compile(self, kind: str, bucket: int, k: int):
        """The database and graph are runtime ARGUMENTS of the compiled
        module (see module docstring: generation swaps re-bind, not
        recompile); only the bucket-padded query buffer is donated
        (ROADMAP "Donated buffers") so steady-state serving reuses its
        device memory instead of re-allocating per call."""
        specs = self._op_specs()
        wrapped = jax.jit(
            self._flat_search(kind, k),
            donate_argnums=(len(specs),) if self.donate else ())
        raw = wrapped.lower(*specs, self._qspec(bucket)).compile()
        return self._bind(raw, self.shape_token())

    def compile_stream(self, kind: str, bucket: int, k: int):
        """The mutable-index serving computation (DESIGN.md §7): the base
        graph search with the tombstone mask threaded into its in-kernel
        keep-masks, a brute-force scan of the delta shard, and one
        ``merge_topk`` fuse.  Delta rows answer at global ids
        ``N + slot``; rows with fewer than k live candidates pad with
        (PAD_ID, INF).  Keyed by delta capacity in the engine cache — the
        shard grows geometrically, so recompiles are logarithmic in the
        number of added vectors."""
        from repro.core.distributed import PAD_ID, merge_topk

        stream = self._require_stream()
        cap = int(stream[1].shape[0])
        fn, kwargs = self._search_args(kind, k)
        has_hubs = self.graph.hubs is not None
        has_perm = self.graph.perm is not None
        n_base = 5 if has_hubs else 4
        i_perm = n_base + (2 if self.quantized else 0)
        n_ops = len(self.operands())
        N = int(self.X.shape[0])
        metric = self.cfg.metric
        backend = self.backend
        gather_fused = self.gather_fused
        quantized = self.quantized
        rerank_mult = getattr(self.cfg, "rerank_mult", 4)
        INF = hotpath.INF

        def call(*args):
            Xa, nbrs, lams, degs = args[:4]
            g = PackedGraph(neighbors=nbrs, lambdas=lams, degrees=degs,
                            hubs=args[4] if has_hubs else None,
                            perm=args[i_perm] if has_perm else None)
            Qb = args[-1]
            extra = dict(codes=args[n_base], scales=args[n_base + 1],
                         rerank_mult=rerank_mult) if quantized else {}
            al, dX, dal = args[n_ops:n_ops + 3]
            bids, bd = fn(Xa, g, Qb, alive=al, **kwargs, **extra)
            valid = (bids < N) & (bd < INF)
            pool_i = jnp.where(valid, bids, PAD_ID)
            pool_d = jnp.where(valid, bd, INF)
            if quantized:
                # approximate scan of the int8 delta codes, then exact
                # fp32 re-score of the best rerank_mult*k slots — the
                # same approx->exact pipeline the base search runs
                dcodes, dscales = args[n_ops + 3:n_ops + 5]
                dd = hotpath.scan_distances(Qb, dcodes, metric=metric,
                                            mask=dal, backend=backend,
                                            scales=dscales)
                r = min(rerank_mult * k, cap)
                slots = jnp.broadcast_to(
                    jnp.arange(cap, dtype=jnp.int32)[None], dd.shape)
                # dead/unfilled lanes are already INF from the masked scan
                sd, ss = hotpath.rank_merge(dd, slots, keep=r,
                                            backend=backend)
                ed = hotpath.neighbor_distances(
                    Qb, dX, ss, metric=metric, mask=sd < INF,
                    backend=backend, gather_fused=gather_fused)
                d_ids = jnp.where(ed < INF, N + ss, PAD_ID)
                all_i = jnp.concatenate([pool_i, d_ids], axis=1)
                all_d = jnp.concatenate([pool_d, ed], axis=1)
                return merge_topk(all_i, all_d, k)
            dd = hotpath.scan_distances(Qb, dX, metric=metric, mask=dal,
                                        backend=backend)
            d_ids = jnp.where(dal, N + jnp.arange(cap, dtype=jnp.int32),
                              PAD_ID)
            all_i = jnp.concatenate(
                [pool_i, jnp.broadcast_to(d_ids[None], dd.shape)], axis=1)
            all_d = jnp.concatenate(
                [pool_d, jnp.where(dal[None], dd, INF)], axis=1)
            return merge_topk(all_i, all_d, k)

        specs = self._op_specs() + self._stream_specs()
        wrapped = jax.jit(
            call, donate_argnums=(len(specs),) if self.donate else ())
        raw = wrapped.lower(*specs, self._qspec(bucket)).compile()
        return self._bind(raw, self.shape_token(), stream_cap=cap)

    # -- AOT persistence ----------------------------------------------------

    def export(self, kind: str, bucket: int, k: int) -> bytes:
        """Serialize one (regime, bucket, k) serving computation with
        ``jax.export`` — the persistent form of a compile-cache entry.

        The database and packed graph are *arguments* of the exported
        module (not embedded constants), so blobs stay graph-independent
        small and one artifact can hold many entries.  Bitwise contract:
        the exported module is lowered from the same trace :meth:`compile`
        compiles, so a primed executable answers identically to a
        locally-compiled one.  Only the frozen form is exported — stream
        state is serving state, persisted separately by the artifact's
        ``streaming`` payload (format v3)."""
        from jax import export as jax_export
        specs = self._op_specs()
        exported = jax_export.export(jax.jit(self._flat_search(kind, k)))(
            *specs, self._qspec(bucket))
        return bytes(exported.serialize())

    def prime(self, exported, kind: str, bucket: int, k: int):
        """Compile a deserialized module back into the snapshot-bound
        single-argument executable form the engine's cache expects."""
        specs = self._op_specs()
        fn = jax.jit(lambda *args: exported.call(*args),
                     donate_argnums=(len(specs),) if self.donate else ())
        raw = fn.lower(*specs, self._qspec(bucket)).compile()
        return self._bind(raw, self.shape_token())


# ==========================================================================
# mesh plane
# ==========================================================================

class MeshPlane(_SnapshotPlane):
    """Database + per-shard sub-indexes over a device mesh; searches lowered
    from the shard-mapped procedures (:mod:`repro.core.distributed`).

    Owns the mesh, the DB/query PartitionSpecs, and (via the distributed
    search bodies) the global-id offset logic.  ``parts=`` accepts prebuilt
    device-resident ``(X, neighbors, lambdas, degrees, hubs)`` — how the
    artifact loader restores a sharded index without rebuilding.  Streaming
    operands place the tombstone mask row-sharded with the database and the
    delta shard replicated (every shard scores it; ``merge_topk``'s id
    dedup collapses the copies).
    """

    name = "mesh"

    def __init__(self, X, cfg: ANNConfig, mesh, *, parts: tuple | None = None):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.core import distributed as D
        self._D = D
        self._P = P
        self._NamedSharding = NamedSharding
        self.cfg = cfg
        self.mesh = mesh
        self.backend = hotpath.resolve_backend(
            getattr(cfg, "kernel_backend", "auto"))
        self.gather_fused = getattr(cfg, "gather_fused", "auto")
        self.donate = jax.default_backend() != "cpu"
        d_ax = D.db_axes(mesh)
        if not d_ax:
            raise ValueError(
                f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} has "
                "no DB axis; name one of its axes 'data' (and optionally "
                "'pod'/'model')")
        self.n_db_shards = D.n_db_shards(mesh)
        self.n_q_shards = D.n_query_shards(mesh)
        self._db2 = NamedSharding(mesh, P(d_ax, None))   # [N, *] row-sharded
        self._db1 = NamedSharding(mesh, P(d_ax))         # [N] row-sharded
        self._repl = NamedSharding(mesh, P(None, None))
        self._repl1 = NamedSharding(mesh, P(None))
        self._qsharded = NamedSharding(mesh, P(D.query_axes(mesh) or None,
                                               None))
        if parts is None:
            Xs = self._put(X, self._db2)
            built = D.make_build_fn(mesh, cfg)(Xs)
            jax.block_until_ready(built[0])
            Xs, built = self._host_layout(Xs, built)
            parts = (Xs,) + tuple(built)
        self._install(parts[0], parts[1:], stream=None)

    def _put(self, a, sharding):
        """Hook: lay a host array out over the mesh.  ``device_put`` when
        every device is process-local; the pod plane overrides this with a
        per-process ``make_array_from_callback`` assembly (a device_put to
        non-addressable devices is illegal in multi-process jax)."""
        return jax.device_put(jnp.asarray(a), sharding)

    def _quantize_sharded(self, Xs):
        """Per-row codes + scales, row-sharded alongside the database (the
        quantization is row-local, so no cross-shard traffic)."""
        from repro.ann.quantize import quantize_rows
        return jax.jit(quantize_rows,
                       out_shardings=(self._db2, self._db1))(Xs)

    def _host_layout(self, Xs, built):
        """Per-shard locality packing (DESIGN.md §10).  The traced shard
        build cannot run the host-BFS "layout" stage (it is stripped from
        the shard_map pipeline by ``distributed.make_build_fn``), so a
        layout config packs here instead: pull each shard's sub-index to
        host, BFS-order its LOCAL ids, relabel, and lay the packed arrays
        (plus the [N] shard-local perm operand) back over the mesh.  The
        ``ids + offset`` global-id composition in the distributed search is
        untouched because the search procedures translate back to
        external-local ids before returning."""
        pipeline = tuple(getattr(self.cfg, "build_pipeline", ()) or ())
        if "layout" not in pipeline:
            return Xs, tuple(built)
        import numpy as np

        from repro.ann import layout as L

        def host(a):
            if isinstance(a, jax.Array) and not a.is_fully_addressable:
                from jax.experimental import multihost_utils
                return np.asarray(
                    multihost_utils.process_allgather(a, tiled=True))
            return np.asarray(jax.device_get(a))

        X_h = host(Xs)
        nbrs, lams, degs, hubs = (host(a) for a in built)
        nsh = self.n_db_shards
        n_local = X_h.shape[0] // nsh
        nh = hubs.shape[0] // nsh if hubs.shape[0] else 0
        outs = {"X": [], "nbrs": [], "lams": [], "degs": [], "hubs": [],
                "perm": []}
        for i in range(nsh):
            lo = i * n_local
            hub_i = hubs[i * nh:(i + 1) * nh] if nh else None
            nb_i = nbrs[lo:lo + n_local]
            perm_i = L.locality_order(nb_i, starts=hub_i)
            X2, nb2, lam2, deg2, hub2 = L.apply_layout(
                perm_i, X_h[lo:lo + n_local], nb_i,
                lams[lo:lo + n_local], degs[lo:lo + n_local], hubs=hub_i)
            outs["X"].append(X2)
            outs["nbrs"].append(nb2)
            outs["lams"].append(lam2)
            outs["degs"].append(deg2)
            outs["hubs"].append(hub2 if hub2 is not None
                                else np.zeros((0,), np.int32))
            outs["perm"].append(perm_i)
        cat = {k: np.concatenate(v, axis=0) for k, v in outs.items()}
        return (self._put(cat["X"], self._db2),
                (self._put(cat["nbrs"], self._db2),
                 self._put(cat["lams"], self._db2),
                 self._put(cat["degs"], self._db1),
                 self._put(cat["hubs"], self._db1),
                 self._put(cat["perm"].astype(np.int32), self._db1)))

    def _install(self, Xs, parts, *, stream) -> None:
        parts = tuple(parts)
        # perm (layout configs) rides LAST in the operand tuple, after any
        # quantization extras — same convention as the single plane
        has_layout = "layout" in tuple(
            getattr(self.cfg, "build_pipeline", ()) or ())
        perm = None
        if has_layout:
            perm = parts[-1]
            parts = parts[:-1]
        if self.quantized and len(parts) == 4:
            # built fresh / restored from a pre-v4 artifact: derive the
            # codes here (a v4 artifact restores them via parts directly).
            # Xs is already in packed order, so the row-local codes are too.
            parts = parts + self._quantize_sharded(Xs)
        if perm is not None:
            parts = parts + (perm,)
        nbrs, lams, degs, hubs = parts[:4]
        self.X = Xs
        self._parts = parts
        self.graph = PackedGraph(
            neighbors=nbrs, lambdas=lams, degrees=degs,
            hubs=hubs if hubs.shape[0] else None, perm=perm)
        ops = (Xs, *parts)
        self._snap = (_token_of(ops), ops, stream)

    # -- generations & streaming -------------------------------------------

    def rebind(self, X) -> None:
        """Hot-swap to a new generation: re-lay the corpus over the mesh
        and rebuild the shard-local sub-indexes — the same device_put +
        shard-mapped build a fresh mesh plane runs, so the swapped-in state
        is bitwise a fresh build's (compaction's parity bar)."""
        Xs = self._put(X, self._db2)
        built = self._D.make_build_fn(self.mesh, self.cfg)(Xs)
        jax.block_until_ready(built[0])
        Xs, built = self._host_layout(Xs, built)
        self._install(Xs, built, stream=None)

    def set_stream(self, alive, delta_X, delta_alive) -> None:
        """Tombstone mask row-sharded like ``degrees``; delta shard
        replicated across every DB shard (codes + scales too when
        quantized — every shard runs the identical delta selection, and
        ``merge_topk``'s id dedup collapses the copies)."""
        token, ops, _ = self._snap
        stream = (
            self._put(alive, self._db1),
            self._put(delta_X, self._repl),
            self._put(delta_alive, self._repl1))
        if self.quantized:
            from repro.ann.quantize import quantize_rows
            # quantize on host inputs so the codes can be laid out via
            # _put (works for both the single-process and pod planes)
            dcodes, dscales = quantize_rows(jnp.asarray(delta_X))
            stream = stream + (
                self._put(dcodes, self._repl),
                self._put(dscales, self._repl1))
        self._snap = (token, ops, stream)

    # -- engine-facing geometry --------------------------------------------

    def batch_multiple(self) -> int:
        """Sharded large-batch search splits B over the model axis, so
        buckets must divide evenly across the query shards."""
        return self.n_q_shards

    def topology(self) -> dict:
        """Mesh shape persisted in the artifact manifest and compared on
        load: ``n_db_shards`` gates sub-index reuse, the full axis map
        (+ device count, via the fingerprint) gates AOT executable reuse."""
        return {
            "axes": {name: int(size) for name, size in
                     zip(self.mesh.axis_names, self.mesh.devices.shape)},
            "n_db_shards": self.n_db_shards,
            "n_q_shards": self.n_q_shards,
        }

    def shardings(self) -> dict:
        return {"X": self._db2, "neighbors": self._db2, "lambdas": self._db2,
                "degrees": self._db1, "hubs": self._db1, "perm": self._db1,
                "codes": self._db2, "scales": self._db1,
                "alive": self._db1, "delta_X": self._repl,
                "delta_alive": self._repl1, "delta_codes": self._repl,
                "delta_scales": self._repl1,
                "query_small": self._repl, "query_large": self._qsharded}

    def fingerprint(self) -> dict:
        fp = _runtime_fingerprint(self)
        fp["mesh_axes"] = self.topology()["axes"]
        return fp

    def query_sharding(self, kind: str):
        """Small-regime queries are replicated (the t0 population splits
        over `model` instead); large-regime queries shard over `model`."""
        return self._repl if kind == "small" else self._qsharded

    # -- lowering -----------------------------------------------------------

    def _qspec(self, kind: str, bucket: int):
        return jax.ShapeDtypeStruct((bucket, self.X.shape[1]), jnp.float32,
                                    sharding=self.query_sharding(kind))

    def _sharded_specs(self, arrays, shardings) -> tuple:
        return tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
                     for a, s in zip(arrays, shardings))

    def compile(self, kind: str, bucket: int, k: int):
        fn = self._D.make_search_fn(self.mesh, self.cfg, kind=kind, k=k)
        specs = self._sharded_specs(self.operands(),
                                    self._operand_shardings())
        wrapped = jax.jit(
            fn, donate_argnums=(len(specs),) if self.donate else ())
        raw = wrapped.lower(*specs, self._qspec(kind, bucket)).compile()
        return self._bind(raw, self.shape_token())

    def compile_stream(self, kind: str, bucket: int, k: int):
        stream = self._require_stream()
        cap = int(stream[1].shape[0])
        fn = self._D.make_search_fn(self.mesh, self.cfg, kind=kind, k=k,
                                    stream=True)
        stream_sh = (self._db1, self._repl, self._repl1)
        if self.quantized:
            stream_sh = stream_sh + (self._repl, self._repl1)
        specs = self._sharded_specs(
            self.operands() + stream,
            self._operand_shardings() + stream_sh)
        wrapped = jax.jit(
            fn, donate_argnums=(len(specs),) if self.donate else ())
        raw = wrapped.lower(*specs, self._qspec(kind, bucket)).compile()
        return self._bind(raw, self.shape_token(), stream_cap=cap)

    # -- AOT persistence ----------------------------------------------------

    def export(self, kind: str, bucket: int, k: int) -> bytes:
        """jax.export of the shard-mapped computation.  The exported module
        records the input shardings and logical device count; it can only
        be re-bound on a mesh of identical shape (gated by the fingerprint
        + topology check at load)."""
        from jax import export as jax_export
        fn = self._D.make_search_fn(self.mesh, self.cfg, kind=kind, k=k)
        specs = self._sharded_specs(self.operands(),
                                    self._operand_shardings())
        exported = jax_export.export(jax.jit(fn))(
            *specs, self._qspec(kind, bucket))
        return bytes(exported.serialize())

    def prime(self, exported, kind: str, bucket: int, k: int):
        specs = self._sharded_specs(self.operands(),
                                    self._operand_shardings())
        fn = jax.jit(lambda *args: exported.call(*args),
                     donate_argnums=(len(specs),) if self.donate else ())
        raw = fn.lower(*specs, self._qspec(kind, bucket)).compile()
        return self._bind(raw, self.shape_token())

    def _operand_shardings(self) -> tuple:
        base = (self._db2, self._db2, self._db2, self._db1, self._db1)
        if self.quantized:
            base = base + (self._db2, self._db1)
        if self.graph.perm is not None:
            base = base + (self._db1,)  # shard-local perm, row-sharded
        return base


register_plane("single", lambda X, cfg, **kw: SingleDevicePlane(X, cfg, **kw))
register_plane("mesh", lambda X, cfg, **kw: MeshPlane(X, cfg, **kw))
