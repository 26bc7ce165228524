"""Pod execution plane — `jax.distributed` multi-process sharded serving.

:class:`PodPlane` extends the mesh plane across OS processes (hosts): the
mesh spans EVERY process's devices, the database + per-shard sub-indexes
are laid out over the global ``data`` axis, and the cross-shard top-k merge
(:func:`repro.core.distributed.merge_topk`) runs inside the shard-mapped
search as a cross-process collective.  Because the plane protocol is the
only seam the serving engine sees, a pod engine inherits the bucketed AOT
compile cache, donation, warmup, streaming snapshots and stats unchanged.

Execution model is SPMD serving: every process runs the same program and
calls ``engine.query`` with the SAME batch (the request router is the front
door that broadcasts requests in a real deployment); collectives inside the
compiled search do the cross-process work, and the replicated output is
materialized identically on every process.  Three multi-process specifics
live here, each an override of a hook the base planes expose:

* operands are assembled with ``jax.make_array_from_callback`` from each
  process's host copy (``_put``) — a plain ``device_put`` cannot address
  other processes' devices;
* the engine's process-local padded query batch is lifted into a global
  replicated array per call (``_place_query``);
* ``fingerprint()``/``topology()`` additionally pin the process count, so
  AOT artifacts saved by a pod are only re-primed on an identical pod.

On CPU, collectives need the gloo backend::

    # one process per host, all pointing at the same coordinator
    init_pod("10.0.0.1:29500", num_processes=4, process_id=i)
    plane = PodPlane(X, cfg)               # mesh over all global devices
    index = Index(None, cfg, k=10, plane=plane)

Registered as ``"pod"`` via the :func:`repro.serve.plane.register_plane`
seam; :func:`repro.serve.plane.get_plane` imports this module lazily so
single-process code never initializes jax.distributed.

One multi-process caveat: ``cfg.regime_calibration="probe"`` fits the
regime threshold from *timed* probe batches, which could diverge across
processes near the split point and desynchronize the SPMD dispatch — pin a
static ``threshold=`` (or ship the saved artifact's calibrated value) on a
pod.

NOTE `jax.distributed.initialize` must run before ANY jax computation, and
several repro modules trace constants at import — so this module defers
every repro (and backend-touching jax) import: ``init_pod`` only needs the
coordinator client, and :class:`PodPlane` itself is built on first
attribute access (PEP 562) rather than at import.
"""
from __future__ import annotations

_INITIALIZED = False


def init_pod(coordinator: str = "localhost:29500", *,
             num_processes: int = 1, process_id: int = 0) -> None:
    """Initialize ``jax.distributed`` for one pod process (idempotent).

    Must run before anything touches the jax backend (device queries and
    traced constants included — import this module FIRST).  On CPU the
    collectives implementation is switched to gloo — the only CPU backend
    that supports cross-process collectives — which is what makes the pod
    plane testable without TPUs."""
    global _INITIALIZED
    if _INITIALIZED:
        return
    if num_processes > 1:
        import jax
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)
    _INITIALIZED = True


def _default_mesh():
    """All global devices on one ``data`` axis: pure DB sharding, queries
    replicated — the layout where every process can hold the full answer."""
    import jax
    return jax.make_mesh((jax.device_count(),), ("data",))


_POD_CLS = None


def _build_pod_class():
    """Define + register :class:`PodPlane` on first use (deferred so that
    importing this module for ``init_pod`` stays free of backend-touching
    imports — see the module docstring)."""
    global _POD_CLS
    if _POD_CLS is not None:
        return _POD_CLS

    import numpy as np

    import jax

    from repro.serve.plane import MeshPlane, register_plane

    class PodPlane(MeshPlane):
        """Cross-process mesh plane (see module docstring).  ``mesh=``
        defaults to every global device on one ``data`` axis; a custom mesh
        may add ``pod``/``data`` DB axes but not a ``model`` (query-
        sharding) axis when spanning processes — pod serving keeps queries
        and answers fully replicated so each process materializes the
        result locally."""

        name = "pod"

        def __init__(self, X, cfg, mesh=None, *, parts: tuple | None = None):
            if jax.process_count() > 1 and mesh is not None:
                from repro.core import distributed as D
                if D.n_query_shards(mesh) > 1:
                    raise ValueError(
                        "the pod plane serves queries replicated (every "
                        "process must hold the full answer); drop the "
                        "'model' axis from the pod mesh")
            super().__init__(X, cfg, mesh if mesh is not None
                             else _default_mesh(), parts=parts)

        # -- multi-process hooks ------------------------------------------

        def _put(self, a, sharding):
            """Assemble a global array from this process's full host copy:
            each process contributes exactly the shards local to it (SPMD —
            every process passes the same host data, so the global array is
            consistent by construction)."""
            a = np.asarray(a)
            return jax.make_array_from_callback(a.shape, sharding,
                                                lambda idx: a[idx])

        def _place_query(self, Qb):
            """Lift the engine's process-local padded batch into the global
            replicated query array the compiled module expects.  Every
            process submits the same batch (SPMD serving), so replication
            is assembly, not communication."""
            return self._put(Qb, self._repl)

        # -- identity -----------------------------------------------------

        def topology(self) -> dict:
            t = super().topology()
            t["n_processes"] = jax.process_count()
            return t

        def fingerprint(self) -> dict:
            fp = super().fingerprint()
            fp["n_processes"] = jax.process_count()
            return fp

    register_plane("pod", lambda X, cfg, **kw: PodPlane(X, cfg, **kw))
    _POD_CLS = PodPlane
    return PodPlane


def __getattr__(name: str):
    if name == "PodPlane":
        return _build_pod_class()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
