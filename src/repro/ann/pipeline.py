"""Staged, pluggable index-build pipeline (knn -> diversify -> bridges).

:func:`build_graph` is the facade's build path: it runs the named stages of
``cfg.build_pipeline`` over a shared :class:`BuildState` and returns the
:class:`~repro.core.diversify.PackedGraph`.  The default stages reproduce
the paper's build exactly — bit-for-bit the same graph the old
``build_tsdg`` entry point produced (that function is now a thin shim over
this pipeline):

  * ``"knn"``       — NN-expansion k-NN graph (skipped when the caller
    supplies a precomputed ``knn_ids``/``knn_dists`` pair);
  * ``"diversify"`` — the paper's §3 two-stage diversification: relaxed GD
    (Eq. 2) -> symmetrize (reverse edges) -> soft GD occlusion factors,
    λ-sorted and truncated to ``max_degree``;
  * ``"bridges"``   — beyond-paper hub cross-links (no-op when
    ``cfg.bridge_hubs == 0``).

Third-party stages plug in with :func:`register_stage`, mirroring the
kernel-backend registry in :mod:`repro.core.hotpath`: a stage is a callable
``stage(state) -> None`` mutating the :class:`BuildState` in place, and a
config selects it by name via ``cfg.build_pipeline``.
"""
from __future__ import annotations

import dataclasses
import difflib
import logging
import time

import jax
import jax.numpy as jnp

from repro.core import metrics as M
from repro.core.diversify import (PackedGraph, add_bridges, append_reverse,
                                  relaxed_gd, soft_gd)
from repro.core.knn_build import nn_descent

log = logging.getLogger(__name__)


@dataclasses.dataclass
class BuildState:
    """Mutable scratch shared by the stages of one build.

    ``X`` is metric-preprocessed; stages communicate through the optional
    fields (``knn`` fills ``knn_ids``/``knn_dists``, ``diversify`` fills the
    packed arrays, later stages may rewrite them).
    """

    X: jax.Array
    cfg: object
    tile: int = 2048
    unroll: bool = False
    backend: str = "auto"
    gather_fused: str | None = None
    knn_ids: jax.Array | None = None
    knn_dists: jax.Array | None = None
    neighbors: jax.Array | None = None
    lambdas: jax.Array | None = None
    degrees: jax.Array | None = None
    hubs: jax.Array | None = None
    perm: jax.Array | None = None  # [N] int32 new->old ("layout" stage)


# --------------------------------------------------------------------------
# stage registry (mirrors hotpath.register_backend)
# --------------------------------------------------------------------------

_STAGES: dict = {}


def register_stage(name: str, fn=None):
    """Register a build stage; usable directly or as a decorator.

    A stage is ``fn(state: BuildState) -> None`` and becomes selectable by
    name in ``cfg.build_pipeline`` / ``Index.build(stages=...)``.
    """
    if fn is None:
        def deco(f):
            _STAGES[name] = f
            return f
        return deco
    _STAGES[name] = fn
    return fn


def build_stages() -> tuple:
    """Registered stage names, sorted."""
    return tuple(sorted(_STAGES))


def get_stage(name: str):
    """Stage callable for ``name``; unknown names suggest close matches."""
    try:
        return _STAGES[name]
    except KeyError:
        close = difflib.get_close_matches(name, _STAGES, n=3, cutoff=0.5)
        hint = f"; did you mean {', '.join(close)}?" if close else ""
        raise KeyError(f"unknown build stage {name!r}{hint}; "
                       f"registered: {build_stages()}") from None


# --------------------------------------------------------------------------
# default stages — the paper's build, factored
# --------------------------------------------------------------------------

@register_stage("knn")
def _stage_knn(s: BuildState) -> None:
    """NN-expansion k-NN graph; respects caller-precomputed lists."""
    if s.knn_ids is None:
        s.knn_ids, s.knn_dists = nn_descent(
            s.X, s.cfg.k_graph, metric=s.cfg.metric, unroll=s.unroll,
            backend=s.backend, gather_fused=s.gather_fused)


@register_stage("diversify")
def _stage_diversify(s: BuildState) -> None:
    """Paper §3: relaxed GD -> symmetrize -> soft GD (λ-sorted, truncated)."""
    cfg = s.cfg
    keep = relaxed_gd(s.X, s.knn_ids, s.knn_dists, alpha=cfg.alpha,
                      metric=cfg.metric, tile=s.tile, unroll=s.unroll,
                      backend=s.backend, gather_fused=s.gather_fused)
    adj_ids, adj_d = append_reverse(s.X, s.knn_ids, s.knn_dists, keep,
                                    rev_cap=cfg.k_graph, metric=cfg.metric,
                                    backend=s.backend,
                                    gather_fused=s.gather_fused)
    s.neighbors, s.lambdas, s.degrees = soft_gd(
        s.X, adj_ids, adj_d, lambda0=cfg.lambda0,
        max_degree=cfg.max_degree, metric=cfg.metric, tile=s.tile,
        unroll=s.unroll, backend=s.backend, gather_fused=s.gather_fused)


@register_stage("bridges")
def _stage_bridges(s: BuildState) -> None:
    """Beyond-paper hub cross-links; no-op when ``cfg.bridge_hubs == 0``."""
    cfg = s.cfg
    n_hubs = getattr(cfg, "bridge_hubs", 0)
    if not n_hubs:
        return
    N = s.X.shape[0]
    n_hubs = min(n_hubs, N // 4)
    hub_k = min(getattr(cfg, "bridge_k", 8), cfg.max_degree // 2)
    s.neighbors, s.lambdas, s.hubs = add_bridges(
        s.X, s.neighbors, s.lambdas, n_hubs=n_hubs, hub_k=hub_k,
        metric=cfg.metric)
    s.degrees = jnp.sum(s.neighbors < N, axis=1).astype(jnp.int32)


@register_stage("layout")
def _stage_layout(s: BuildState) -> None:
    """Locality-packed layout (DESIGN.md §10): BFS-reorder node ids so
    neighbor rows land contiguous in HBM and the gather kernel's grouped
    DMA coalesces.  Host-side numpy — the traversal is sequential and runs
    once per build, so this stage cannot appear inside a traced (mesh
    shard_map) build; the mesh plane applies it per shard after the traced
    stages instead."""
    import numpy as np

    from repro.ann import layout as L

    if isinstance(s.X, jax.core.Tracer):
        raise ValueError(
            "the 'layout' build stage runs on host and cannot be traced; "
            "mesh builds must strip it from the in-map pipeline and apply "
            "the layout per shard afterwards (distributed.make_build_fn "
            "does this automatically)")
    if s.neighbors is None:
        raise ValueError("'layout' must come after a graph-producing stage "
                         "(e.g. 'diversify')")
    nbrs = np.asarray(jax.device_get(s.neighbors))
    hubs_np = None if s.hubs is None else np.asarray(jax.device_get(s.hubs))
    perm = L.locality_order(nbrs, starts=hubs_np)
    X2, nb2, lam2, deg2, hubs2 = L.apply_layout(
        perm, jax.device_get(s.X), nbrs, jax.device_get(s.lambdas),
        jax.device_get(s.degrees), hubs_np)
    s.X = jnp.asarray(X2)
    s.neighbors = jnp.asarray(nb2)
    s.lambdas = jnp.asarray(lam2)
    s.degrees = jnp.asarray(deg2)
    s.hubs = None if hubs2 is None else jnp.asarray(hubs2)
    s.perm = jnp.asarray(perm)


# --------------------------------------------------------------------------
# runner
# --------------------------------------------------------------------------

def build_graph(X, cfg, *, stages=None, tile: int = 2048,
                knn_ids=None, knn_dists=None) -> PackedGraph:
    """Run the staged build pipeline and return the packed graph.

    ``stages`` overrides ``cfg.build_pipeline`` (default
    ``("knn", "diversify", "bridges")``).  Stage names resolve through the
    registry, so configs can select third-party stages registered with
    :func:`register_stage`.
    """
    names = tuple(stages if stages is not None
                  else getattr(cfg, "build_pipeline",
                               ("knn", "diversify", "bridges")))
    fns = [(n, get_stage(n)) for n in names]  # resolve before any compute
    state = BuildState(
        X=M.preprocess(jnp.asarray(X), cfg.metric), cfg=cfg, tile=tile,
        unroll=getattr(cfg, "unroll_scans", False),
        backend=getattr(cfg, "kernel_backend", "auto"),
        gather_fused=getattr(cfg, "gather_fused", None),
        knn_ids=knn_ids, knn_dists=knn_dists)
    # stage times only when someone reads them (waiting per stage stops the
    # next stage's trace and compile from overlapping this one's run), and
    # never inside a traced (mesh shard) build
    timed = (log.isEnabledFor(logging.INFO)
             and not isinstance(state.X, jax.core.Tracer))
    for name, fn in fns:
        t0 = time.perf_counter()
        fn(state)
        if timed:
            jax.block_until_ready((state.knn_ids, state.neighbors))
            log.info("build stage %s: %.3f s", name,
                     time.perf_counter() - t0)
    if state.neighbors is None:
        raise ValueError(
            f"build pipeline {names} produced no graph — it must include a "
            "stage that sets state.neighbors/lambdas/degrees "
            "(e.g. 'diversify')")
    return PackedGraph(neighbors=state.neighbors, lambdas=state.lambdas,
                       degrees=state.degrees, hubs=state.hubs,
                       perm=state.perm)
