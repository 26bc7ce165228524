"""The yardstick's own data and exact answers: corpus, exact top-k, recall,
reference distances, and the lower-precision control.

Nothing here imports the program under test.  The corpus generator and the
blocked exact top-k are copies of the bring-up smoke's (``chip_smoke.py``),
so the yardstick does not move when the smoke does; the exact top-k here
computes its own l2 distances instead of calling the program's metric.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def make_corpus(seed: int, n: int, n_queries: int, d: int, *,
                layout_seed: int, clusters: int, subspace: int,
                spread: float, noise: float):
    """Clustered corpus and queries on the device, in one jitted call:
    cluster centres plus a displacement in a shared ``subspace``-dimensional
    basis (a low intrinsic dimension, like SIFT's) plus isotropic noise.
    The centres and the basis are the deployment's layout, drawn from the
    configuration's ``layout_seed``; the rows and queries are drawn from
    ``seed``.  Returns (X [n, d], Q [n_queries, d]) float32 device arrays."""

    @functools.partial(jax.jit, static_argnums=(2, 3))
    def gen(layout_key, key, n, m):
        kc, kb = jax.random.split(layout_key)
        kx, kq = jax.random.split(key)
        centers = jax.random.normal(kc, (clusters, d), jnp.float32)
        basis = jax.random.normal(kb, (subspace, d), jnp.float32) \
            / jnp.sqrt(jnp.float32(subspace))

        def draw(k, m):
            ka, kz, ke = jax.random.split(k, 3)
            a = jax.random.randint(ka, (m,), 0, clusters)
            z = jax.random.normal(kz, (m, subspace), jnp.float32)
            e = jax.random.normal(ke, (m, d), jnp.float32)
            return centers[a] + spread * (z @ basis) + noise * e
        return draw(kx, n), draw(kq, m)

    with jax.default_matmul_precision("highest"):
        return gen(jax.random.key(layout_seed), jax.random.key(seed), n,
                   n_queries)


def _sq_l2(q, x, dtype):
    """Squared l2 distances [tq, n], all in f32 at ``highest`` precision
    from operands rounded to ``dtype``: float32 for the reference,
    bfloat16 for the control.  The rounding is ``reduce_precision``, which
    XLA keeps: a convert to bf16 and back may be folded away where XLA
    allows itself excess precision (it was, at one query per call)."""
    if jnp.dtype(dtype) == jnp.bfloat16:
        q = jax.lax.reduce_precision(q, exponent_bits=8, mantissa_bits=7)
        x = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    qn = jnp.sum(jnp.square(q), axis=1)
    xn = jnp.sum(jnp.square(x), axis=1)
    dot = jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    return qn[:, None] + xn[None, :] - 2.0 * dot


@functools.partial(jax.jit, static_argnames=("k", "tile", "dtype"))
def _top_k_tiles(X, Qp, *, k: int, tile: int, dtype: str):
    def one(i):
        q = jax.lax.dynamic_slice_in_dim(Qp, i * tile, tile, 0)
        neg, ids = jax.lax.top_k(-_sq_l2(q, X, jnp.dtype(dtype)), k)
        return ids.astype(jnp.int32), -neg
    ids, dists = jax.lax.map(one, jnp.arange(Qp.shape[0] // tile))
    return ids.reshape(-1, k), dists.reshape(-1, k)


def top_k(X, Q, k: int, *, dtype: str = "float32", tile: int = 256):
    """Brute-force top-k of Q against X, in query tiles: (ids [B, k] int32,
    dists [B, k] f32) numpy arrays.  ``dtype="float32"`` is the exact
    reference (full f32 matmul precision); ``"bfloat16"`` is the control,
    the same search one precision below the configuration's."""
    B = Q.shape[0]
    tile = min(tile, B)
    n_tiles = -(-B // tile)
    Qp = jnp.pad(jnp.asarray(Q), ((0, n_tiles * tile - B), (0, 0)))
    ids, dists = _top_k_tiles(X, Qp, k=k, tile=tile, dtype=dtype)
    return np.asarray(ids)[:B], np.asarray(dists)[:B]


def recall_hits(found, gt, k: int) -> np.ndarray:
    """Per-row count of the first ``k`` found ids that are among the first
    ``k`` exact ids (ids are unique within a row of ``gt``)."""
    f = np.asarray(found)[:, :k]
    g = np.asarray(gt)[:, :k]
    return (f[:, :, None] == g[:, None, :]).any(axis=2).sum(axis=1)


@jax.jit
def _ref_dists(X, Q, qi, ii):
    q = Q[qi][:, None, :]
    x = X[ii]
    return (jnp.sum(jnp.square(q - x), axis=-1),
            jnp.sum(jnp.square(q), axis=-1) + jnp.sum(jnp.square(x), axis=-1))


def distance_gaps(X, Q, qidx, ids, dists, *, chunk: int = 2048):
    """For each returned (query, id, distance): the gap between the returned
    distance and the reference's, over the size of the terms the distance is
    made of (|q|^2 + |x|^2; an f32 evaluation of the expanded form errs in
    proportion to them).  The reference sums squared differences in f32,
    element by element, so no matmul precision enters it.  Ids outside
    [0, n) give an infinite gap.  Returns [m, k] float64."""
    n = X.shape[0]
    ids = np.asarray(ids)
    bad = (ids < 0) | (ids >= n)
    safe = np.where(bad, 0, ids).astype(np.int32)
    out = np.empty(ids.shape, np.float64)
    m = ids.shape[0]
    for s in range(0, m, chunk):
        e = min(m, s + chunk)
        qi = np.zeros((chunk,), np.int32)
        ii = np.zeros((chunk, ids.shape[1]), np.int32)
        qi[:e - s] = qidx[s:e]
        ii[:e - s] = safe[s:e]
        d_ref, scale = jax.device_get(_ref_dists(X, Q, qi, ii))
        got = np.asarray(dists[s:e], np.float64)
        out[s:e] = np.abs(got - d_ref[:e - s]) / np.maximum(
            scale[:e - s], 1e-30)
    out[bad] = np.inf
    return out


def bad_rows(ids, dists, n: int) -> int:
    """Rows that cannot be a top-k answer: an id outside [0, n), an id twice,
    or distances that fall from one rank to the next."""
    ids = np.asarray(ids)
    dists = np.asarray(dists)
    out_of_range = ((ids < 0) | (ids >= n)).any(axis=1)
    s = np.sort(ids, axis=1)
    repeated = (s[:, 1:] == s[:, :-1]).any(axis=1)
    unsorted = (np.diff(dists, axis=1) < 0).any(axis=1)
    return int((out_of_range | repeated | unsorted).sum())
