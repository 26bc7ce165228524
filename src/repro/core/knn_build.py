"""k-NN graph construction: exact tiled brute force + NN-Descent.

The paper builds its k-NN graphs with GPU NN-Descent [31].  TPU adaptation
(DESIGN.md §2): NN-Descent's *local join* trades distance computations for
scatter traffic — the right trade on CUDA cores, the wrong one on an MXU
where batched gather+GEMM distance evaluation is nearly free.  We therefore
run NN-*expansion* with reverse edges: per iteration each node evaluates its
neighbors-of-neighbors + reverse neighbors with one batched GEMM and merges
top-k.  Same fixpoint, TPU-shaped inner loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import hotpath as HP
from repro.core import metrics as M

INF = jnp.float32(3.4e38)


def tiled_map(fn, n: int, unroll: bool = False):
    """lax.map over range(n); python-unrolled when `unroll` (so the dry-run's
    cost_analysis counts every tile — XLA costs a while body exactly once)."""
    if unroll:
        outs = [fn(i) for i in range(n)]
        return jax.tree.map(lambda *a: jnp.stack(a), *outs)
    return jax.lax.map(fn, jnp.arange(n))


# --------------------------------------------------------------------------
# exact (tiled brute force)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "metric", "tile", "unroll"))
def exact_knn(X, k: int, metric: str = "l2", tile: int = 1024,
              unroll: bool = False):
    """[N, d] -> (ids [N, k], dists [N, k]); excludes self."""
    N = X.shape[0]
    n_tiles = -(-N // tile)
    Xp = jnp.pad(X, ((0, n_tiles * tile - N), (0, 0)))

    def one_tile(i):
        q = jax.lax.dynamic_slice_in_dim(Xp, i * tile, tile, axis=0)
        dist = M.pairwise(q, X, metric)                        # [tile, N]
        rows = i * tile + jnp.arange(tile)
        dist = jnp.where(rows[:, None] == jnp.arange(N)[None, :], INF, dist)
        dist = jnp.where(rows[:, None] >= N, INF, dist)
        neg, ids = jax.lax.top_k(-dist, k)
        return ids.astype(jnp.int32), -neg

    ids, dists = tiled_map(one_tile, n_tiles, unroll)
    return ids.reshape(-1, k)[:N], dists.reshape(-1, k)[:N]


# --------------------------------------------------------------------------
# reverse adjacency with fixed cap (sort-based scatter; shared with MoE trick)
# --------------------------------------------------------------------------

def reverse_neighbors(ids, valid, cap: int):
    """ids [N, K] (+valid mask) -> reverse lists [N, cap] (sentinel = N)."""
    N, K = ids.shape
    src = jnp.repeat(jnp.arange(N, dtype=jnp.int32), K)
    dst = ids.reshape(-1)
    dst = jnp.where(valid.reshape(-1), dst, N)                 # invalid -> trash
    order = jnp.argsort(dst, stable=True)
    sdst, ssrc = dst[order], src[order]
    counts = jnp.bincount(dst, length=N + 1)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(N * K) - starts[sdst]
    keep = (rank < cap) & (sdst < N)
    slot = jnp.where(keep, sdst * cap + rank, N * cap)
    rev = jnp.full((N * cap + 1,), N, jnp.int32).at[slot].set(ssrc)
    return rev[: N * cap].reshape(N, cap)


# --------------------------------------------------------------------------
# NN-expansion (TPU-shaped NN-Descent)
# --------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("k", "metric", "iters", "sample",
                                    "unroll", "backend", "gather_fused",
                                    "tile"))
def nn_descent(X, k: int, metric: str = "l2", iters: int = 8,
               sample: int = 8, seed: int = 0, unroll: bool = False,
               backend: str = "auto", gather_fused: str | None = None,
               tile: int = 16384):
    """Approximate k-NN graph. Returns (ids [N, k], dists [N, k]) sorted asc.

    Per iteration, candidates(u) = reverse(u) ++ B[B[u]][:, :sample] — one
    gather + one batched GEMM per node, merged by (dedup, top-k).  Both
    the evaluation and the merge run over row tiles of ``tile`` nodes
    (rows are independent), so the [N, k + k*sample] candidate arrays
    exist one tile at a time: at N = 2^20 they would not fit a 16 GiB
    chip all at once.
    """
    N, d = X.shape
    tile = min(tile, N)
    n_tiles = -(-N // tile)
    pad = n_tiles * tile - N
    key = jax.random.key(seed)
    ids = jax.random.randint(key, (N, k), 0, N, jnp.int32)
    # avoid self at init
    ids = jnp.where(ids == jnp.arange(N)[:, None], (ids + 1) % N, ids)

    def rows_of(a, i):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return jax.lax.dynamic_slice_in_dim(a, i * tile, tile, 0)

    def per_tile(fn, *arrays):
        """fn over row tiles of ``arrays``; outputs re-joined to [N, ...]."""
        out = tiled_map(lambda i: fn(i, *(rows_of(a, i) for a in arrays)),
                        n_tiles, unroll)
        return jax.tree.map(lambda o: o.reshape((-1,) + o.shape[2:])[:N],
                            out)

    def init(i, X_t, ids_t):
        dists_t = HP.neighbor_distances(X_t, X, ids_t, metric=metric,
                                        backend=backend,
                                        gather_fused=gather_fused)
        d_t, i_t = HP.rank_merge(dists_t, ids_t, keep=k, backend=backend)
        return i_t, d_t

    ids, dists = per_tile(init, X, ids)

    def expand(ids, i, X_t, ids_t, dists_t, rev_t):
        rows = i * tile + jnp.arange(tile)
        hop2 = ids[jnp.clip(ids_t, 0, N - 1)][:, :, :sample]  # [T, k, sample]
        cand = jnp.concatenate([rev_t, hop2.reshape(tile, k * sample)],
                               axis=1)
        cand = jnp.where(cand == rows[:, None], N, cand)      # drop self
        # one fused gather+GEMM evaluation; cand >= N masked in-kernel
        cdist = HP.neighbor_distances(X_t, X, cand, metric=metric,
                                      backend=backend,
                                      gather_fused=gather_fused)
        all_ids = jnp.concatenate([ids_t, cand], axis=1)
        all_d = jnp.concatenate([dists_t, cdist], axis=1)
        # dedup by id then keep k smallest
        order = jnp.argsort(all_ids, axis=1)
        sid = jnp.take_along_axis(all_ids, order, axis=1)
        sd = jnp.take_along_axis(all_d, order, axis=1)
        dup = jnp.concatenate(
            [jnp.zeros((tile, 1), bool), sid[:, 1:] == sid[:, :-1]], axis=1)
        new_d, new_ids = HP.rank_merge(sd, sid, keep=k,
                                       mask=~dup & (sid < N),
                                       backend=backend)
        return new_ids.astype(jnp.int32), new_d

    def body(state, _):
        ids, dists = state
        rev = reverse_neighbors(ids, ids < N, cap=k)           # [N, k]
        return per_tile(functools.partial(expand, ids), X, ids, dists,
                        rev), None

    (ids, dists), _ = jax.lax.scan(body, (ids, dists), None, length=iters,
                                   unroll=unroll)
    return ids, dists
