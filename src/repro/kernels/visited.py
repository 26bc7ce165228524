"""Bucketed open-addressing visited filter (CAGRA-style, DESIGN.md §10).

A per-query hash SET of already-visited node ids, consulted before
neighbor rows enter the candidate pool: ``search_small``/``search_large``
with ``visited_filter="hash"`` replace their per-hop full-width
dedup-by-id membership scans (O(width²) id comparisons through the
bitonic rank-merge) with W probes per candidate lane.

Layout: ``table`` [B, W, S] int32 — S buckets (power of two, last axis so
the TPU lane dimension does the probing) × W ways per bucket, ``EMPTY``
= -1 (node ids are always >= 0).  The probe flattens the way and bucket
axes into one lane axis of W*S.  An id hashes to one bucket
(Fibonacci/Knuth multiplicative hash on the HIGH bits via a logical right
shift); membership is "any way equals id"; insertion takes the first
empty way.  A full bucket treats the id as already visited — a safe
*drop* (the search may rarely skip a revisit it would have re-pruned
anyway) and never a duplicate, which is what the downstream merges rely
on.  Tables are sized by :func:`repro.core.hotpath.visited_table` at load
factor <= 1/2, so overflow drops are rare.

Bitwise contract: everything here is int32 compare/select arithmetic, so
the Pallas kernel and the XLA reference (both driven through
:func:`lane_step`, one lane at a time in the caller-canonicalized order)
agree exactly — the parity harness extends over the filter unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

VF_EMPTY = -1  # node ids are >= 0 (plain int: kernels must not capture it)
# int32 wrap of Knuth's 2654435761 — multiplicative hashing wants the
# high bits, hence the logical (unsigned) right shift in hash_bucket
_GOLD = -1640531527


def shift_for(n_buckets: int) -> int:
    """Right-shift amount mapping a 32-bit hash onto [0, n_buckets)."""
    if n_buckets < 2 or n_buckets & (n_buckets - 1):
        raise ValueError(
            f"visited-filter bucket count must be a power of two >= 2, "
            f"got {n_buckets}")
    return 32 - (n_buckets.bit_length() - 1)


def hash_bucket(ids, shift: int):
    """[*, ] int32 ids -> bucket indices in [0, 2**(32-shift))."""
    return jax.lax.shift_right_logical(ids * jnp.int32(_GOLD), shift)


def lane_step(tab, lid, lval, *, n_buckets: int):
    """Probe-and-insert ONE lane across the row batch.

    ``tab`` [B, W*S] int32 (the [B, W, S] table with its way and bucket
    axes flattened: lane ``w*S + s`` is way w of bucket s), ``lid`` [B, 1]
    int32, ``lval`` [B, 1] bool -> ``(tab', fresh [B, 1] bool)`` where
    ``fresh`` means: valid, not already present, and inserted (bucket had
    a free way).  Pure int32 compare/select on 2-D arrays with lane
    broadcasts — the single formulation both backends execute (and one
    Mosaic lowers without shape casts), so they agree bitwise by
    construction.
    """
    B, L = tab.shape
    S = n_buckets
    W = L // S
    lane = jax.lax.broadcasted_iota(jnp.int32, (B, L), 1)
    way = lane // S
    sel = (lane & (S - 1)) == hash_bucket(lid, shift_for(S))    # [B, L]
    hit = jnp.max(jnp.where(sel & (tab == lid), 1, 0), axis=1,
                  keepdims=True) > 0                             # [B, 1]
    emptyw = sel & (tab == jnp.int32(VF_EMPTY))
    slot = jnp.min(jnp.where(emptyw, way, W), axis=1, keepdims=True)
    fresh = lval & jnp.logical_not(hit) & (slot < W)
    wmask = sel & (way == slot) & fresh
    return jnp.where(wmask, lid, tab), fresh


def visited_filter_xla(table, ids, valid):
    """Reference path: lanes applied sequentially with ``lax.scan``."""
    B, W, S = table.shape
    shift_for(S)  # validates the bucket count

    def lane(tab, xs):
        lid, lval = xs
        tab, fresh = lane_step(tab, lid[:, None], lval[:, None],
                               n_buckets=S)
        return tab, fresh[:, 0]

    table2, fresh_t = jax.lax.scan(lane, table.reshape(B, W * S),
                                   (ids.T, valid.T))
    return table2.reshape(B, W, S), fresh_t.T


def _vf_kernel(ids_ref, val_ref, tab_ref, tab_out, fresh_ref, *,
               n_buckets):
    """One row-block: table resident in VMEM, lanes statically unrolled
    (M is a trace constant; per-lane work is a handful of [bs, W*S]
    compare/selects)."""
    tab = tab_ref[...]
    n_lanes = ids_ref.shape[1]
    for lane in range(n_lanes):
        lid = ids_ref[:, lane:lane + 1]
        lval = val_ref[:, lane:lane + 1] != 0
        tab, fresh = lane_step(tab, lid, lval, n_buckets=n_buckets)
        fresh_ref[:, lane:lane + 1] = fresh.astype(jnp.int32)
    tab_out[...] = tab


def visited_filter_pallas(table, ids, valid, *, interpret: bool = False):
    """Pallas path: grid over row blocks, the [bs, W*S] table block stays
    VMEM-resident across all lanes of the call (the XLA path re-streams it
    per scan step).  Same :func:`lane_step` arithmetic — bitwise the
    reference."""
    B, W, S = table.shape
    M = ids.shape[1]
    shift_for(S)  # validates the bucket count
    # 8 rows per block (one sublane tile); a smaller batch is one block
    bs = 8 if B >= 8 else B
    Bp = -(-B // bs) * bs
    tab = table.reshape(B, W * S)
    if Bp != B:
        pad = ((0, Bp - B), (0, 0))
        tab = jnp.pad(tab, pad, constant_values=int(VF_EMPTY))
        ids = jnp.pad(ids, pad)
        valid = jnp.pad(valid, pad)
    table2, fresh = pl.pallas_call(
        functools.partial(_vf_kernel, n_buckets=S),
        grid=(Bp // bs,),
        in_specs=[pl.BlockSpec((bs, M), lambda i: (i, 0)),
                  pl.BlockSpec((bs, M), lambda i: (i, 0)),
                  pl.BlockSpec((bs, W * S), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bs, W * S), lambda i: (i, 0)),
                   pl.BlockSpec((bs, M), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((Bp, W * S), jnp.int32),
                   jax.ShapeDtypeStruct((Bp, M), jnp.int32)],
        interpret=interpret, name="visited_filter",
    )(ids, valid.astype(jnp.int32), tab)
    return table2[:B].reshape(B, W, S), fresh[:B] != 0
