"""Bitonic sort / top-k Pallas kernel (paper §4.1 R_ij merge).

Batcher's bitonic network [3] is data-oblivious: every compare-exchange
stage is a fixed permutation + vectorized select, which maps 1:1 onto TPU
vector lanes (the paper runs the same network on a warp).  We sort a fixed
power-of-two window per row, carrying ids alongside distances.

Grid: (rows/br,).  Block [br, W] (W >= 128 lanes); the full network is
log2(W)(log2(W)+1)/2 unrolled stages, all in VMEM/registers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INF = jnp.float32(3.4e38)
# column-padding id sentinel: sorts after every real id (incl. the graph's
# own sentinel N) among equal-INF entries, so padded lanes never displace
# real entries within the kept prefix
PAD_ID = np.int32(2**31 - 1)
LANES = 128  # TPU vreg lane count: the narrowest row the network sorts
BLOCK_ELEMS = 64 * LANES  # elements per operand block of the merge


def _bitonic_network(d, ids, width: int):
    """Bitonic network by partner exchange along lanes (Mosaic-safe: lane
    rolls, iota masks and selects only — no reshapes, no gathers, no
    captured constants).  Sorts rows ascending by (dist, id) — the same
    total order as ``lexsort((ids, dists))``, which is what keeps the XLA
    backend of :mod:`repro.core.hotpath` bit-identical to this kernel.

    Stage (k, j) pairs lane p with lane p ^ j.  Both lane rotations by j
    are taken together with a rotated lane iota, and the partner is the
    rotation whose iota equals p ^ j — so the network does not depend on
    the rotation's direction convention."""
    pos = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    k = 2
    while k <= width:
        j = k // 2
        while j >= 1:
            want = pos ^ j
            a_d, a_i = pltpu.roll(d, j, 1), pltpu.roll(ids, j, 1)
            b_d = pltpu.roll(d, width - j, 1)
            b_i = pltpu.roll(ids, width - j, 1)
            from_a = pltpu.roll(pos, j, 1) == want
            p_d = jnp.where(from_a, a_d, b_d)
            p_i = jnp.where(from_a, a_i, b_i)
            lower = (pos & j) == 0
            asc = (pos & k) == 0              # block direction
            self_smaller = (d < p_d) | ((d == p_d) & (ids < p_i))
            # the lower lane of an ascending pair keeps the smaller entry,
            # the upper lane the larger; descending blocks the reverse
            keep = self_smaller == (lower == asc)
            d = jnp.where(keep, d, p_d)
            ids = jnp.where(keep, ids, p_i)
            j //= 2
        k *= 2
    return d, ids


def _sort_kernel(d_ref, i_ref, od_ref, oi_ref, *, width: int):
    od_ref[...], oi_ref[...] = _bitonic_network(d_ref[...], i_ref[...],
                                                width)


def _masked_sort_kernel(d_ref, i_ref, m_ref, od_ref, oi_ref, *, width: int):
    """Keep-mask fused into the sort: dropped lanes get INF distance (their
    ids are kept, matching the XLA reference path exactly)."""
    d = d_ref[...]
    d = jnp.where(m_ref[...] != 0, d, jnp.asarray(3.4e38, d.dtype))
    od_ref[...], oi_ref[...] = _bitonic_network(d, i_ref[...], width)


def bitonic_sort_pallas(dists, ids, *, br: int | None = None,
                        interpret: bool = False):
    """Row-wise ascending sort of (dists [R, W], ids [R, W]); W power of 2."""
    W = dists.shape[1]
    assert W & (W - 1) == 0, f"width {W} must be a power of two"
    return rank_merge_pallas(dists, ids, keep=W, br=br, interpret=interpret)


def bitonic_topk_pallas(dists, ids, k: int, **kw):
    od, oi = bitonic_sort_pallas(dists, ids, **kw)
    return od[:, :k], oi[:, :k]


@functools.partial(jax.jit, static_argnames=("keep", "br", "interpret"))
def rank_merge_pallas(dists, ids, mask=None, *, keep: int,
                      br: int | None = None, interpret: bool = False):
    """Row-wise (dist, id)-ascending merge: sort [R, W] carrying ids, keep
    the `keep` smallest per row.  Generalizes :func:`bitonic_sort_pallas` to
    arbitrary widths (column-padded to the next power of two with
    (INF, PAD_ID) lanes) and an optional keep-mask (masked lanes -> INF
    distance, fused into the kernel).  Widths below one vreg row are
    padded to 128 lanes, the lane rotation's native width."""
    R, W = dists.shape
    if not 0 < keep <= W:
        raise ValueError(f"keep={keep} must be in (0, {W}]")
    Wp = max(1 << max(W - 1, 0).bit_length(), LANES)
    if br is None:
        # ~64 vregs per operand block: wider rows take fewer rows per
        # block (the unrolled network's code grows with the block)
        br = max(8, BLOCK_ELEMS // Wp)
    Rp = -(-R // br) * br
    dp = jnp.pad(dists, ((0, Rp - R), (0, Wp - W)), constant_values=INF)
    ip = jnp.pad(ids, ((0, Rp - R), (0, Wp - W)), constant_values=PAD_ID)
    spec = pl.BlockSpec((br, Wp), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((Rp, Wp), dists.dtype),
                 jax.ShapeDtypeStruct((Rp, Wp), ids.dtype)]
    if mask is None:
        od, oi = pl.pallas_call(
            functools.partial(_sort_kernel, width=Wp),
            grid=(Rp // br,), in_specs=[spec, spec],
            out_specs=[spec, spec], out_shape=out_shape,
            interpret=interpret, name="rank_merge")(dp, ip)
    else:
        mp = jnp.pad(mask.astype(jnp.int8), ((0, Rp - R), (0, Wp - W)))
        od, oi = pl.pallas_call(
            functools.partial(_masked_sort_kernel, width=Wp),
            grid=(Rp // br,), in_specs=[spec, spec, spec],
            out_specs=[spec, spec], out_shape=out_shape,
            interpret=interpret, name="rank_merge_masked")(dp, ip, mp)
    return od[:R, :keep], oi[:R, :keep]
