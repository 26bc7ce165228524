"""Tiled distance-matrix Pallas kernel — the ANN hot spot (paper §4.1).

Computes D[i, j] = ||q_i - x_j||^2 (or -<q_i, x_j> for ip/cos) for a tile of
queries against a tile of database vectors with ONE MXU contraction per
(bq x bn) block plus rank-1 norm corrections.  This is the TPU mapping of
the paper's warp-per-distance scheme: the unit of work is a 128x128 MXU
block, not a 32-thread warp (DESIGN.md §2).

Grid: (Q/bq, N/bn).  Each block touches q-tile [bq, d] + x-tile [bn, d] in
VMEM and writes [bq, bn]; d is kept whole (d <= ~1024 fits VMEM: 128*1024*4B
= 512 KB per operand tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Per-block VMEM budget for operand + output tiles.  ~4 MB of the ~16 MB
# per core, leaving headroom for Pallas' own pipeline double-buffering.
VMEM_BUDGET = 4 << 20


def _dist_kernel(q_ref, x_ref, o_ref, *, metric: str):
    q = q_ref[...].astype(jnp.float32)            # [bq, d]
    x = x_ref[...].astype(jnp.float32)            # [bn, d]
    dots = jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    if metric in ("ip", "cos"):
        o_ref[...] = -dots
    else:
        qn = jnp.sum(q * q, axis=1, keepdims=True)
        xn = jnp.sum(x * x, axis=1)
        o_ref[...] = qn + xn[None, :] - 2.0 * dots


@functools.partial(jax.jit,
                   static_argnames=("metric", "bq", "bn", "interpret"))
def distance_matrix_pallas(Q, X, *, metric: str = "l2", bq: int = 128,
                           bn: int = 128, interpret: bool = False):
    """[B, d] x [N, d] -> [B, N] float32 (smaller = closer)."""
    B, d = Q.shape
    N = X.shape[0]
    Bp = -(-B // bq) * bq
    Np = -(-N // bn) * bn
    Qp = jnp.pad(Q, ((0, Bp - B), (0, 0)))
    Xp = jnp.pad(X, ((0, Np - N), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_dist_kernel, metric=metric),
        grid=(Bp // bq, Np // bn),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Np), jnp.float32),
        interpret=interpret, name="dense_distances",
    )(Qp, Xp)
    return out[:B, :N]


# --------------------------------------------------------------------------
# batched-rowwise block distances — the search hot path's [S, W, d] shape
# --------------------------------------------------------------------------

def _score_block(q, v, m, *, metric: str, pin: bool = False):
    """Shared scoring formulation: fp32 q [bs, Kq, d] x fp32 v [bs, C, d]
    x int8 mask [bs, C] -> [bs, Kq, C] (masked lanes -> INF).  Every
    distance the system computes — both kernel backends, quantized or not —
    funnels through this exact op sequence, which is what makes the
    bitwise-parity contract hold.

    ``pin`` (quantized path, interpret mode only) computes the norm terms
    as batched self-``dot_general`` contractions instead of
    multiply-then-``sum``.  A plain reduce's rounding depends on how the
    *surrounding* program gets scheduled — XLA picks linear vs vectorized
    accumulation per compiled program — so the full-array reference and
    the per-block kernel trace can round the same norm differently by
    1 ulp.  ``dot_general`` lowers to the same per-row contraction
    everywhere (the ``dots`` term below matches bitwise across backends
    for exactly this reason), so both sides route norms through it.  The
    combine is fma-safe as-is: ``2.0 * dots`` is exact (power-of-two
    scale), so fusing it into the subtract cannot change the rounding."""
    dots = jax.lax.dot_general(q, v, (((2,), (2,)), ((0,), (0,))),
                               preferred_element_type=jnp.float32)
    if metric in ("ip", "cos"):
        dist = -dots
    else:
        if pin:
            nd = (((2,), (2,)), ((0, 1), (0, 1)))
            qn = jax.lax.dot_general(q, q, nd,
                                     preferred_element_type=jnp.float32)
            vn = jax.lax.dot_general(v, v, nd,
                                     preferred_element_type=jnp.float32)
        else:
            qn = jnp.sum(q * q, axis=2)
            vn = jnp.sum(v * v, axis=2)
        dist = qn[:, :, None] + vn[:, None, :] - 2.0 * dots
    # widen before the broadcast: Mosaic cannot insert a unit dim into a
    # bool (i1) vector, but can into an int32 one
    keep = m.astype(jnp.int32)[:, None, :] != 0
    return jnp.where(keep, dist, jnp.asarray(3.4e38, dist.dtype))


def _block_kernel(q_ref, v_ref, m_ref, o_ref, *, metric: str):
    """Per-row distance block: q [bs, Kq, d] x v [bs, C, d] -> [bs, Kq, C],
    with the candidate keep-mask fused (masked lanes -> INF)."""
    q = q_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    o_ref[...] = _score_block(q, v, m_ref[...], metric=metric)


def _block_kernel_quant(q_ref, v_ref, s_ref, m_ref, o_ref, *, metric: str,
                        pin: bool = False):
    """Quantized variant: v is int8 codes, s [bs, C] the per-row fp32
    scales; dequantize in-register after the (4x cheaper) VMEM load.

    ``pin`` (set in interpret mode, where the kernel body is ordinary XLA)
    pins the dequantized rows behind an optimization barrier so XLA cannot
    fuse the scale multiply into the norm reduction — the 1-ulp fma drift
    that would break the cross-backend bitwise contract.  Mosaic (real
    TPU) has no such cross-op refusion, and no barrier lowering, so the
    flag stays off there."""
    q = q_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32) * s_ref[...][:, :, None]
    if pin:
        v = jax.lax.optimization_barrier(v)
    o_ref[...] = _score_block(q, v, m_ref[...], metric=metric, pin=pin)


def _block_bytes(bs: int, Kq: int, bc: int, d: int,
                 itemsize: int = 4) -> int:
    """Bytes of one (Q-tile, V-tile, [scale-tile,] mask-tile, out-tile)
    block set.  `itemsize` is the V operand's dtype width — int8 codes
    bill 1 byte/element (plus their fp32 scale row) instead of 4, which
    is exactly the residency win."""
    scales = 0 if itemsize == 4 else bs * bc * 4
    return (bs * Kq * d * 4 + bs * bc * d * itemsize + scales
            + bs * bc + bs * Kq * bc * 4)


def _pick_bs(Kq: int, C: int, d: int, budget: int = VMEM_BUDGET,
             itemsize: int = 4) -> tuple[int, int]:
    """(row tile, candidate tile) whose operand+output blocks fit the VMEM
    budget.  Halves the row tile all the way to 1; if a single row still
    doesn't fit (e.g. GIST d=960 with a wide candidate set), the candidate
    axis is split into a second grid dimension instead of silently
    overflowing VMEM."""
    bs = 128
    while bs > 1 and _block_bytes(bs, Kq, C, d, itemsize) > budget:
        bs //= 2
    if _block_bytes(bs, Kq, C, d, itemsize) <= budget:
        return bs, C
    bc = C
    while bc > 1 and _block_bytes(1, Kq, bc, d, itemsize) > budget:
        bc = -(-bc // 2)
    return 1, bc


@functools.partial(jax.jit,
                   static_argnames=("metric", "bs", "bc", "interpret"))
def block_distances_pallas(Q, V, mask, v_scales=None, *, metric: str = "l2",
                           bs: int | None = None, bc: int | None = None,
                           interpret: bool = False):
    """Q [S, Kq, d] x V [S, C, d] x mask [S, C] -> [S, Kq, C] float32.

    The hot primitive behind ``hotpath.neighbor_distances``: one fused
    tile per `bs` rows computes the MXU contraction, the rank-1 norm
    corrections, and the validity masking in a single VMEM-resident block.
    When even a one-row block exceeds the VMEM budget the candidate axis
    is tiled too (grid dim 2, `bc` columns per block) — padded candidate
    lanes carry mask 0 and come back INF, so the result is unchanged.

    With ``v_scales`` [S, C] float32, V is int8 codes (compressed
    residency, DESIGN.md §8): the tile is loaded at 1 byte/element and
    dequantized in-register as ``v * scale`` before the same contraction.
    """
    S, Kq, d = Q.shape
    C = V.shape[1]
    if bs is None or bc is None:
        pbs, pbc = _pick_bs(Kq, C, d, itemsize=V.dtype.itemsize)
        bs = pbs if bs is None else bs
        bc = pbc if bc is None else bc
    Sp = -(-S // bs) * bs
    Cp = -(-C // bc) * bc
    Qp = jnp.pad(Q, ((0, Sp - S), (0, 0), (0, 0)))
    Vp = jnp.pad(V, ((0, Sp - S), (0, Cp - C), (0, 0)))
    mp = jnp.pad(mask.astype(jnp.int8), ((0, Sp - S), (0, Cp - C)))
    if v_scales is None:
        kernel = functools.partial(_block_kernel, metric=metric)
        name = "block_distances"
        in_specs = [
            pl.BlockSpec((bs, Kq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((bs, bc, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((bs, bc), lambda i, j: (i, j)),
        ]
        args = (Qp, Vp, mp)
    else:
        sp = jnp.pad(v_scales.astype(jnp.float32),
                     ((0, Sp - S), (0, Cp - C)))
        kernel = functools.partial(_block_kernel_quant, metric=metric,
                                   pin=interpret)
        name = "block_distances_int8"
        in_specs = [
            pl.BlockSpec((bs, Kq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((bs, bc, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((bs, bc), lambda i, j: (i, j)),
            pl.BlockSpec((bs, bc), lambda i, j: (i, j)),
        ]
        args = (Qp, Vp, sp, mp)
    out = pl.pallas_call(
        kernel,
        grid=(Sp // bs, Cp // bc),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bs, Kq, bc), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((Sp, Kq, Cp), jnp.float32),
        interpret=interpret, name=name,
    )(*args)
    return out[:S, :, :C]


# --------------------------------------------------------------------------
# gather-fused block distances — in-kernel neighbor gather (DESIGN.md §2)
# --------------------------------------------------------------------------
#
# The paper's throughput bound is how fast one node's neighborhood can be
# fetched and scored (§4.1); CAGRA/GGNN win on GPU by streaming neighbor
# vectors into shared memory.  This is the TPU analogue: the database X
# stays resident in HBM (memory_space=ANY), the neighbor ids arrive via
# scalar prefetch (available before the kernel body runs), and each row
# tile issues one async copy per needed neighbor row HBM->VMEM.  Copies
# for tile i+1 are issued before tile i's compute (double buffering), so
# the DMA stream hides behind the MXU contraction.  The [S, C, d]
# gathered-neighbor buffer of the gather-then-block path never exists.


def span_group(C: int, *, cap: int = 8) -> int:
    """Aligned-group width for span-coalesced gather DMA: the largest
    power of two <= ``cap`` dividing C.  Group g covers candidate lanes
    [g*G, (g+1)*G); when its prefetched ids are contiguous ascending the
    kernel issues ONE [G, d] copy instead of G row copies.  Static in C,
    so the kernel trace (and its issue/wait pairing) never depends on the
    data.  ``ann.layout.span_stats`` mirrors this rule host-side."""
    g = 1
    while g * 2 <= cap and C % (g * 2) == 0:
        g *= 2
    return g


def _gather_tile_bytes(Kq: int, C: int, d: int, *, self_q: bool,
                       itemsize: int = 4) -> int:
    """Bytes of one gather-fused block set per row of tile: Q tile (unless
    the query side is gathered from the same ids), the double-buffered
    neighbor scratch (at the row's DMA width — int8 codes move as
    :func:`pack_words` rows, d bytes rounded up to whole 512-byte lane
    rows, and bill their fp32 scale row), mask, and output."""
    q = 0 if self_q else Kq * d * 4
    scales = 0 if itemsize == 4 else C * 4
    row = d * 4 if itemsize == 4 else packed_row_bytes(d)
    return q + 2 * C * row + scales + C + Kq * C * 4


def packed_row_bytes(d: int) -> int:
    """Bytes of one :func:`pack_words` row: d int8 codes as int32 words,
    padded to whole 128-lane rows."""
    return -(-d // 512) * 512


def gather_fused_fits(Kq: int, C: int, d: int, *, self_q: bool = False,
                      budget: int = VMEM_BUDGET, itemsize: int = 4) -> bool:
    """True when at least a one-row tile of the fused gather kernel fits
    the VMEM budget (the dispatch fallback check in hotpath)."""
    return _gather_tile_bytes(Kq, C, d, self_q=self_q,
                              itemsize=itemsize) <= budget


def _pick_bs_fused(S: int, Kq: int, C: int, d: int, *,
                   self_q: bool, budget: int = VMEM_BUDGET,
                   itemsize: int = 4) -> int:
    per_row = _gather_tile_bytes(Kq, C, d, self_q=self_q, itemsize=itemsize)
    bs = 128
    while bs > 1 and bs * per_row > budget:
        bs //= 2
    while bs // 2 >= S and bs > 1:  # don't pad tiny batches up to 128 rows
        bs //= 2
    return bs


def pack_words(codes):
    """int8 codes [N, d] -> int32 words [N, Wd] for the fused gather, Wd =
    d/4 rounded up to whole 128-lane rows (zero words pad the tail).

    Mosaic tiles an int8 HBM array by (8, 128) with four rows interleaved
    per 32-bit word, so one int8 row is not contiguous and cannot be a DMA
    source; an int32 row of whole 128-lane tiles is.  Byte k of word j
    holds element ``k*d/4 + j`` (chunk-major), so :func:`_unpack_words`
    restores the natural order with a lane concat of four contiguous
    chunks — no interleave."""
    N, d = codes.shape
    q = d // 4
    w = jax.lax.bitcast_convert_type(
        codes.reshape(N, 4, q).transpose(0, 2, 1), jnp.int32)
    return jnp.pad(w, ((0, 0), (0, -(-q // 128) * 128 - q)))


def _unpack_words(w, d: int):
    """[..., Wd] int32 words of :func:`pack_words` -> [..., d] fp32 with
    the exact int8 values (sign-extending byte extraction)."""
    w = w[..., :d // 4]
    chunks = [jax.lax.shift_right_arithmetic(
        jax.lax.shift_left(w, jnp.int32(24 - 8 * k)), jnp.int32(24))
        for k in range(4)]
    return jnp.concatenate(chunks, axis=-1).astype(jnp.float32)


def _gather_body(cur_ref, nxt_ref, q_ref, s_ref, m_ref, x_hbm, o_ref, vbuf,
                 sem, *, metric: str, bs: int, C: int, d: int,
                 pin: bool = False):
    """One grid step = one row tile.  ``cur_ref``/``nxt_ref`` [bs, C] are
    this tile's and the next tile's neighbor ids, each an SMEM block of
    its own (SMEM use is fixed by the tile, never by S: a whole [S, C]
    scalar prefetch outgrows the 1 MiB SMEM at large batches).  x_hbm is
    the whole database in HBM/ANY; vbuf [2, bs, C, d] revolves across the
    grid.  ``s_ref`` (quantized path only) carries the gathered per-row
    fp32 scales; the int8 tile dequantizes in-register after the DMA.
    ``pin`` — see :func:`_block_kernel_quant` (interpret-mode fma-fusion
    guard).  On that path x_hbm holds :func:`pack_words` rows.
    """
    i = pl.program_id(0)
    n = pl.num_programs(0)
    G = span_group(C)  # aligned-group width for span-coalesced copies

    def _dma(slot, ids_ref, r):
        # r enumerates the bs*C neighbor rows of the tile
        s, c = r // C, jax.lax.rem(r, C)
        return pltpu.make_async_copy(
            x_hbm.at[ids_ref[s, c]],
            vbuf.at[slot, s, c],
            sem.at[slot])

    def _span(ids_ref, g):
        """Group g of the tile: (row-in-tile, lane offset, base id, ok)
        where ok means the G ids form one contiguous ascending run — a
        single multi-row HBM slice.  Layout-packed graphs (DESIGN.md §10)
        make this the common case.  All-SMEM scalar reads, recomputed
        identically at issue and wait time so starts and waits pair up;
        contiguity also bounds the slice (the last id is pre-clipped < N,
        so base + G <= N)."""
        gpr = C // G
        s, c0 = g // gpr, jax.lax.rem(g, gpr) * G
        base = ids_ref[s, c0]
        ok = base >= 0
        for j in range(1, G):
            ok = jnp.logical_and(ok, ids_ref[s, c0 + j] == base + j)
        return s, c0, base, ok

    def _span_dma(slot, ids_ref, g):
        s, c0, base, _ = _span(ids_ref, g)
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(base, G)],
            vbuf.at[slot, s, pl.ds(c0, G)],
            sem.at[slot])

    def _sweep(slot, ids_ref, act):
        """Drive every DMA of a tile through ``act`` (start or wait).
        G == 1: the original per-row enumeration.  Else per group: one
        coalesced copy when the span predicate holds, the G per-row
        copies otherwise — both phases traverse the same groups with the
        same predicates, so every started copy gets one matching wait."""
        if G == 1:
            def body(r, carry):
                act(_dma(slot, ids_ref, r))
                return carry
            jax.lax.fori_loop(0, bs * C, body, 0)
            return

        def body(g, carry):
            s, c0, _, ok = _span(ids_ref, g)

            @pl.when(ok)
            def _():
                act(_span_dma(slot, ids_ref, g))

            @pl.when(jnp.logical_not(ok))
            def _():
                for j in range(G):
                    act(_dma(slot, ids_ref, s * C + c0 + j))
            return carry
        jax.lax.fori_loop(0, bs * (C // G), body, 0)

    @pl.when(i == 0)
    def _():
        _sweep(0, cur_ref, lambda cp: cp.start())

    @pl.when(i + 1 < n)  # prefetch the next tile's rows behind this compute
    def _():
        _sweep((i + 1) % 2, nxt_ref, lambda cp: cp.start())

    slot = jax.lax.rem(i, 2)
    _sweep(slot, cur_ref, lambda cp: cp.wait())

    if s_ref is None:
        v = vbuf[slot].astype(jnp.float32)         # [bs, C, d]
    else:
        v = _unpack_words(vbuf[slot], d)           # int8 codes -> fp32
    if s_ref is not None:
        v = v * s_ref[...][:, :, None]
        if pin:
            v = jax.lax.optimization_barrier(v)
    q = v if q_ref is None else q_ref[...].astype(jnp.float32)
    o_ref[...] = _score_block(q, v, m_ref[...], metric=metric, pin=pin)


def _gather_block_kernel(cur_ref, nxt_ref, q_ref, m_ref, x_hbm, o_ref, vbuf,
                         sem, **kw):
    _gather_body(cur_ref, nxt_ref, q_ref, None, m_ref, x_hbm, o_ref, vbuf,
                 sem, **kw)


def _gather_block_kernel_quant(cur_ref, nxt_ref, q_ref, s_ref, m_ref, x_hbm,
                               o_ref, vbuf, sem, **kw):
    _gather_body(cur_ref, nxt_ref, q_ref, s_ref, m_ref, x_hbm, o_ref, vbuf,
                 sem, **kw)


def _self_q_gather_kernel(cur_ref, nxt_ref, m_ref, x_hbm, o_ref, vbuf, sem,
                          **kw):
    """self_q variant: the query rows ARE the gathered neighbor rows (the
    diversify tiles' [T, K, K] pairwise blocks), so no Q input at all."""
    _gather_body(cur_ref, nxt_ref, None, None, m_ref, x_hbm, o_ref, vbuf,
                 sem, **kw)


@functools.partial(jax.jit,
                   static_argnames=("metric", "bs", "interpret", "self_q"))
def gather_block_distances_pallas(Q, X, idx, mask, scales=None, *,
                                  metric: str = "l2",
                                  bs: int | None = None,
                                  interpret: bool = False,
                                  self_q: bool = False):
    """In-kernel-gather distance block.

    Q [S, Kq, d] (ignored/None when ``self_q``) x X [N, d] resident in HBM
    x idx [S, C] int32 (pre-clipped to [0, N)) x mask [S, C] bool ->
    [S, Kq, C] float32 (Kq = C when ``self_q``).  Bitwise-identical to
    ``block_distances_pallas(Q, X[idx], mask)`` — same contraction, same
    rank-1 norm corrections, same mask — without ever materializing the
    [S, C, d] neighbor buffer.

    With ``scales`` [S, C] float32 (the per-row scales pre-gathered by the
    same idx), X is the int8 code matrix: the DMA streams packed int32
    rows (:func:`pack_words`; d bytes rounded up to 512, so ~3.75x less
    HBM->VMEM traffic than fp32 at d=960 and none at d=128) and the tile
    dequantizes in-register before the contraction.
    """
    S, C = idx.shape
    d = X.shape[1]
    Kq = C if self_q else Q.shape[1]
    if bs is None:
        bs = _pick_bs_fused(S, Kq, C, d, self_q=self_q,
                            itemsize=X.dtype.itemsize)
    if scales is not None:
        if d % 4:
            raise ValueError(f"fused int8 gather needs d % 4 == 0, got {d}")
        X = pack_words(X)
    Sp = -(-S // bs) * bs
    n_tiles = Sp // bs
    ip = jnp.pad(idx, ((0, Sp - S), (0, 0)))
    mp = jnp.pad(mask.astype(jnp.int32), ((0, Sp - S), (0, 0)))
    # this tile's ids and the next tile's (the DMA prefetch), SMEM blocks
    ids_specs = [
        pl.BlockSpec((bs, C), lambda i: (i, 0), memory_space=pltpu.SMEM),
        pl.BlockSpec((bs, C), lambda i: (jnp.minimum(i + 1, n_tiles - 1), 0),
                     memory_space=pltpu.SMEM),
    ]
    row_spec = pl.BlockSpec((bs, C), lambda i: (i, 0))
    q_spec = pl.BlockSpec((bs, Kq, d), lambda i: (i, 0, 0))
    x_spec = pl.BlockSpec(memory_space=pl.ANY)
    if self_q:
        kernel = functools.partial(_self_q_gather_kernel, metric=metric,
                                   bs=bs, C=C, d=d)
        name = "gather_pair_distances"
        in_specs = ids_specs + [row_spec, x_spec]
        args = (ip, ip, mp, X)
    elif scales is not None:
        kernel = functools.partial(_gather_block_kernel_quant, metric=metric,
                                   bs=bs, C=C, d=d, pin=interpret)
        name = "gather_distances_int8"
        in_specs = ids_specs + [q_spec, row_spec, row_spec, x_spec]
        Qp = jnp.pad(Q, ((0, Sp - S), (0, 0), (0, 0)))
        sp = jnp.pad(scales.astype(jnp.float32), ((0, Sp - S), (0, 0)))
        args = (ip, ip, Qp, sp, mp, X)
    else:
        kernel = functools.partial(_gather_block_kernel, metric=metric,
                                   bs=bs, C=C, d=d)
        name = "gather_distances"
        in_specs = ids_specs + [q_spec, row_spec, x_spec]
        Qp = jnp.pad(Q, ((0, Sp - S), (0, 0), (0, 0)))
        args = (ip, ip, Qp, mp, X)
    out = pl.pallas_call(
        kernel, grid=(n_tiles,), in_specs=in_specs,
        out_specs=pl.BlockSpec((bs, Kq, C), lambda i: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, bs, C, X.shape[1]), X.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
        out_shape=jax.ShapeDtypeStruct((Sp, Kq, C), jnp.float32),
        interpret=interpret, name=name,
    )(*args)
    return out[:S]
