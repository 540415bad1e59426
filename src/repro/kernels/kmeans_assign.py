"""Pallas TPU kernels: K-means nearest-centroid assignment (+ fused reduce).

The K-means router's hot loop (paper Alg. 2 lines 3/9) is a pairwise-distance
argmin. TPU mapping: query rows are tiled into VMEM blocks; the centroid
table is tiled along K into ``block_k`` VMEM blocks (so K in the thousands
never overflows VMEM); −2·x·μᵀ runs on the MXU and the rank-1 ‖μ‖²
correction + argmin run on the VPU. ‖x‖² is dropped (argmin-invariant), so
assignment is one matmul + a lane reduction per (query, centroid) tile.

Very wide embeddings additionally tile the feature dimension: beyond
``block_d`` columns (default 2048 — full rows of d ≈ 8k would blow VMEM on
real hardware) the grid grows an innermost d axis that accumulates the
x·μᵀ partials in VMEM scratch, deferring the argmin merge to the last d
tile. Centroid norms ‖μ‖² are computed once per call in XLA (the oracle's
expression) and enter every kernel as a (1, K) row. d ≤ block_d keeps the
single-pass kernels.

``kmeans_assign_reduce_pallas`` additionally fuses the Lloyd's-step update
into the same pass: the per-tile one-hot of the argmin feeds a second MXU
matmul that accumulates per-cluster weighted coordinate sums and counts
across query tiles, so a full Lloyd iteration is one kernel launch instead
of assign + host-visible one-hot scatter.

Inputs are only padded when their shapes are not already (8, 128)-aligned;
padded centroids carry an +inf norm so they are never selected. Per-row
vectors (assignments, weights) travel as ``(n, 1)`` columns and per-centroid
counts as a ``(1, K)`` row: 2-D blocks whose tiling Mosaic and XLA agree on
for any padded n (1-D blocks do not once n exceeds one block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rup(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def _pad2(a, rows: int, cols: int):
    """Zero-pad a 2-D array up to (rows, cols) — no-op when already there."""
    if a.shape == (rows, cols):
        return a
    return jnp.zeros((rows, cols), a.dtype).at[:a.shape[0], :a.shape[1]].set(a)


def _block_argmin(dist):
    """Row-wise (min, first argmin) of a (BN, BK) tile as (BN, 1) columns
    — lane reductions with keepdims only; ties keep the lowest index,
    like ``jnp.argmin``."""
    mn = jnp.min(dist, axis=1, keepdims=True)
    iota = jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    arg = jnp.min(jnp.where(dist == mn, iota, dist.shape[1]), axis=1,
                  keepdims=True)
    return mn, arg


def _norms_row(cents, k_p: int):
    """(1, k_p) row of centroid norms ‖μ‖² (the oracle's expression, in
    XLA) with +inf on padded centroids so they are never selected."""
    c2 = jnp.sum(jnp.asarray(cents, jnp.float32) ** 2, axis=-1)
    return jnp.full((1, k_p), jnp.inf, jnp.float32).at[0, :c2.shape[0]].set(c2)


def _col(v, rows: int, dtype):
    """A length-n vector as a zero-padded (rows, 1) column."""
    v = jnp.asarray(v, dtype)
    return jnp.zeros((rows, 1), dtype).at[:v.shape[0], 0].set(v)


def _assign_kernel(x_ref, c_ref, c2b_ref, out_ref, min_s):
    """One (query tile, centroid tile) step: block argmin merged into the
    running (min distance, argmin). The min carry lives in VMEM scratch
    (persists across the inner centroid-tile grid steps) — only the
    argmin itself ever reaches HBM."""
    k = pl.program_id(1)
    bk = c_ref.shape[0]
    x = x_ref[...].astype(jnp.float32)          # (BN, D)
    c = c_ref[...].astype(jnp.float32)          # (BK, D)
    xc = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)     # (BN, BK) — MXU
    dist = c2b_ref[...] - 2.0 * xc              # (BN, BK)
    blk_min, blk_arg = _block_argmin(dist)      # (BN, 1) each
    blk_arg = blk_arg + k * bk

    @pl.when(k == 0)
    def _():
        out_ref[...] = blk_arg
        min_s[...] = blk_min

    @pl.when(k > 0)
    def _():
        # strict < keeps the earlier tile on ties — global argmin semantics
        better = blk_min < min_s[...]
        out_ref[...] = jnp.where(better, blk_arg, out_ref[...])
        min_s[...] = jnp.minimum(blk_min, min_s[...])


def _assign_kernel_dtiled(x_ref, c_ref, c2b_ref, out_ref, min_s, xc_s, *,
                          nd: int):
    """d-tiled variant: grid (query tile, centroid tile, d tile) with d
    innermost. Each d step accumulates this (query, centroid) pair's x·μᵀ
    partial into VMEM scratch; the last d step forms the distances and
    merges the block argmin into the running (min, argmin) exactly like
    the single-pass kernel."""
    k = pl.program_id(1)
    dt = pl.program_id(2)
    bk = c_ref.shape[0]
    x = x_ref[...].astype(jnp.float32)          # (BN, BD)
    c = c_ref[...].astype(jnp.float32)          # (BK, BD)
    part = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)     # (BN, BK) — MXU

    @pl.when(dt == 0)
    def _():
        xc_s[...] = part

    @pl.when(dt > 0)
    def _():
        xc_s[...] += part

    # merge only once the full-d distance is assembled (the reduction work
    # is gated on the last d tile — earlier tiles only accumulate); the
    # block stays VMEM-resident across its consecutive (k, d) revisits
    @pl.when(dt == nd - 1)
    def _():
        dist = c2b_ref[...] - 2.0 * xc_s[...]
        blk_min, blk_arg = _block_argmin(dist)
        blk_arg = blk_arg + k * bk
        # strict < keeps the earlier tile on ties — global argmin
        # semantics; the first centroid tile takes unconditionally (the
        # carry holds the previous query block's leftovers)
        better = (blk_min < min_s[...]) | (k == 0)
        out_ref[...] = jnp.where(better, blk_arg, out_ref[...])
        min_s[...] = jnp.where(better, blk_min, min_s[...])


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_k", "block_d",
                                    "interpret"))
def kmeans_assign_pallas(x: jnp.ndarray, cents: jnp.ndarray, *,
                         interpret: bool, block_n: int = 256,
                         block_k: int = 512, block_d: int = 2048):
    """x: (n, d), cents: (K, d) → (n,) int32."""
    n, d = x.shape
    K = cents.shape[0]
    assert block_k % 128 == 0, "block_k must be lane-aligned (multiple of 128)"
    assert block_d % 128 == 0, "block_d must be lane-aligned (multiple of 128)"

    n_p, d_p = _rup(n, block_n), _rup(d, 128)
    bk = min(block_k, _rup(max(K, 8), 128))
    k_p = _rup(max(K, 8), bk)
    c2b = _norms_row(cents, k_p)                                  # (1, k_p)

    if d_p > block_d:                           # wide-d: tile the features
        d_p = _rup(d, block_d)
        nd = d_p // block_d
        x_p = _pad2(x, n_p, d_p)
        c_p = _pad2(cents, k_p, d_p)
        out = pl.pallas_call(
            functools.partial(_assign_kernel_dtiled, nd=nd),
            grid=(n_p // block_n, k_p // bk, nd),   # d innermost
            in_specs=[
                pl.BlockSpec((block_n, block_d), lambda i, k, dt: (i, dt)),
                pl.BlockSpec((bk, block_d), lambda i, k, dt: (k, dt)),
                pl.BlockSpec((1, bk), lambda i, k, dt: (0, k)),
            ],
            out_specs=pl.BlockSpec((block_n, 1), lambda i, k, dt: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n_p, 1), jnp.int32),
            scratch_shapes=[
                pltpu.VMEM((block_n, 1), jnp.float32),   # running min carry
                pltpu.VMEM((block_n, bk), jnp.float32),  # x·μᵀ accumulator
            ],
            interpret=interpret,
        )(x_p, c_p, c2b)
        return out[:n, 0]

    x_p = _pad2(x, n_p, d_p)
    c_p = _pad2(cents, k_p, d_p)
    grid = (n_p // block_n, k_p // bk)  # centroid tiles innermost
    out = pl.pallas_call(
        _assign_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d_p), lambda i, k: (i, 0)),
            pl.BlockSpec((bk, d_p), lambda i, k: (k, 0)),
            pl.BlockSpec((1, bk), lambda i, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((block_n, 1), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_p, 1), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((block_n, 1), jnp.float32),  # running min carry
        ],
        interpret=interpret,
    )(x_p, c_p, c2b)
    return out[:n, 0]


def _assign_reduce_kernel(x_ref, c_ref, c2b_ref, w_ref, assign_ref,
                          sums_ref, cnts_ref):
    """One query tile, whole centroid table resident: nearest-centroid
    argmin AND its weighted one-hot reduction (per-cluster coordinate sums
    + counts), sharing the x·μᵀ MXU pass. sums/cnts blocks are
    grid-invariant → VMEM accumulation across consecutive grid steps (the
    only revisit pattern Pallas TPU guarantees)."""
    i = pl.program_id(0)
    kk = c_ref.shape[0]
    x = x_ref[...].astype(jnp.float32)          # (BN, D)
    c = c_ref[...].astype(jnp.float32)          # (K, D)
    xc = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)     # (BN, K) — MXU
    dist = c2b_ref[...] - 2.0 * xc
    _, assign = _block_argmin(dist)             # (BN, 1)
    assign_ref[...] = assign

    onehot = (jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], kk), 1)
              == assign).astype(jnp.float32)
    wv = onehot * w_ref[...]                    # (BN, K) — pad rows have w=0
    part_sums = jax.lax.dot_general(
        wv, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)     # (K, D) — MXU
    part_cnts = jnp.sum(wv, axis=0, keepdims=True)   # (1, K)

    @pl.when(i == 0)
    def _():
        sums_ref[...] = part_sums
        cnts_ref[...] = part_cnts

    @pl.when(i > 0)
    def _():
        sums_ref[...] += part_sums
        cnts_ref[...] += part_cnts


def _reduce_tiled_kernel(x_ref, w_ref, assign_ref, sums_ref, cnts_ref, *,
                         bk: int):
    """Weighted one-hot reduction for ONE centroid tile, streaming query
    tiles innermost: grid (nk, nq) keeps each (bk, D) sums block resident
    in VMEM across all its consecutive query-tile steps — no
    non-consecutive output revisits (which compiled Pallas TPU does not
    support). Rows assigned outside this tile fall out of the iota
    comparison; padded rows carry w=0."""
    kt = pl.program_id(0)
    i = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)          # (BN, D)
    local = assign_ref[...] - kt * bk           # in [0, bk) iff in this tile
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], bk), 1)
              == local).astype(jnp.float32)
    wv = onehot * w_ref[...]                    # (BN, BK)
    part_sums = jax.lax.dot_general(
        wv, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)     # (BK, D) — MXU
    part_cnts = jnp.sum(wv, axis=0, keepdims=True)   # (1, BK)

    @pl.when(i == 0)
    def _():
        sums_ref[...] = part_sums
        cnts_ref[...] = part_cnts

    @pl.when(i > 0)
    def _():
        sums_ref[...] += part_sums
        cnts_ref[...] += part_cnts


def _reduce_tiled_kernel_d(x_ref, w_ref, assign_ref, sums_ref, cnts_ref, *,
                           bk: int):
    """Weighted one-hot reduction for one (centroid tile, d tile) output
    block, streaming query tiles innermost: grid (nk, nd, nq). The sums
    block stays VMEM-resident across its consecutive query steps; counts
    are d-independent, so only the dt == 0 sweep accumulates them (their
    block is resident across the whole (dt, nq) revisit run)."""
    kt = pl.program_id(0)
    dt = pl.program_id(1)
    i = pl.program_id(2)
    x = x_ref[...].astype(jnp.float32)          # (BN, BD)
    local = assign_ref[...] - kt * bk           # in [0, bk) iff in this tile
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], bk), 1)
              == local).astype(jnp.float32)
    wv = onehot * w_ref[...]                    # (BN, BK)
    part_sums = jax.lax.dot_general(
        wv, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)     # (BK, BD) — MXU
    part_cnts = jnp.sum(wv, axis=0, keepdims=True)   # (1, BK)

    @pl.when(i == 0)
    def _():
        sums_ref[...] = part_sums

    @pl.when(i > 0)
    def _():
        sums_ref[...] += part_sums

    @pl.when((dt == 0) & (i == 0))
    def _():
        cnts_ref[...] = part_cnts

    @pl.when((dt == 0) & (i > 0))
    def _():
        cnts_ref[...] += part_cnts


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_k", "block_d",
                                    "interpret"))
def kmeans_assign_reduce_pallas(x: jnp.ndarray, cents: jnp.ndarray,
                                w: jnp.ndarray, *, interpret: bool,
                                block_n: int = 256, block_k: int = 512,
                                block_d: int = 2048):
    """x: (n, d), cents: (K, d), w: (n,) →
    (assign (n,) int32, sums (K, d) f32, counts (K,) f32) where
    sums[k] = Σ_{i: assign_i=k} w_i·x_i and counts[k] = Σ w_i.

    When the centroid table fits one ``block_k`` tile (Lloyd's usual K)
    and the rows fit one ``block_d`` tile, assignment and reduction run as
    ONE fused pass sharing the x·μᵀ matmul. Larger tables tile along K:
    the shared ``_assign_kernel`` block_k loop produces the global argmin,
    then a reduction kernel with query tiles innermost accumulates each
    centroid tile's sums/counts. Rows wider than ``block_d`` additionally
    tile the feature dimension in both phases (d-tiled assign, then a
    (centroid, d, query) reduction grid). All variants only ever
    accumulate into VMEM-resident blocks across consecutive grid steps
    (compiled Pallas TPU does not support non-consecutive output
    revisits), at the cost of streaming x twice in the tiled regimes.
    """
    n, d = x.shape
    K = cents.shape[0]
    assert block_k % 128 == 0, "block_k must be lane-aligned (multiple of 128)"
    assert block_d % 128 == 0, "block_d must be lane-aligned (multiple of 128)"

    n_p, d_p = _rup(n, block_n), _rup(d, 128)
    bk = min(block_k, _rup(max(K, 8), 128))
    k_p = _rup(max(K, 8), bk)
    nk = k_p // bk
    nq = n_p // block_n

    if d_p > block_d:                   # wide-d: d-tiled assign + reduce
        assign = kmeans_assign_pallas(x, cents, block_n=block_n,
                                      block_k=block_k, block_d=block_d,
                                      interpret=interpret)
        d_p = _rup(d, block_d)
        nd = d_p // block_d
        col = lambda kt, dt, i: (i, 0)
        sums, cnts = pl.pallas_call(
            functools.partial(_reduce_tiled_kernel_d, bk=bk),
            grid=(nk, nd, nq),                  # query tiles innermost
            in_specs=[
                pl.BlockSpec((block_n, block_d),
                             lambda kt, dt, i: (i, dt)),
                pl.BlockSpec((block_n, 1), col),
                pl.BlockSpec((block_n, 1), col),
            ],
            out_specs=[
                pl.BlockSpec((bk, block_d), lambda kt, dt, i: (kt, dt)),
                pl.BlockSpec((1, bk), lambda kt, dt, i: (0, kt)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((k_p, d_p), jnp.float32),
                jax.ShapeDtypeStruct((1, k_p), jnp.float32),
            ],
            interpret=interpret,
        )(_pad2(x, n_p, d_p), _col(w, n_p, jnp.float32),
          _col(assign, n_p, jnp.int32))
        return assign, sums[:K, :d], cnts[0, :K]

    x_p = _pad2(x, n_p, d_p)
    w_p = _col(w, n_p, jnp.float32)
    c2b = _norms_row(cents, k_p)

    if nk == 1:                                 # fused single pass
        c_p = _pad2(cents, k_p, d_p)
        whole = lambda i: (0, 0)
        col = lambda i: (i, 0)
        assign, sums, cnts = pl.pallas_call(
            _assign_reduce_kernel,
            grid=(nq,),
            in_specs=[
                pl.BlockSpec((block_n, d_p), col),
                pl.BlockSpec((k_p, d_p), whole),
                pl.BlockSpec((1, k_p), whole),
                pl.BlockSpec((block_n, 1), col),
            ],
            out_specs=[
                pl.BlockSpec((block_n, 1), col),
                pl.BlockSpec((k_p, d_p), whole),
                pl.BlockSpec((1, k_p), whole),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((n_p, 1), jnp.int32),
                jax.ShapeDtypeStruct((k_p, d_p), jnp.float32),
                jax.ShapeDtypeStruct((1, k_p), jnp.float32),
            ],
            interpret=interpret,
        )(x_p, c_p, c2b, w_p)
        return assign[:n, 0], sums[:K, :d], cnts[0, :K]

    # tiled: global argmin via the shared block_k assign kernel, then the
    # per-tile reduction (query tiles innermost — consecutive accumulation)
    assign = kmeans_assign_pallas(x, cents, block_n=block_n,
                                  block_k=block_k, interpret=interpret)
    col = lambda kt, i: (i, 0)
    sums, cnts = pl.pallas_call(
        functools.partial(_reduce_tiled_kernel, bk=bk),
        grid=(nk, nq),                          # query tiles innermost
        in_specs=[
            pl.BlockSpec((block_n, d_p), col),
            pl.BlockSpec((block_n, 1), col),
            pl.BlockSpec((block_n, 1), col),
        ],
        out_specs=[
            pl.BlockSpec((bk, d_p), lambda kt, i: (kt, 0)),
            pl.BlockSpec((1, bk), lambda kt, i: (0, kt)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k_p, d_p), jnp.float32),
            jax.ShapeDtypeStruct((1, k_p), jnp.float32),
        ],
        interpret=interpret,
    )(x_p, w_p, _col(assign, n_p, jnp.int32))
    return assign, sums[:K, :d], cnts[0, :K]
