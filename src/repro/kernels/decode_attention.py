"""Pallas TPU kernels: flash-decoding attention (one query token vs KV cache).

The §Perf H3 hot-spot: batched decode reads the whole (B,Hkv,S,hd) cache
every step. ``decode_attention_pallas`` streams the cache through VMEM in
seq blocks with online-softmax accumulation — the cache never materializes
in f32 and never needs a layout transpose (head-major storage, matching
models/attention.init_kv_cache). Both kernels follow the jnp reference
path's dtype discipline (``_masked_grouped_attn``): q·k dots in the cache
dtype, probs downcast to the value dtype before the p·v dot, f32
accumulators only — so scores and attention weights quantize identically
to the reference and greedy argmax tokens agree on bf16 caches. Grid
(B, Hkv, nS); the innermost seq dimension accumulates (m, l, acc) in VMEM
scratch. A validity bound masks unwritten cache slots (positions ≥
n_valid); it is per-batch — a (B,) vector scalar-prefetched into SMEM —
so a continuous-batching slot pool (serve/engine.py) can decode requests
sitting at different positions in one launch. A row whose bound is 0
(fully-invalid slot — e.g. a drained pool row) returns exactly 0.

``paged_decode_attention_pallas`` is the vLLM-style variant for the paged
KV pool (serve/kv_cache.alloc_page_pool): the cache is a flat pool of
fixed-size pages shared by every request, and each batch row owns a list
of page indices (its *page table* row). The page table is scalar-prefetched
(``pltpu.PrefetchScalarGridSpec``) so the BlockSpec index map can DMA each
row's pages straight from the pool — the gather never materializes in HBM.
Grid (B, Hkv, n_pages) with the page dimension innermost, same
online-softmax accumulation as the contiguous kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30



def _seq_block(S: int, block_s: int) -> int:
    """Seq block for an S-position cache: S itself when it fits one block
    (a block equal to the full dim is always a legal TPU tile), else the
    largest multiple of 8 no larger than ``block_s`` that divides S."""
    if S <= block_s:
        return S
    for bs in range(block_s - block_s % 8, 7, -8):
        if S % bs == 0:
            return bs
    raise ValueError(f"cache length {S} > block_s={block_s} has no "
                     "sublane-aligned (multiple of 8) block that divides it")


def _kernel(nv_ref, q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s, *,
            bs: int, ns: int, scale: float):
    b = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc[...] = jnp.zeros_like(acc)

    # Dtype discipline mirrors models/attention._masked_grouped_attn: dot
    # q·k in the CACHE dtype with f32 accumulation (never an f32 copy of
    # the cache tile), and downcast probs to the value dtype before the
    # p·v dot — so kernel and jnp scores/weights quantize identically and
    # argmax token parity holds on bf16 caches (tests/test_kernels.py
    # pins token equality; the online-softmax normalization order still
    # differs, so values match to tolerance, not bitwise).
    q = q_ref[0, 0].astype(k_ref.dtype)              # (g, hd)
    k = k_ref[0, 0]                                  # (bs, hd)
    v = v_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = ik * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = pos < nv_ref[b]
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # re-mask after the exp: when a row has NO valid positions m_new stays
    # NEG_INF and exp(s - m_new) would be 1 everywhere — the row must
    # instead accumulate l = 0 and emit exactly 0 (see _final's guard)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc[...] = acc[...] * corr + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(ik == ns - 1)
    def _final():
        # max(l, tiny) guard: a fully-invalid row has l = 0 → emits 0
        o_ref[0, 0] = (acc[...] / jnp.maximum(l_s[...], 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention_pallas(q, k_cache, v_cache, n_valid, *, interpret: bool,
                            block_s: int = 512):
    """q: (B, Hkv, g, hd); caches: (B, Hkv, S, hd) head-major;
    n_valid: scalar int32 — number of filled cache slots — or a (B,)
    vector giving each batch row (pool slot) its own validity bound.
    S is any length up to ``block_s``, or a multiple of 8 beyond it (see
    ``_seq_block``). The validity vector is scalar-prefetched into SMEM
    (as in the paged kernel). Returns (B, Hkv, g, hd)."""
    B, Hkv, g, hd = q.shape
    S = k_cache.shape[2]
    bs = _seq_block(S, block_s)
    ns = S // bs
    nv = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32).reshape(-1), (B,))

    kern = functools.partial(_kernel, bs=bs, ns=ns, scale=hd ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                       # n_valid
        grid=(B, Hkv, ns),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda b, h, i, nv: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, h, i, nv: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, h, i, nv: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda b, h, i, nv: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, hd), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(nv, q, k_cache, v_cache)


# ---------------------------------------------------------------------------
# Paged variant: gather-by-page-table via scalar prefetch
# ---------------------------------------------------------------------------


def _paged_kernel(pt_ref, nv_ref, q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s,
                  *, ps: int, npg: int, scale: float):
    """One (batch row, kv head, page) step. The page table was consumed by
    the BlockSpec index maps (scalar prefetch) to DMA this row's i-th page
    out of the pool; here only the logical position bookkeeping remains:
    page i of a row covers absolute positions [i*ps, (i+1)*ps)."""
    b = pl.program_id(0)
    ip = pl.program_id(2)

    @pl.when(ip == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc[...] = jnp.zeros_like(acc)

    # same dtype discipline as _kernel (and therefore as the jnp
    # reference path): cache-dtype dots, f32 accumulation
    q = q_ref[0, 0].astype(k_ref.dtype)              # (g, hd)
    k = k_ref[0, 0]                                  # (ps, hd)
    v = v_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = ip * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = pos < nv_ref[b]
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc[...] = acc[...] * corr + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(ip == npg - 1)
    def _final():
        o_ref[0, 0] = (acc[...] / jnp.maximum(l_s[...], 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(q, k_pool, v_pool, page_table, n_valid, *,
                                  interpret: bool):
    """q: (B, Hkv, g, hd); pools: (P, Hkv, page_size, hd) page-major — one
    flat page pool shared by every batch row; page_table: (B, npg) int32 —
    row b's i-th entry is the pool page holding its logical positions
    [i*page_size, (i+1)*page_size); n_valid: (B,) int32 per-row validity
    bound (entries past it — including trash-page table entries — are
    masked; a 0 bound emits exactly 0). Returns (B, Hkv, g, hd).

    The page table and validity vector are scalar-prefetched so the k/v
    BlockSpec index maps can address the pool by page id — each (b, h, i)
    grid step DMAs exactly one (page_size, hd) page into VMEM; the gathered
    (B, npg*page_size) view never materializes.
    """
    B, Hkv, g, hd = q.shape
    ps = k_pool.shape[2]
    npg = page_table.shape[1]
    pt = jnp.asarray(page_table, jnp.int32)
    nv = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32).reshape(-1), (B,))

    kern = functools.partial(_paged_kernel, ps=ps, npg=npg, scale=hd ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # page table + n_valid
        grid=(B, Hkv, npg),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda b, h, i, pt, nv: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd),
                         lambda b, h, i, pt, nv: (pt[b, i], h, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd),
                         lambda b, h, i, pt, nv: (pt[b, i], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda b, h, i, pt, nv: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, hd), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pt, nv, q, k_pool, v_pool)
    return out
