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
of page indices (its *page table* row). The page table and validity
vector are scalar-prefetched into SMEM; the pools stay in HBM. Grid (B,):
a row walks its table in blocks of ``paged_block_pages`` entries, only as
far as its validity bound reaches, and DMAs each page of a block — every
KV head of it in one copy, the pool being page-major — into a
double-buffered VMEM block while the previous block is computed. Work
follows each row's length, not the table's width; the gather never
materializes in HBM. Per block and KV head, the same online softmax as
the contiguous kernel, with the running output kept normalized.
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
# Paged variant: ragged page-block fetching from the pool in HBM
# ---------------------------------------------------------------------------

#: bytes of K (one block of pages, every KV head) the paged kernel fetches
#: per block; V is fetched alongside, and each is double-buffered. Chosen
#: by a chip sweep at the served lanes' shapes (PERF.md section 6)
PAGED_BLOCK_BYTES = 64 * 1024


def paged_block_pages(npg: int, page_bytes: int) -> int:
    """Pages per block of the paged kernel: the largest power of two no
    larger than the table width ``npg`` whose pages (``page_bytes`` each:
    every KV head of one page) stay within ``PAGED_BLOCK_BYTES``; at
    least 1."""
    ppb = 1
    while 2 * ppb <= npg and 2 * ppb * page_bytes <= PAGED_BLOCK_BYTES:
        ppb *= 2
    return ppb


def _paged_kernel(pt_ref, nv_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf,
                  sem, acc, m_s, l_s, *, ps: int, npg: int, ppb: int,
                  scale: float):
    """One batch row. Its valid positions are walked in blocks of ``ppb``
    table entries (``ppb * ps`` positions); block i's pages are DMA'd from
    the pools in HBM, one copy per page holding every KV head, into slot
    i % 2 of the VMEM buffers while block i - 1 is computed. Blocks past
    the row's validity bound are neither fetched nor computed."""
    b = pl.program_id(0)
    hkv = q_ref.shape[1]
    bk = ppb * ps
    nv = jnp.minimum(nv_ref[b], npg * ps)
    n_blocks = pl.cdiv(nv, bk)

    def pages(i, slot, op):
        """Start (or wait for) block i's copies into buffer ``slot``: its
        ``ppb`` table entries, fewer where the table ends inside it."""
        def page(j, carry):
            src = pt_ref[b, i * ppb + j]
            for hbm, buf, kv in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                getattr(pltpu.make_async_copy(hbm.at[src], buf.at[slot, j],
                                              sem.at[kv, slot]), op)()
            return carry
        jax.lax.fori_loop(0, jnp.minimum(ppb, npg - i * ppb), page, 0)

    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc[...] = jnp.zeros_like(acc)

    @pl.when(n_blocks > 0)
    def _first():
        pages(0, 0, "start")

    def block(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _next():
            pages(i + 1, 1 - slot, "start")

        pages(i, slot, "wait")
        pos = i * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        valid = pos < nv
        for h in range(hkv):
            # same dtype discipline as _kernel (and therefore as the jnp
            # reference path): cache-dtype dots, f32 accumulation
            q = q_ref[0, h].astype(k_buf.dtype)              # (g, hd)
            k = k_buf[slot, :, h].reshape(bk, -1)            # (bk, hd)
            v = v_buf[slot, :, h].reshape(bk, -1)
            if npg % ppb:
                # the last block's entries past the table were not fetched:
                # their buffer rows hold whatever an earlier block left
                row = i * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
                v = jnp.where(row < nv, v, 0)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid, s * scale, NEG_INF)
            m_prev, l_prev = m_s[h], l_s[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
            # acc holds the output normalized by the running l, so the
            # probabilities are normalized before their downcast, as the
            # reference's softmax is: a row that fits one block rounds
            # exactly as the jnp path does
            acc[h] = acc[h] * (l_prev * corr / l_new) + jax.lax.dot(
                (p / l_new).astype(v.dtype), v,
                preferred_element_type=jnp.float32)
            m_s[h], l_s[h] = m_new, l_new
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    # a row with no valid position runs no block and emits exactly 0
    o_ref[0] = acc[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(q, k_pool, v_pool, page_table, n_valid, *,
                                  interpret: bool):
    """q: (B, Hkv, g, hd); pools: (P, Hkv, page_size, hd) page-major — one
    flat page pool shared by every batch row; page_table: (B, npg) int32 —
    row b's i-th entry is the pool page holding its logical positions
    [i*page_size, (i+1)*page_size); n_valid: (B,) int32 per-row validity
    bound (entries past it — including trash-page table entries — are
    masked; a 0 bound emits exactly 0). Returns (B, Hkv, g, hd).

    The page table and validity vector are scalar-prefetched into SMEM;
    the pools stay in HBM and the kernel DMAs each row's pages itself,
    ``paged_block_pages`` of them a block, as far as the row's bound
    reaches — the gathered (B, npg*page_size) view never materializes.
    """
    B, Hkv, g, hd = q.shape
    ps = k_pool.shape[2]
    npg = page_table.shape[1]
    ppb = paged_block_pages(npg, Hkv * ps * hd * k_pool.dtype.itemsize)
    pt = jnp.asarray(page_table, jnp.int32)
    nv = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32).reshape(-1), (B,))

    kern = functools.partial(_paged_kernel, ps=ps, npg=npg, ppb=ppb,
                             scale=hd ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # page table + n_valid
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hkv, g, hd), lambda b, pt, nv: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hkv, g, hd),
                               lambda b, pt, nv: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, Hkv, ps, hd), k_pool.dtype),
            pltpu.VMEM((2, ppb, Hkv, ps, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),         # (k|v, buffer slot)
            pltpu.VMEM((Hkv, g, hd), jnp.float32),
            pltpu.VMEM((Hkv, g, 1), jnp.float32),
            pltpu.VMEM((Hkv, g, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(pt, nv, q, k_pool, v_pool)
