"""Pallas TPU kernel: blockwise online-softmax (flash) attention — prefill.

Grid (B, H, nq, nk); the innermost nk dimension accumulates into VMEM
scratch (running max m, normalizer l, weighted accumulator acc) — the
classic flash schedule mapped to TPU: q/k/v tiles are DMA'd HBM→VMEM per
block, qkᵀ and p·v hit the MXU, the online-softmax rescale is VPU work.
Causal masking is computed from block indices; fully-masked k-blocks are
skipped via ``pl.when`` (the causal wedge does ~half the work).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # python float: avoids capturing a traced constant


def _kernel(q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s, *,
            causal: bool, bq: int, bk: int, nk: int, scale: float):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc[...] = jnp.zeros_like(acc)

    # Skip k-blocks strictly above the causal diagonal.
    run = (ik * bk <= iq * bq + bq - 1) if causal else (ik >= 0)

    @pl.when(run)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)        # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)        # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc[...] = acc[...] * corr + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_s[...] = m_new

    @pl.when(ik == nk - 1)
    def _final():
        o_ref[0, 0] = (acc[...] / jnp.maximum(l_s[...], 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret"))
def flash_attention_pallas(q, k, v, *, interpret: bool, causal: bool = True,
                           block_q: int = 128, block_k: int = 128):
    """q,k,v: (B, S, H, hd) (equal head counts) → (B, S, H, hd)."""
    B, S, H, hd = q.shape
    bq, bk = min(block_q, S), min(block_k, S)
    assert S % bq == 0 and S % bk == 0, "seq must divide block sizes"
    nq, nk = S // bq, S // bk
    scale = hd ** -0.5

    # layout (B, H, S, hd) for clean per-(batch, head) tiling
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)

    kern = functools.partial(_kernel, causal=causal, bq=bq, bk=bk, nk=nk,
                             scale=scale)
    out = pl.pallas_call(
        kern,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),   # acc
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # normalizer
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.moveaxis(out, 1, 2)
