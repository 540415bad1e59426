"""Pallas TPU kernel: fused routing decision.

Serving-gateway hot spot: given trunk features h, compute per-model
accuracy/cost head projections, the utility U_λ = σ(h·Wa+ba) − λ(h·Wc+bc),
and its argmax — in one VMEM-resident pass, so the (n, M) accuracy/cost
tensors never round-trip to HBM. Both head matmuls hit the MXU; sigmoid,
the λ-combine and the argmax/max reductions run on the VPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(h_ref, aw_ref, ab_ref, cw_ref, cb_ref, lam_ref, mask_ref,
            choice_ref, best_ref):
    h = h_ref[...].astype(jnp.float32)                       # (BN, dh)
    A = jax.nn.sigmoid(
        jax.lax.dot(h, aw_ref[...].astype(jnp.float32),
                    preferred_element_type=jnp.float32) + ab_ref[...])
    C = jax.lax.dot(h, cw_ref[...].astype(jnp.float32),
                    preferred_element_type=jnp.float32) + cb_ref[...]
    U = A - lam_ref[0, 0] * C + mask_ref[...]                # (BN, M)
    # (BN, 1) columns from keepdims lane reductions; ties keep the lowest
    # model index, like jnp.argmax
    best = jnp.max(U, axis=1, keepdims=True)
    iota = jax.lax.broadcasted_iota(jnp.int32, U.shape, 1)
    choice_ref[...] = jnp.min(jnp.where(U == best, iota, U.shape[1]), axis=1,
                              keepdims=True)
    best_ref[...] = best


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def router_utility_pallas(h, acc_w, acc_b, cost_w, cost_b, lam, *,
                          interpret: bool, block_n: int = 256):
    """h: (n, dh); heads (dh, M)/(M,); lam scalar → (choice (n,), best (n,)).
    Both outputs leave the kernel as (n, 1) columns: a 1-D output block
    does not match XLA's tiling once n spans more than one block."""
    n, dh = h.shape
    M = acc_w.shape[1]

    def rup(v, m):
        return (v + m - 1) // m * m

    n_p, dh_p, m_p = rup(n, block_n), rup(dh, 128), rup(max(M, 8), 128)
    h_p = jnp.zeros((n_p, dh_p), h.dtype).at[:n, :dh].set(h)

    def pad_w(w):
        return jnp.zeros((dh_p, m_p), jnp.float32).at[:dh, :M].set(
            w.astype(jnp.float32))

    def pad_b(b):
        return jnp.zeros((1, m_p), jnp.float32).at[0, :M].set(
            b.astype(jnp.float32))

    mask = jnp.where(jnp.arange(m_p) < M, 0.0, -jnp.inf)[None, :]
    lam_arr = jnp.full((1, 1), lam, jnp.float32)

    grid = (n_p // block_n,)
    whole = lambda i: (0, 0)
    choice, best = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, dh_p), lambda i: (i, 0)),
            pl.BlockSpec((dh_p, m_p), whole),
            pl.BlockSpec((1, m_p), whole),
            pl.BlockSpec((dh_p, m_p), whole),
            pl.BlockSpec((1, m_p), whole),
            pl.BlockSpec((1, 1), whole),
            pl.BlockSpec((1, m_p), whole),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_p, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_p, 1), jnp.float32),
        ],
        interpret=interpret,
    )(h_p, pad_w(acc_w), pad_b(acc_b), pad_w(cost_w), pad_b(cost_b),
      lam_arr, mask)
    return choice[:n, 0], best[:n, 0]
