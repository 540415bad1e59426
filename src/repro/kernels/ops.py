"""jit'd dispatch wrappers for the Pallas kernels.

On TPU the compiled kernels run natively (``interpret=False``); on any
other backend the pure-jnp oracles from ``ref.py`` are the default, and a
forced ``impl="pallas"`` (or ``REPRO_KERNELS=pallas``) runs the kernels
interpreted — how the CPU tests exercise them. The ``*_pallas`` wrappers
take ``interpret`` with no default: this module is the one place that
decides it, from the backend.
"""
from __future__ import annotations

import os

import jax

from repro.kernels import ref
from repro.kernels.decode_attention import (decode_attention_pallas,
                                            paged_decode_attention_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.kmeans_assign import (kmeans_assign_pallas,
                                         kmeans_assign_reduce_pallas)
from repro.kernels.router_utility import router_utility_pallas


def _default_impl() -> str:
    env = os.environ.get("REPRO_KERNELS")
    if env in ("ref", "pallas"):
        return env
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def kmeans_assign(x, cents, *, impl: str | None = None,
                  block_d: int = 2048):
    impl = impl or _default_impl()
    if impl == "pallas":
        return kmeans_assign_pallas(x, cents, block_d=block_d,
                                    interpret=_interpret())
    return ref.kmeans_assign_ref(x, cents)


def kmeans_assign_reduce(x, cents, w, *, impl: str | None = None,
                         block_k: int = 512, block_d: int = 2048):
    """Fused Lloyd's-step op: nearest-centroid assignment + per-cluster
    weighted coordinate sums and counts in one pass over x. The centroid
    table is streamed through VMEM in ``block_k`` tiles (K in the
    thousands stays resident); rows wider than ``block_d`` stream their
    features in tiles too (very wide embeddings never hold a full row in
    VMEM)."""
    impl = impl or _default_impl()
    if impl == "pallas":
        return kmeans_assign_reduce_pallas(x, cents, w, block_k=block_k,
                                           block_d=block_d,
                                           interpret=_interpret())
    return ref.kmeans_assign_reduce_ref(x, cents, w)


def router_utility(h, acc_w, acc_b, cost_w, cost_b, lam, *,
                   impl: str | None = None):
    impl = impl or _default_impl()
    if impl == "pallas":
        return router_utility_pallas(h, acc_w, acc_b, cost_w, cost_b, lam,
                                     interpret=_interpret())
    return ref.router_utility_ref(h, acc_w, acc_b, cost_w, cost_b, lam)


def flash_attention(q, k, v, *, causal: bool = True, impl: str | None = None):
    impl = impl or _default_impl()
    if impl == "pallas":
        return flash_attention_pallas(q, k, v, causal=causal,
                                      interpret=_interpret())
    return ref.flash_attention_ref(q, k, v, causal=causal)


def decode_attention(q, k_cache, v_cache, n_valid, *, impl: str | None = None):
    impl = impl or _default_impl()
    if impl == "pallas":
        return decode_attention_pallas(q, k_cache, v_cache, n_valid,
                                       interpret=_interpret())
    return ref.decode_attention_ref(q, k_cache, v_cache, n_valid)


def paged_decode_attention(q, k_pool, v_pool, page_table, n_valid, *,
                           impl: str | None = None):
    """Decode attention against the paged KV pool (serve/kv_cache): each
    batch row attends the pages its page-table row names. On TPU the Pallas
    kernel DMAs pages via scalar prefetch; the CPU fallback runs the
    segment-summed formulation (ref.paged_decode_attention_seg_ref), which
    reads the pools in place instead of materializing each row's
    (B, Hkv, npg·ps, hd) gathered copy. The gather-based oracle
    (ref.paged_decode_attention_ref) stays the parity ground truth in
    tests for both this fallback and the Pallas kernel."""
    impl = impl or _default_impl()
    if impl == "pallas":
        return paged_decode_attention_pallas(q, k_pool, v_pool, page_table,
                                             n_valid, interpret=_interpret())
    return ref.paged_decode_attention_seg_ref(q, k_pool, v_pool, page_table,
                                              n_valid)
