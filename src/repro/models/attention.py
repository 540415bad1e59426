"""Attention layer: MHA/GQA with RoPE, qk-norm, optional QKV bias.

Paths:
  * ``attn_forward``     — train / prefill attention, computed in query
    chunks (``lax.scan`` over blocks, mask generated on the fly) so the
    S×S score matrix is never materialized — pure-JAX flash-style memory
    behaviour; the Pallas kernel in ``repro.kernels.flash_attention`` is the
    TPU hot-spot version of the same schedule.
  * ``attn_decode_step`` — one-token decode against a KV cache; supports a
    rolling (sliding-window) cache for long contexts.

Logical sharding: batch → ("pod","data"), flat head dim → "model",
batch=1 decode-cache seq → "data" (see launch/sharding.py).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import layers as L
from repro.sharding import constrain, kernel_call

#: logical axes of the decode kernels' operands, for ``kernel_call``:
#: queries and uniform caches (B, Hkv, g|S, hd), paged pools (P, Hkv, ps, hd)
_ROWS_HEADS = ("batch", "heads4d", None, None)
_PAGES = (None, "heads4d", None, None)


def init_attn(key, cfg: ModelConfig) -> dict:
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = L.dtype_of(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d ** -0.5
    p = {
        "wq": L._normal(k1, (d, hq * hd), s, dt),
        "wk": L._normal(k2, (d, hkv * hd), s, dt),
        "wv": L._normal(k3, (d, hkv * hd), s, dt),
        "wo": L._normal(k4, (hq * hd, d), (hq * hd) ** -0.5, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), dt)
        p["bk"] = jnp.zeros((hkv * hd,), dt)
        p["bv"] = jnp.zeros((hkv * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = L.init_rmsnorm(hd, dt)
        p["k_norm"] = L.init_rmsnorm(hd, dt)
    return p


def _project_qkv(p, x, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = constrain(x @ p["wq"], ("batch", "seq", "heads"))
    k = constrain(x @ p["wk"], ("batch", "seq", "kv_heads"))
    v = constrain(x @ p["wv"], ("batch", "seq", "kv_heads"))
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, hq, hd)
    k = k.reshape(B, S, hkv, hd)
    v = v.reshape(B, S, hkv, hd)
    if cfg.qk_norm:
        q = L.rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = L.rmsnorm(p["k_norm"], k, cfg.norm_eps)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def chunked_attention(q, k, v, *, causal: bool, window: Optional[int],
                      q_chunk: int = 512, layout: str = "grouped"):
    """Query-chunked attention; no (S,S) materialization.

    q: (B,S,Hq,hd); k,v: (B,Sk,Hkv,hd). Returns (B,S,Hq*hd).

    layout="grouped" keeps KV unexpanded (B,Sk,Hkv,g,…) — minimal memory,
    but the (Hkv, g) split is unshardable when Hq doesn't divide the TP
    axis. layout="flat" repeats KV to Hq heads and shards the head dim
    *unevenly* ("heads!") over the TP axis — the §Perf fix for archs like
    yi-34b (56 heads on a 16-way axis): scores stay head-local, so the
    per-chunk score all-reduce disappears.
    """
    B, S, Hq, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    qc = min(q_chunk, S)
    while S % qc:
        qc //= 2
    nq = S // qc
    scale = hd ** -0.5
    kpos = jnp.arange(Sk)

    if layout == "flat":
        k = constrain(jnp.repeat(k, g, axis=2),
                      ("batch", "seq", "heads4d!", None))
        v = constrain(jnp.repeat(v, g, axis=2),
                      ("batch", "seq", "heads4d!", None))
        q = constrain(q, ("batch", "seq", "heads4d!", None))
        qg = jnp.moveaxis(q.reshape(B, nq, qc, Hq, hd), 1, 0)

        def body(_, inp):
            q_blk, idx = inp
            qpos = idx * qc + jnp.arange(qc)
            scores = jnp.einsum("bqhd,bkhd->bhqk",
                                q_blk.astype(jnp.float32),
                                k.astype(jnp.float32)) * scale
            scores = constrain(scores, ("batch", "heads4d!", None, None))
            if causal:
                m = kpos[None, :] <= qpos[:, None]
                if window is not None:
                    m &= (qpos[:, None] - kpos[None, :]) < window
                scores = jnp.where(m[None, None], scores, jnp.float32(-1e30))
            probs = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
            return None, out.reshape(B, qc, Hq * hd)

        _, outs = jax.lax.scan(body, None, (qg, jnp.arange(nq)))
        return jnp.moveaxis(outs, 0, 1).reshape(B, S, Hq * hd)

    qg = q.reshape(B, nq, qc, Hkv, g, hd)
    qg = jnp.moveaxis(qg, 1, 0)  # (nq, B, qc, Hkv, g, hd)

    def body(_, inp):
        q_blk, idx = inp
        qpos = idx * qc + jnp.arange(qc)
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        if causal:
            m = kpos[None, :] <= qpos[:, None]
            if window is not None:
                m &= (qpos[:, None] - kpos[None, :]) < window
            scores = jnp.where(m[None, None, None], scores, jnp.float32(-1e30))
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
        return None, out.reshape(B, qc, Hq * hd)

    _, outs = jax.lax.scan(body, None, (qg, jnp.arange(nq)))
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, Hq * hd)


def attn_forward(p: dict, x: jnp.ndarray, cfg: ModelConfig,
                 positions: jnp.ndarray, *, window: Optional[int] = None,
                 q_chunk: int = 512, return_kv: bool = False,
                 layout: str = "grouped"):
    q, k, v = _project_qkv(p, x, cfg, positions)
    win = window if window is not None else (
        cfg.sliding_window if cfg.sliding_window_always else None)
    out = chunked_attention(q, k, v, causal=cfg.causal, window=win,
                            q_chunk=q_chunk, layout=layout)
    out = constrain(out, ("batch", "seq", "heads"))
    out = out @ p["wo"]
    if return_kv:  # prefill: post-RoPE k/v become the decode cache
        return out, {"k": jnp.moveaxis(k, 1, 2), "v": jnp.moveaxis(v, 1, 2)}
    return out


# ---------------------------------------------------------------------------
# Decode with KV cache
# ---------------------------------------------------------------------------


def init_kv_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype=None):
    """Cache layout (B, Hkv, S, hd) — head-major so the decode dot consumes
    it without a per-step full-cache layout transpose (§Perf H3 iter 3)."""
    dt = dtype or L.dtype_of(cfg)
    shape = (batch, cfg.n_kv_heads, cache_len, cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def attn_decode_step(p: dict, x: jnp.ndarray, cache: dict, pos: jnp.ndarray,
                     cfg: ModelConfig, *, rolling: bool) -> tuple:
    """x: (B, 1, d). pos: int32 absolute position → (out, new_cache).

    pos may be a scalar (all rows at the same position — the classic
    same-age batch) or a (B,) vector (continuous batching: every cache row
    is a pool *slot* holding a different request at its own position; RoPE,
    the cache write, and the attention-validity mask are all per-slot).

    rolling=True → cache length W is a sliding window written at ``pos % W``;
    RoPE is applied before caching, so slot order is irrelevant.
    """
    B = x.shape[0]
    W = cache["k"].shape[2]
    per_slot = jnp.ndim(pos) == 1
    positions = (pos[:, None].astype(jnp.int32) if per_slot
                 else jnp.full((1, 1), pos, dtype=jnp.int32))
    q, k_new, v_new = _project_qkv(p, x, cfg, positions)
    slot = (pos % W if rolling else pos).astype(jnp.int32)
    k_new = jnp.moveaxis(k_new, 1, 2)  # (B, Hkv, 1, hd)
    v_new = jnp.moveaxis(v_new, 1, 2)
    if per_slot:
        upd = jax.vmap(lambda c, u, s:
                       jax.lax.dynamic_update_slice(c, u, (0, s, 0)))
        k_cache = upd(cache["k"], k_new, slot)
        v_cache = upd(cache["v"], v_new, slot)
    else:
        k_cache = jax.lax.dynamic_update_slice(cache["k"], k_new,
                                               (0, 0, slot, 0))
        v_cache = jax.lax.dynamic_update_slice(cache["v"], v_new,
                                               (0, 0, slot, 0))
    k_cache = constrain(k_cache, ("batch", None, "kv_seq", None))
    v_cache = constrain(v_cache, ("batch", None, "kv_seq", None))
    # Validity: before the window wraps, only slots [0, pos] are filled —
    # per row when pos is a vector ((B, W)), shared otherwise ((1, W)).
    n_valid = jnp.minimum(pos + 1, W)

    Hkv, hd, g = cfg.n_kv_heads, cfg.head_dim, cfg.q_per_kv
    qg = q.reshape(B, Hkv, g, hd)
    from repro.kernels import ops as kops
    if kops._default_impl() == "pallas":
        # TPU: stream the cache through the flash-decoding kernel (online
        # softmax, f32 accumulation, no f32 cache copy) — the same kernel
        # family the paged path dispatches to; the engine's uniform decode
        # scan rides this too. It tiles any W up to its seq block and any
        # multiple of 8 beyond (extend_cache rounds to one). The kernel
        # shares this path's dtype discipline (cache-dtype dots, f32
        # accumulation), so greedy tokens agree on bf16 caches
        # (tests/test_kernels.py).
        out = kernel_call(kops.decode_attention,
                          (qg, k_cache, v_cache, n_valid),
                          (_ROWS_HEADS, _ROWS_HEADS, _ROWS_HEADS,
                           ("batch",) if per_slot else ()))
        out = out.astype(v_cache.dtype)
    else:
        valid = (jnp.arange(W)[None, :] < n_valid[:, None] if per_slot
                 else jnp.arange(W)[None, :] < n_valid)
        out = _masked_grouped_attn(qg, k_cache, v_cache, valid)
    out = out.reshape(B, 1, cfg.n_heads * hd)
    return out @ p["wo"], {"k": k_cache, "v": v_cache}


def _masked_grouped_attn(qg, k_cache, v_cache, valid):
    """The decode attention block shared by the contiguous and paged (CPU
    fallback) paths — ONE definition so the engine-vs-solo token-parity
    guarantee can't silently split across copies. qg: (B, Hkv, g, hd);
    caches (B, Hkv, K, hd); valid: (B|1, K) bool. Dot in the cache dtype
    with f32 accumulation: upcasting the cache (k.astype(f32)) makes XLA
    materialize an f32 copy of the whole cache every step — measured 60%
    of decode HBM traffic (§Perf H3 iter 2). Returns (B, Hkv, g, hd) in
    the cache dtype."""
    hd = qg.shape[-1]
    scores = jnp.einsum("bhgd,bhkd->bhgk", qg.astype(k_cache.dtype), k_cache,
                        preferred_element_type=jnp.float32) * hd ** -0.5
    scores = jnp.where(valid[:, None, None], scores, jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhgk,bhkd->bhgd", probs.astype(v_cache.dtype), v_cache,
                      preferred_element_type=jnp.float32).astype(v_cache.dtype)


def _masked_grouped_attn_multi(qg, k_cache, v_cache, valid):
    """Multi-position variant of ``_masked_grouped_attn`` for the
    speculative verify step: T query positions per row, folded into the
    query-group axis so the einsum strings — and therefore the per-row
    contraction discipline the token-parity guarantee rests on — are
    IDENTICAL to the single-token path (each folded query row is the same
    dot over hd, masked softmax over K, and dot over K as a lone decode
    query; only the causal bound varies per offset). qg:
    (B, Hkv, T, g, hd); caches (B, Hkv, K, hd); valid: (B, T, K) bool
    (query offset t attends keys below its own bound). Returns
    (B, Hkv, T, g, hd) in the cache dtype."""
    B, Hkv, T, g, hd = qg.shape
    K = k_cache.shape[2]
    qf = qg.reshape(B, Hkv, T * g, hd)
    scores = jnp.einsum("bhgd,bhkd->bhgk", qf.astype(k_cache.dtype), k_cache,
                        preferred_element_type=jnp.float32) * hd ** -0.5
    mask = jnp.broadcast_to(valid[:, None, :, None, :], (B, Hkv, T, g, K))
    scores = jnp.where(mask.reshape(B, Hkv, T * g, K), scores,
                       jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", probs.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32).astype(v_cache.dtype)
    return out.reshape(B, Hkv, T, g, hd)


# ---------------------------------------------------------------------------
# Paged decode (vLLM-style page pool — serve/kv_cache.alloc_page_pool)
# ---------------------------------------------------------------------------


def init_paged_kv_cache(cfg: ModelConfig, pages: int, page_size: int,
                        dtype=None):
    """One layer's page pool: (pages, Hkv, page_size, hd) page-major — the
    slot-pool layout with the batch dim reinterpreted as a flat pool of
    fixed-size pages shared by every in-flight request."""
    return init_kv_cache(cfg, pages, page_size, dtype)


def attn_decode_step_paged(p: dict, x: jnp.ndarray, cache: dict,
                           page_table: jnp.ndarray, pos: jnp.ndarray,
                           cfg: ModelConfig) -> tuple:
    """One-token decode against the paged pool. x: (B, 1, d);
    cache leaves (P, Hkv, page_size, hd) shared by all rows; page_table:
    (B, npg) int32 — row b's i-th entry is the pool page holding its
    logical positions [i*page_size, (i+1)*page_size); pos: (B,) int32
    absolute positions (always per-row — paging exists for continuous
    batching). Returns (out, new_cache).

    The new K/V lands at (page_table[b, pos_b // ps], pos_b % ps); rows
    whose table entry is the trash page (index 0 by serve/kv_cache
    convention) scatter harmlessly there. On TPU attention runs the
    Pallas kernel (``paged_decode_attention_pallas`` — each row's valid
    pages DMA'd by table lookup, gather never materialized); elsewhere it
    gathers the pages and reuses ``attn_decode_step``'s exact einsum
    discipline — dot in the cache dtype with f32 accumulation — so engine
    tokens stay bit-identical to the solo scan path on every dtype (a
    blanket f32 upcast diverges from the contiguous path on bf16 models).
    """
    from repro.kernels import ops as kops
    B = x.shape[0]
    ps = cache["k"].shape[2]
    npg = page_table.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[:, None]
    q, k_new, v_new = _project_qkv(p, x, cfg, positions)
    k_new = jnp.moveaxis(k_new, 1, 2)[:, :, 0]        # (B, Hkv, hd)
    v_new = jnp.moveaxis(v_new, 1, 2)[:, :, 0]
    pages = jnp.take_along_axis(page_table, (pos // ps)[:, None], axis=1)[:, 0]
    off = pos % ps
    # scatter each row's token into its page; duplicate targets only ever
    # happen on the trash page (inactive rows), where any value is fine
    k_cache = cache["k"].at[pages, :, off].set(k_new.astype(cache["k"].dtype))
    v_cache = cache["v"].at[pages, :, off].set(v_new.astype(cache["v"].dtype))

    Hkv, hd, g = cfg.n_kv_heads, cfg.head_dim, cfg.q_per_kv
    qg = q.reshape(B, Hkv, g, hd)
    if kops._default_impl() == "pallas":
        out = kernel_call(kops.paged_decode_attention,
                          (qg, k_cache, v_cache, page_table, pos + 1),
                          (_ROWS_HEADS, _PAGES, _PAGES, ("batch", None),
                           ("batch",)))
    else:
        # Deliberately the GATHER formulation, not the copy-free
        # segment-summed one (ref.paged_decode_attention_seg_ref, the CPU
        # fallback of kops.paged_decode_attention): the engine's tokens
        # must stay bit-identical to solo serving, and that requires the
        # softmax normalizer and V contraction to reduce in the same
        # logical-position order as _masked_grouped_attn — the seg form
        # reduces pool-major and differs in the last ulp.
        from repro.kernels.ref import paged_gather_ref
        k_g = paged_gather_ref(k_cache, page_table)   # (B, Hkv, npg*ps, hd)
        v_g = paged_gather_ref(v_cache, page_table)
        n_valid = jnp.minimum(pos + 1, npg * ps)
        valid = jnp.arange(npg * ps)[None, :] < n_valid[:, None]
        out = _masked_grouped_attn(qg, k_g, v_g, valid)
    out = out.astype(v_cache.dtype).reshape(B, 1, cfg.n_heads * hd)
    return out @ p["wo"], {"k": k_cache, "v": v_cache}


# ---------------------------------------------------------------------------
# Speculative multi-position verify (serve/engine.py draft/verify rounds)
# ---------------------------------------------------------------------------


def attn_decode_verify(p: dict, x: jnp.ndarray, cache: dict,
                       pos: jnp.ndarray, cfg: ModelConfig) -> tuple:
    """Multi-position decode against the uniform slot pool: row b carries
    T consecutive tokens at absolute positions pos_b .. pos_b+T-1 (the
    last committed token plus the drafted window). x: (B, T, d); pos: (B,)
    int32 base positions. All T K/V entries are written BEFORE attention
    (write-ahead — the cache's validity convention is per-query causal
    masking, so query offset t sees exactly positions < pos_b+t+1,
    including the drafts written by this same dispatch), and the write is
    a scatter with out-of-bounds DROP: near the region end the
    write-ahead window may poke past the pool's seq extent, and those
    positions are never committed — dropping them keeps in-bounds cache
    contents intact where a clamped ``dynamic_update_slice`` would smear
    over live positions. Rollback of a rejected suffix is pure host
    bookkeeping (the engine resets ``pos``): stale drafted K/V above the
    new position is masked by validity and overwritten — each later step
    writes a position before any query's bound reaches it. Returns
    (out (B, T, d), new_cache)."""
    B, T, _ = x.shape
    W = cache["k"].shape[2]
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    q, k_new, v_new = _project_qkv(p, x, cfg, positions)   # (B, T, Hkv, hd)
    b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]
    k_cache = cache["k"].at[b_idx, :, positions].set(
        k_new.astype(cache["k"].dtype), mode="drop")
    v_cache = cache["v"].at[b_idx, :, positions].set(
        v_new.astype(cache["v"].dtype), mode="drop")
    k_cache = constrain(k_cache, ("batch", None, "kv_seq", None))
    v_cache = constrain(v_cache, ("batch", None, "kv_seq", None))
    valid = jnp.arange(W)[None, None, :] < (positions + 1)[:, :, None]

    Hkv, hd, g = cfg.n_kv_heads, cfg.head_dim, cfg.q_per_kv
    qg = jnp.moveaxis(q.reshape(B, T, Hkv, g, hd), 1, 2)  # (B, Hkv, T, g, hd)
    out = _masked_grouped_attn_multi(qg, k_cache, v_cache, valid)
    out = jnp.moveaxis(out, 2, 1).reshape(B, T, cfg.n_heads * hd)
    return out @ p["wo"], {"k": k_cache, "v": v_cache}


def attn_decode_verify_paged(p: dict, x: jnp.ndarray, cache: dict,
                             page_table: jnp.ndarray, pos: jnp.ndarray,
                             cfg: ModelConfig) -> tuple:
    """Multi-position decode against the paged pool — the paged twin of
    ``attn_decode_verify``. x: (B, T, d); page_table: (B, npg) int32;
    pos: (B,) int32 base positions. Write-ahead targets each position's
    own page; positions past the table's logical extent — and positions
    whose page was never claimed (table entry 0) — scatter into the trash
    page by the serve/kv_cache convention, so speculative overflow can
    never corrupt a live page. Attention gathers the pages and reuses the
    single-token path's exact einsum discipline (dot in the cache dtype,
    f32 accumulation) with a per-offset causal bound. Returns
    (out (B, T, d), new_cache)."""
    from repro.kernels.ref import paged_gather_ref
    B, T, _ = x.shape
    ps = cache["k"].shape[2]
    npg = page_table.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    q, k_new, v_new = _project_qkv(p, x, cfg, positions)   # (B, T, Hkv, hd)
    in_bounds = positions < npg * ps
    blk = jnp.minimum(positions // ps, npg - 1)
    pages = jnp.take_along_axis(page_table, blk, axis=1)   # (B, T)
    pages = jnp.where(in_bounds, pages, 0)                 # overflow → trash
    off = positions % ps
    k_cache = cache["k"].at[pages, :, off].set(k_new.astype(cache["k"].dtype))
    v_cache = cache["v"].at[pages, :, off].set(v_new.astype(cache["v"].dtype))

    Hkv, hd, g = cfg.n_kv_heads, cfg.head_dim, cfg.q_per_kv
    qg = jnp.moveaxis(q.reshape(B, T, Hkv, g, hd), 1, 2)  # (B, Hkv, T, g, hd)
    k_g = paged_gather_ref(k_cache, page_table)           # (B, Hkv, npg*ps, hd)
    v_g = paged_gather_ref(v_cache, page_table)
    valid = (jnp.arange(npg * ps)[None, None, :]
             < jnp.minimum(positions + 1, npg * ps)[:, :, None])
    out = _masked_grouped_attn_multi(qg, k_g, v_g, valid)
    out = jnp.moveaxis(out, 2, 1).reshape(B, T, cfg.n_heads * hd)
    return out @ p["wo"], {"k": k_cache, "v": v_cache}
