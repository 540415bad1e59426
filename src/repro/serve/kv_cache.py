"""KV-cache utilities for the serving path.

Three cache regimes live here:

* ``extend_cache`` — the per-request regime: a prefill-produced cache is
  pad-copied up to prompt+max_new so a single batch can decode. Kept as
  the fallback path (``RoutedServer.generate(engine=False)``).
* the **slot pool** — the uniform continuous-batching regime: one
  persistent cache per (model config, pool shape) with a fixed number of
  sequence *slots* (the batch dim) and a fixed per-slot region length
  ``max_seq``. Requests claim a slot at admission, their prefill K/V is
  written with ``write_slot``, and steady-state decode does zero cache
  reallocation — per-slot validity (``pos + 1``) masks whatever a previous
  occupant left behind. Every slot reserves worst-case room.
* the **page pool** — the vLLM-style regime (serve/engine.py's default):
  one flat pool of fixed-size *pages* shared by every in-flight request.
  A request holds only the pages its actual length needs (its *page
  table* row maps logical blocks → pool pages), so long and short
  requests share the pool with no per-slot worst-case reservation —
  strictly more in-flight requests per byte of KV memory under mixed
  lengths. Page index 0 is the **trash page**: never handed out, the
  scatter target for inactive decode rows and the table filler past a
  request's reservation — gathers from it are masked by validity.

Speculative write-ahead (serve/engine.py draft/verify rounds) rides the
same write-before-validity invariant in BOTH pool regimes: a verify step
writes K/V for positions [pos, pos + k] before any of them is committed,
and a query only ever attends positions below its own causal bound — so
uncommitted drafts are physically present but logically invisible.
**Rollback is pure host bookkeeping**: rejecting a drafted suffix just
resets the slot's ``pos`` to the last accepted position; the stale
drafted K/V above it stays masked until the next occupant of those
positions overwrites it (each decode/verify step writes a position
strictly before validity reaches it). No device-side cache surgery, no
retrace. Overflow discipline differs per regime: the paged verify scatter
redirects positions past a row's claimed pages to the trash page, while
the uniform verify scatter drops out-of-bounds positions — either way a
speculative window poking past the region can never corrupt live
entries. ``alloc_draft_pool`` sizes the drafter's slot pool with the
write-ahead headroom so the draft model's own sequential decode never
clamps at the region end.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def extend_cache(cache, new_len: int):
    """Pad the seq dim of attention caches (leaf names k/v, dim 3 of the
    stacked (L,B,Hkv,S,hd) head-major layout) up to new_len, rounded up to
    a multiple of 8 positions (the TPU sublane, so the Pallas decode kernel
    can tile any such cache) — used to continue decoding from a
    prefill-produced cache. Positions past the validity bound are masked,
    so the rounding never changes a token."""
    new_len = -(-new_len // 8) * 8

    def leaf(path, a):
        names = [p.key for p in path if hasattr(p, "key")]
        if names[-1] in ("k", "v"):
            pad = new_len - a.shape[3]
            if pad > 0:
                a = jnp.pad(a, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
        return a
    return jax.tree_util.tree_map_with_path(leaf, cache)


def alloc_slot_pool(cfg, slots: int, max_seq: int):
    """Allocate the persistent slot-pool cache for one model: the stacked
    decode cache with ``slots`` sequence rows and ``max_seq`` positions per
    slot. Zero-filled; slot contents only become attention-valid once a
    request writes them (validity is per-slot ``pos + 1``)."""
    from repro.models import model as mdl
    return mdl.init_decode_cache(cfg, slots, max_seq)


def write_slot(pool, prefill_cache, slot):
    """Copy a single-sequence prefill cache (leaves (L, 1, ...)) into row
    ``slot`` of the pool (leaves (L, slots, ...)). ``slot`` may be traced —
    one compiled program serves every slot index. Attention leaves land at
    positions [0, S_prefill) of the slot's region; anything beyond stays
    whatever the previous occupant wrote, masked off by per-slot validity.
    """
    slot = jnp.asarray(slot, jnp.int32)

    def leaf(p, u):
        return jax.lax.dynamic_update_slice(
            p, u.astype(p.dtype), (0, slot) + (0,) * (u.ndim - 2))

    return jax.tree.map(leaf, pool, prefill_cache)


def alloc_draft_pool(cfg, slots: int, max_seq: int, spec_k: int):
    """Allocate the drafter's slot pool for a speculative lane: a uniform
    pool (drafts are cheap models — page elasticity buys nothing there)
    with ``spec_k`` positions of write-ahead headroom past the target
    lane's region. The headroom matters: the draft model decodes
    sequentially through the speculative window, and its last draft for a
    request ending flush at ``max_seq`` writes at position
    ``max_seq + spec_k - 1``; without the slack a clamped
    ``dynamic_update_slice`` would smear that write over the region's live
    tail and corrupt the draft cache (costing acceptance, not
    correctness — the verify step is the sole authority on tokens)."""
    return alloc_slot_pool(cfg, slots, max_seq + spec_k)


# ---------------------------------------------------------------------------
# Paged pool
# ---------------------------------------------------------------------------


def alloc_page_pool(cfg, pages: int, page_size: int):
    """Allocate the persistent paged cache for one model: leaves
    (n_units, pages + 1, Hkv, page_size, hd) — ``pages`` allocatable pages
    plus the trash page at index 0 (never handed out; absorbs the scatter
    writes of inactive decode rows and backs unassigned page-table
    entries). Zero-filled; page contents only become attention-valid once
    a request's validity frontier (``pos + 1``) covers them."""
    from repro.models import model as mdl
    return mdl.init_paged_cache(cfg, pages + 1, page_size)


class PageTable:
    """Host-side page bookkeeping for one engine lane: a free list over
    pool pages [1, pages] (0 is the trash page) and one table row per
    decode slot mapping logical blocks → pool pages. Unassigned entries
    stay 0 — the decode gather reads the trash page there and validity
    masks it. Recycling a slot is O(pages held): its pages return to the
    free list and the row zeroes; no data movement, the next holder's
    write-before-validity discipline masks whatever was left behind."""

    def __init__(self, slots: int, pages: int, page_size: int, max_seq: int):
        self.page_size = page_size
        self.pages = pages
        self.max_pages = -(-max_seq // page_size)    # table width (static)
        self.table = np.zeros((slots, self.max_pages), np.int32)
        self.free: List[int] = list(range(pages, 0, -1))   # pop() → page 1
        self._held: Dict[int, List[int]] = {}              # slot → pages

    def pages_needed(self, region_len: int) -> int:
        return -(-region_len // self.page_size)

    @property
    def available(self) -> int:
        return len(self.free)

    def held(self, slot: int) -> int:
        """Pages currently held by ``slot`` (0 if none)."""
        return len(self._held.get(slot, ()))

    def alloc(self, slot: int, n: int) -> np.ndarray:
        """Claim n pages for ``slot``; returns their pool indices in
        logical-block order. Raises if the pool is exhausted (callers gate
        admission on ``available``)."""
        if n > len(self.free):
            raise RuntimeError(f"page pool exhausted: need {n}, "
                               f"have {len(self.free)}")
        if slot in self._held:
            raise RuntimeError(f"slot {slot} already holds pages")
        got = [self.free.pop() for _ in range(n)]
        self.table[slot, :n] = got
        self.table[slot, n:] = 0
        self._held[slot] = got
        return np.asarray(got, np.int32)

    def grow(self, slot: int, n: int) -> np.ndarray:
        """On-demand growth: append ``n`` more pages to a slot that already
        holds some (initial-reservation admission — the decode loop grows a
        request's table right before its writes cross a page boundary).
        Raises on exhaustion (callers preempt a victim first), on a slot
        holding nothing (growth is not admission), and past the static
        table width."""
        if slot not in self._held:
            raise RuntimeError(f"slot {slot} holds no pages — grow() "
                               "extends an existing reservation; use "
                               "alloc() to admit")
        held = self._held[slot]
        if len(held) + n > self.max_pages:
            raise RuntimeError(
                f"slot {slot} cannot grow to {len(held) + n} pages: the "
                f"table row is {self.max_pages} wide (max_seq-bound)")
        if n > len(self.free):
            raise RuntimeError(f"page pool exhausted: grow needs {n}, "
                               f"have {len(self.free)}")
        got = [self.free.pop() for _ in range(n)]
        self.table[slot, len(held):len(held) + n] = got
        held.extend(got)
        return np.asarray(got, np.int32)

    def release(self, slot: int) -> bool:
        """Return a slot's pages to the free list and zero its row.

        Deterministic under the cancellation/expiry/preemption paths that
        may race completion: releasing a slot that holds nothing (double
        release included) is a NO-OP returning False — pages are never
        re-added to the free list, so it cannot be corrupted. A slot index
        outside the table raises IndexError (that is a caller bug, not a
        race). Pinned by tests/test_engine_resilience.py."""
        if not 0 <= int(slot) < self.table.shape[0]:
            raise IndexError(
                f"slot {slot} outside the page table "
                f"(slots={self.table.shape[0]})")
        pages = self._held.pop(slot, None)
        if pages is None:
            return False
        self.free.extend(pages)
        self.table[slot] = 0
        return True


def write_prefill_pages(pool, prefill_cache, pages_mat):
    """Scatter a batched prefill cache (leaves (L, B, Hkv, S_b, hd)) into
    the page pool (leaves (L, P, Hkv, ps, hd)): row b's logical positions
    [i*ps, (i+1)*ps) land in pool page ``pages_mat[b, i]``. ``pages_mat``
    is (B, n_pp) with n_pp = ceil(S_b / ps); pad rows of a coalesced batch
    point every entry at the trash page (0). S_b not a multiple of ps is
    zero-padded up — the tail stays masked until decode overwrites it
    (write-before-validity, same invariant as the slot pool)."""
    pages_mat = jnp.asarray(pages_mat, jnp.int32)
    n_pp = pages_mat.shape[1]

    def leaf(p, u):
        L, B, Hkv, S_b, hd = u.shape
        ps = p.shape[3]
        if S_b < n_pp * ps:
            u = jnp.pad(u, ((0, 0), (0, 0), (0, 0),
                            (0, n_pp * ps - S_b), (0, 0)))
        u = u.reshape(L, B, Hkv, n_pp, ps, hd)
        u = jnp.moveaxis(u, 3, 2)                # (L, B, n_pp, Hkv, ps, hd)
        return p.at[:, pages_mat].set(u.astype(p.dtype))

    return jax.tree.map(leaf, pool, prefill_cache)
