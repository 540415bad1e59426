"""Continuous-batching serving engine with a persistent paged KV pool.

The deployment shape the paper targets (§3) is a router in front of a
model pool serving *many clients concurrently*. The per-request gateway
path serves one caller's batch at a time and pad-copies a fresh KV cache
per request; this engine instead keeps, per routed model, one persistent
cache pool and decodes every in-flight request together:

  admission  — ``submit()`` queues a request; when capacity frees up it is
               prefilled in its pow2 length bucket and its K/V written
               into the pool (buffers donated — no copy). Same-bucket
               admissions **coalesce** into one (B_b, S_b) prefill
               dispatch (per-row ``last_pos``) instead of B separate
               (1, S_b) calls — one trace per (B_b, S_b), and bursty
               arrivals pay one dispatch instead of a convoy.
  decode     — ``step()`` runs ONE cached jitted ``lax.scan`` chunk of
               ``chunk`` greedy tokens over the whole decode batch. Each
               row carries its own position (a per-row ``pos`` vector),
               so requests at different depths share the batch; per-row
               validity (``pos + 1``) masks anything an earlier occupant
               left behind. New requests join between chunks instead of
               waiting for the batch to drain.
  completion — a request that has emitted ``max_new`` tokens frees its
               capacity at the next chunk boundary — steady-state decode
               never reallocates.

KV memory comes in two regimes (``EngineConfig.page_size``):

* **paged** (default, vLLM-style — see ``kv_cache.alloc_page_pool``): one
  flat pool of fixed-size pages shared by every request. A request
  reserves only the pages its own prompt + decode budget needs (its page
  table row maps logical blocks → pool pages; decode gathers by page
  table — ``models.decode_step_paged``, Pallas scalar-prefetch kernel on
  TPU, jnp gather on CPU). Long and short requests share the pool with no
  per-slot worst-case reservation: strictly more in-flight requests per
  byte of KV pool under long-tail length mixes.
* **uniform** (``page_size=None`` — the PR 3 engine, kept as baseline and
  for benchmarks): every slot reserves a full ``max_seq`` region.

Every jitted function is built once per (model config, static shape) and
cached at module level; warm traffic compiles nothing (appends to
``TRACE_LOG`` are per jit *trace*, and tests pin them flat — including
paged decode across mixed per-request page counts, whose shapes are
static ``(slots, max_pages)``).

Greedy decode is prefix-stable, so a request's tokens are bit-identical
to the single-request scan path (``RoutedServer.generate(engine=False)``
on that prompt alone) — test-enforced in tests/test_engine.py and
property-tested over random schedules in tests/test_engine_properties.py.
The parity guarantee is verified on the jnp paths (CPU/interpret); the
TPU Pallas decode kernels now share the jnp path's dtype discipline
(cache-dtype dots, f32 accumulation — kernels/decode_attention.py), and
token equality across the dispatch boundary is pinned in
tests/test_kernels.py on both f32 and bf16 caches; confirming on real
hardware remains a ROADMAP item (online-softmax normalization order still
differs from the one-shot softmax, values agree to tolerance).

**Speculative decode** (``EngineConfig.spec_k > 0``): each round a cheap
drafter — per request, router-chosen through the gateway or pinned via
``submit(draft=)`` / ``EngineConfig.draft`` — decodes ``spec_k`` tokens
ahead in its own slot pool, the target verifies the window in ONE
multi-position dispatch, and the longest matching prefix commits (plus
the verify's correction token on a mismatch). Rollback is free: ``pos``
simply doesn't advance past the accepted point, and write-before-validity
masks the stale suffix. Emitted tokens stay bit-identical to the
non-speculative engine (greedy verify at every position — test-pinned),
and acceptance variation is data, never shape: zero decode retraces
(``_draft_fn``/``_verify_fn``/``_verify_paged_fn`` cache like every other
engine jit). Counters: ``spec_rounds`` / ``spec_drafted`` /
``spec_accepted`` / ``spec_rejected``.

SSM/hybrid archs integrate state over every prefill position and cannot
share right-padded prompt buckets; they stay on the gateway's per-request
path (``RoutedServer.generate`` falls back automatically).

Overload resilience (PR 8): requests carry an optional **deadline**
(engine steps) and can be **cancelled**; both release their slot and
pages immediately between chunks — pure host bookkeeping, the decode
program never retraces. Paged lanes with ``reserve="initial"`` claim only
the prefill bucket's pages at admission and **grow on demand** each chunk;
under page pressure the engine **preempts** the lowest-priority victim
(latest deadline first, then fewest tokens generated), releases its pages,
and re-queues it as a prefill of prompt + tokens-so-far — greedy decode is
prefix-stable, so the resumed request's tokens are bit-identical to its
never-preempted twin (test-pinned). A bounded admission queue
(``queue_cap`` / per-model ``lane_quotas``) **sheds** excess load instead
of queuing without bound. Every request ends in exactly one typed terminal
status — ``DONE`` / ``PREEMPTED-resumed`` / ``EXPIRED`` / ``CANCELLED`` /
``SHED`` — surfaced through ``step()``/``drain()``/``status()``, and the
counters (``sheds``, ``preemptions``, ``expiries``, ``cancels``,
``resume_recompute_toks``, ``queue_depth_hw``) are exact accounting for
the chaos bench (``benchmarks/perf_suite.bench_preempt``).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import time
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import repro.sharding as shd
from repro.config import ModelConfig
from repro.models import model as mdl
from repro.serve.kv_cache import (PageTable, alloc_draft_pool,
                                  alloc_page_pool, alloc_slot_pool,
                                  write_prefill_pages, write_slot)

#: one entry appended per jit TRACE of an engine/serve function (including
#: the gateway's route program — hot-swapped router state must enter it as
#: a traced argument, never a retrace) — bounded so a long-running server
#: can't leak memory; tests assert its length stays flat after warmup and
#: across router hot-swaps. gateway.py re-exports this same object.
TRACE_LOG: Deque[tuple] = collections.deque(maxlen=4096)


def reset_trace_log() -> None:
    """Explicitly clear the retrace log (long-running servers)."""
    TRACE_LOG.clear()


def next_pow2(v: int) -> int:
    return 1 << (max(v, 1) - 1).bit_length()


def region_len(n_tokens: int, max_new: int, chunk: int) -> int:
    """Positions a request writes over its lifetime: the pow2 prefill
    bucket or prompt + whole decode chunks, whichever is larger. Module
    level so tests/benchmarks size page pools with the engine's own math
    instead of re-deriving it."""
    steps = -(-max_new // chunk) * chunk
    return max(next_pow2(n_tokens), n_tokens + steps)


#: typed terminal statuses. A completed request (DONE, or PREEMPTED-resumed
#: when it survived >= 1 preemption) surfaces its np token array directly —
#: result-consuming callers written against the PR 3 engine never change.
#: The non-completion terminals (EXPIRED / CANCELLED / SHED) surface an
#: ``Outcome`` record carrying any partial tokens.
DONE = "DONE"
PREEMPTED_RESUMED = "PREEMPTED-resumed"
EXPIRED = "EXPIRED"
CANCELLED = "CANCELLED"
SHED = "SHED"
TERMINAL_STATUSES = (DONE, PREEMPTED_RESUMED, EXPIRED, CANCELLED, SHED)

_INF = float("inf")


@dataclasses.dataclass(frozen=True)
class Outcome:
    """Terminal record for a request that did NOT complete: ``status`` is
    EXPIRED / CANCELLED / SHED and ``tokens`` holds whatever it emitted
    before termination (None if nothing was). Surfaced as the request's
    result through ``step()``/``drain()`` in place of the token array."""
    rid: int
    status: str
    tokens: Optional[np.ndarray] = None


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static engine shape — one compiled program set per value of this."""
    slots: int = 8     #: concurrent sequences per model (decode batch rows)
    max_seq: int = 256  #: max per-request region: prompt bucket + decode room
    chunk: int = 8     #: decode tokens per jitted chunk (admission period)
    done_buffer: int = 1024  #: finished results kept for drain(); oldest
    #: evicted beyond this, so step()-consuming servers don't leak
    page_size: Optional[int] = 16  #: paged KV pool page length (positions);
    #: None selects the uniform slot pool (every slot reserves max_seq)
    pages: int = 0  #: allocatable pages in the pool; 0 → auto
    #: (slots * ceil(max_seq / page_size) — worst-case-equivalent, so
    #: admission is never page-bound; set lower to trade reservation
    #: headroom for strictly more in-flight requests per byte)
    reserve: str = "lifetime"  #: paged reservation policy. "lifetime"
    #: claims every page a request can ever write at admission (the PR 4
    #: engine — admission stalls on pool exhaustion, never preempts).
    #: "initial" claims only the prefill bucket's pages and grows on
    #: demand at chunk boundaries; under page pressure the engine preempts
    #: the lowest-priority victim (latest deadline first, then fewest
    #: tokens generated) and re-queues it as a prefill of
    #: prompt + tokens-so-far (recompute-on-resume, bit-identical tokens)
    queue_cap: Optional[int] = None  #: bounded admission queue per lane;
    #: a submit past the cap SHEDs per ``shed_policy`` instead of queuing
    #: without bound. None = unbounded (seed behavior)
    shed_policy: str = "reject-newest"  #: which request a full lane queue
    #: sheds: "reject-newest" (the incoming one) or "reject-latest-deadline"
    #: (the queued request best able to afford it — the incoming one only
    #: if its own effective deadline is latest)
    lane_quotas: Tuple[Tuple[int, int], ...] = ()  #: per-model queue-cap
    #: overrides as (model_idx, cap) pairs, so one overloaded pool model
    #: sheds its own excess instead of starving the other lanes
    spec_k: int = 0  #: speculative decode: tokens drafted ahead per round.
    #: 0 disables (seed behavior — ``step()`` decodes ``chunk``-token
    #: scans). > 0 replaces each lane's decode chunk with a draft/verify
    #: ROUND: the request's drafter decodes ``spec_k`` tokens ahead
    #: (a cheap sequential scan on the draft model), the target verifies
    #: all ``spec_k + 1`` positions in ONE batched dispatch, the greedy-
    #: matching prefix commits (plus the verify's own next token), and the
    #: rejected suffix rolls back by resetting the slot's ``pos`` — tokens
    #: stay bit-identical to the non-speculative engine (greedy verify),
    #: between 1 and spec_k + 1 of them per row per round
    draft: Optional[int] = None  #: default drafter (model pool index) for
    #: requests that don't pass ``submit(..., draft=)``. None → each
    #: request drafts with its own target model (degenerate k-step
    #: lookahead, full acceptance). The gateway overrides per request from
    #: the router's utility ranking (cheapest model the router still
    #: rates — see RoutedServer)

    @property
    def resolved_pages(self) -> int:
        """Allocatable pages (excluding the trash page)."""
        if not self.page_size:
            return 0
        return self.pages or self.slots * (-(-self.max_seq // self.page_size))


# ---------------------------------------------------------------------------
# Cached jitted stages (module level — never rebuilt per request)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _prefill_fn(cfg: ModelConfig):
    """Prefill one prompt bucket → (first greedy token (B,), KV cache).
    Identical math to the gateway scan path's prefill segment (same
    q_chunk, same last_pos unembed), so engine tokens stay bit-identical
    to the single-request path. ``last_pos`` may be a scalar (uniform
    lanes admit one request at a time) or a (B,) vector (coalesced paged
    admission: same-bucket requests of different true lengths batched into
    one dispatch, each row unembedded at its own last position)."""
    def prefill(params, toks, last_pos):
        TRACE_LOG.append(("engine_prefill", cfg.name, toks.shape))
        logits, _, cache = mdl.forward(params, cfg, tokens=toks,
                                       logits_last_only=True,
                                       last_pos=last_pos,
                                       return_cache=True, q_chunk=64)
        tok0 = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return tok0, cache
    return jax.jit(prefill)


@functools.lru_cache(maxsize=None)
def _admit_fn(cfg: ModelConfig):
    """Write a prefill cache into one pool slot. The pool argument is
    donated: admission mutates the persistent buffers in place instead of
    copying the whole pool per request."""
    def admit(pool, prefill_cache, slot):
        TRACE_LOG.append(("engine_admit", cfg.name,
                          jax.tree.leaves(prefill_cache)[0].shape))
        return write_slot(pool, prefill_cache, slot)
    return jax.jit(admit, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _write_pages_fn(cfg: ModelConfig):
    """Scatter a coalesced prefill cache into the paged pool. The pool
    argument is donated: admission mutates the persistent page buffers in
    place instead of copying the pool per batch. One trace per
    (B_b, S_b, n_pp) admission shape."""
    def write(pool, prefill_cache, pages_mat):
        TRACE_LOG.append(("engine_write_pages", cfg.name,
                          jax.tree.leaves(prefill_cache)[0].shape,
                          pages_mat.shape))
        return write_prefill_pages(pool, prefill_cache, pages_mat)
    return jax.jit(write, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _chunk_paged_fn(cfg: ModelConfig, chunk: int):
    """One decode chunk over the paged decode batch: ``chunk`` greedy
    tokens via ``lax.scan`` with per-row positions and the (slots,
    max_pages) page table. The table's shape is static, so mixed
    per-request page counts never retrace; the pool is donated —
    steady-state decode reuses the page buffers."""
    def run(params, cache, page_table, tok, pos):
        TRACE_LOG.append(("engine_chunk_paged", cfg.name, tok.shape,
                          page_table.shape, chunk))

        def body(carry, _):
            tok, pos, cache = carry
            logits, cache = mdl.decode_step_paged(
                params, cache, cfg, tokens=tok[:, None],
                page_table=page_table, pos=pos)
            nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            return (nxt, pos + 1, cache), tok

        (tok, pos, cache), out = jax.lax.scan(body, (tok, pos, cache), None,
                                              length=chunk)
        return cache, tok, pos, out.T                     # out: (B, chunk)
    return jax.jit(run, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _chunk_fn(cfg: ModelConfig, chunk: int):
    """One decode chunk over the whole slot batch: ``chunk`` greedy tokens
    via ``lax.scan`` with a per-slot position vector. Emits the token fed
    at each step (same emission order as the gateway scan), the slot
    cache (donated — steady-state decode reuses the pool buffers), and the
    advanced (tok, pos) carry."""
    def run(params, cache, tok, pos):
        TRACE_LOG.append(("engine_chunk", cfg.name, tok.shape, chunk))

        def body(carry, _):
            tok, pos, cache = carry
            logits, cache = mdl.decode_step(params, cache, cfg,
                                            tokens=tok[:, None], pos=pos)
            nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            return (nxt, pos + 1, cache), tok

        (tok, pos, cache), out = jax.lax.scan(body, (tok, pos, cache), None,
                                              length=chunk)
        return cache, tok, pos, out.T                     # out: (B, chunk)
    return jax.jit(run, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _draft_fn(cfg: ModelConfig, k: int):
    """Draft ``k`` tokens ahead on the draft model's slot pool: a cheap
    sequential greedy scan (same body as ``_chunk_fn``) that RETURNS the
    generated tokens instead of the fed ones — the drafted window the
    target's verify step will judge. One trace per (draft config, k);
    the draft pool is donated like every steady-state cache."""
    def run(params, cache, tok, pos):
        TRACE_LOG.append(("engine_draft", cfg.name, tok.shape, k))

        def body(carry, _):
            tok, pos, cache = carry
            logits, cache = mdl.decode_step(params, cache, cfg,
                                            tokens=tok[:, None], pos=pos)
            nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            return (nxt, pos + 1, cache), nxt

        (tok, pos, cache), drafted = jax.lax.scan(body, (tok, pos, cache),
                                                  None, length=k)
        return cache, drafted.T                           # (B, k)
    return jax.jit(run, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _verify_fn(cfg: ModelConfig, T: int):
    """Verify ``T = spec_k + 1`` positions per row in ONE dispatch on the
    uniform slot pool (``mdl.decode_verify``): returns the greedy token at
    every position — position j's argmax is exactly what the sequential
    chain would emit after the first j drafted tokens, so the host-side
    accept loop just compares it against the draft. One trace per
    (model config, T); the pool is donated."""
    def run(params, cache, tok, pos):
        TRACE_LOG.append(("engine_verify", cfg.name, tok.shape))
        logits, cache = mdl.decode_verify(params, cache, cfg,
                                          tokens=tok, pos=pos)
        return cache, jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.jit(run, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _verify_paged_fn(cfg: ModelConfig, T: int):
    """Paged twin of ``_verify_fn`` (``mdl.decode_verify_paged``): the
    (slots, max_pages) table shape is static, so mixed per-request page
    counts never retrace — same guarantee as ``_chunk_paged_fn``."""
    def run(params, cache, page_table, tok, pos):
        TRACE_LOG.append(("engine_verify_paged", cfg.name, tok.shape,
                          page_table.shape))
        logits, cache = mdl.decode_verify_paged(params, cache, cfg,
                                                tokens=tok,
                                                page_table=page_table,
                                                pos=pos)
        return cache, jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.jit(run, donate_argnums=(1,))


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


def _empty_toks() -> np.ndarray:
    return np.zeros((0,), np.int32)


@dataclasses.dataclass
class _Active:
    rid: int
    max_new: int               # TOTAL decode budget (prefix included)
    toks: np.ndarray = dataclasses.field(default_factory=_empty_toks)
    #: original prompt — kept so preemption can re-queue the request
    deadline: Optional[int] = None   # absolute engine-step bound
    t_submit: float = 0.0
    prefix: np.ndarray = dataclasses.field(default_factory=_empty_toks)
    #: tokens emitted before the last preemption (this tenure re-prefilled
    #: prompt + prefix; ``chunks`` holds only the current tenure)
    chunks: List[np.ndarray] = dataclasses.field(default_factory=list)
    #: only COMMITTED tokens ever enter ``chunks`` — a speculative round
    #: appends its accepted prefix after verification, never raw drafts —
    #: so ``_partial_tokens`` stays an exact solo prefix under
    #: cancel/expire/preempt even mid-draft-window
    emitted: int = 0           # total emitted, prefix included
    preempts: int = 0
    draft: int = -1            # drafter pool index (spec mode; -1 = unset)
    region: int = 0            # commit-bound write extent: len(prompt) +
    #: max_new — speculative page growth is clamped here (write-ahead past
    #: it scatters to the trash page and must not claim pages)


@dataclasses.dataclass
class _Pending:
    rid: int
    toks: np.ndarray           # (S,) int32 prompt tokens, unpadded
    max_new: int
    t_submit: float = 0.0      # perf_counter at submit (admission latency)
    deadline: Optional[int] = None   # absolute engine-step bound
    prefix: np.ndarray = dataclasses.field(default_factory=_empty_toks)
    #: tokens already emitted before a preemption — admission prefills
    #: prompt + prefix (recompute-on-resume)
    preempts: int = 0
    draft: int = -1            # drafter pool index (spec mode; -1 = unset)

    def eff_deadline(self) -> float:
        return _INF if self.deadline is None else float(self.deadline)


class _Lane:
    """Per-model engine state: the KV pool (paged or uniform) + host-side
    slot/page bookkeeping."""

    def __init__(self, pm, ecfg: EngineConfig, mesh=None):
        self.pm = pm
        self.ecfg = ecfg
        self.mesh = mesh
        self.paged = bool(ecfg.page_size)
        if self.paged:
            self.pool = alloc_page_pool(pm.cfg, ecfg.resolved_pages,
                                        ecfg.page_size)
            self.pt = PageTable(ecfg.slots, ecfg.resolved_pages,
                                ecfg.page_size, ecfg.max_seq)
        else:
            self.pool = alloc_slot_pool(pm.cfg, ecfg.slots, ecfg.max_seq)
            self.pt = None
        #: the params handle the decode stages feed the jitted programs —
        #: replicated over the mesh when one is live (so every pool-sharded
        #: dispatch is one mesh program), the model's own buffers otherwise
        self.params = pm.params
        if mesh is not None:
            self.pool = shd.shard_kv_pool(self.pool, mesh, paged=self.paged)
            self.params = shd.replicate(pm.params, mesh)
        self.free: List[int] = list(range(ecfg.slots))[::-1]
        self.active: Dict[int, _Active] = {}             # slot -> request
        self.queue: Deque[_Pending] = collections.deque()
        self.tok = np.zeros((ecfg.slots,), np.int32)     # next token to feed
        self.pos = np.zeros((ecfg.slots,), np.int32)     # its write position
        #: speculative mode: drafter pool index → the drafter's own slot
        #: pool (uniform, with spec_k write-ahead headroom — see
        #: kv_cache.alloc_draft_pool), allocated lazily on first use and
        #: kept for the lane's lifetime. Row s mirrors slot s; rows whose
        #: request drafts with a different model hold garbage until the
        #: draft prefill of their next matching occupant overwrites them
        #: (write-before-validity, same invariant as the target pool).
        self.draft_pools: Dict[int, object] = {}
        #: drafter pool index → its params handle (replicated on a mesh),
        #: filled alongside draft_pools
        self.draft_params: Dict[int, object] = {}


class ServeEngine:
    """Admission queue + slot pools over a model pool (attention archs).

    ``submit`` enqueues, ``step`` admits + decodes one chunk per lane,
    ``drain`` steps until idle and returns {request id: np tokens}.
    """

    def __init__(self, pool: List, ecfg: Optional[EngineConfig] = None, *,
                 mesh=None):
        self.ecfg = ecfg or EngineConfig()
        #: cross-silo mesh execution (repro.sharding): with a live Mesh the
        #: per-lane KV pools are placed via ``shard_kv_pool`` (slot dim over
        #: "data" — slot-parallel decode, bit-identical tokens; Hkv dim over
        #: "heads" — tensor-parallel attention), params replicate, and every
        #: jitted stage traces under ``ENGINE_RULES`` so the attention
        #: code's logical-axis annotations bind to mesh axes. Host-side
        #: bookkeeping (slots, page tables, queues) is untouched, so the
        #: zero-retrace guarantees carry over verbatim.
        self.mesh = mesh
        if mesh is not None and not any(a in mesh.shape
                                        for a in ("data", "heads")):
            raise ValueError(
                f"ServeEngine mesh carries axes {tuple(mesh.shape)} — the "
                "engine shards over \"data\" (slot-parallel) and/or "
                "\"heads\" (tensor-parallel); build one with "
                "sharding.data_mesh()/head_mesh()/make_mesh()")
        if self.ecfg.reserve not in ("lifetime", "initial"):
            raise ValueError(f"EngineConfig.reserve={self.ecfg.reserve!r}: "
                             "expected 'lifetime' or 'initial'")
        if self.ecfg.reserve == "initial" and not self.ecfg.page_size:
            raise ValueError("reserve='initial' is a paged-pool feature — "
                             "uniform slot lanes reserve max_seq per slot "
                             "by construction (set page_size)")
        if self.ecfg.shed_policy not in ("reject-newest",
                                         "reject-latest-deadline"):
            raise ValueError(
                f"EngineConfig.shed_policy={self.ecfg.shed_policy!r}: "
                "expected 'reject-newest' or 'reject-latest-deadline'")
        if self.ecfg.spec_k < 0:
            raise ValueError(f"EngineConfig.spec_k={self.ecfg.spec_k}: "
                             "the drafted window cannot be negative")
        if self.ecfg.draft is not None:
            if self.ecfg.spec_k == 0:
                raise ValueError("EngineConfig.draft without spec_k > 0: "
                                 "a drafter only exists in speculative mode")
            if not 0 <= int(self.ecfg.draft) < len(pool):
                raise ValueError(
                    f"EngineConfig.draft={self.ecfg.draft}: not a model "
                    f"pool index (pool has {len(pool)} models)")
        self.pool = pool
        self._lanes: Dict[int, _Lane] = {}
        self._next_rid = 0
        self._done: Dict[int, np.ndarray] = {}
        self._lane_caps = dict(self.ecfg.lane_quotas)
        self._steps = 0              #: step() calls so far — the deadline
        #: clock (submit(deadline=d) expires after d further steps)
        self._status: Dict[int, str] = {}   # rid → terminal status, bounded
        #: terminal records produced since the last step()/drain() flush —
        #: cancel()/shed/expiry land here so their typed results surface
        #: through the same channel as completions
        self._events: List[Tuple[int, object]] = []
        #: resilience counters — exact accounting, threaded into FedLoop
        #: sync history and BENCH_preempt.json. Reset by assigning 0.
        self.sheds = 0
        self.preemptions = 0
        self.expiries = 0
        self.cancels = 0
        #: prompt+prefix positions re-prefilled by preemption resumes (the
        #: recompute cost preemption pays for its page elasticity)
        self.resume_recompute_toks = 0
        self.queue_depth_hw = 0      #: queue-depth high-water across lanes
        #: speculative-decode accounting (exact, host-side): rounds run,
        #: tokens drafted (spec_k per active row per round), drafted tokens
        #: accepted by verify, and drafted tokens rejected-and-recomputed
        #: (the rollback cost — each rejected draft burned draft-model work
        #: and a verify position that re-decodes next round). Acceptance
        #: rate = spec_accepted / spec_drafted.
        self.spec_rounds = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_rejected = 0
        #: queue-wait per admitted request (submit → prefill dispatched),
        #: seconds; bounded like TRACE_LOG so long-running servers don't
        #: leak. benchmarks/perf_suite.bench_paged reads the p99.
        self.admission_lat: Deque[float] = collections.deque(maxlen=65536)
        #: high-water mark of concurrently admitted requests, sampled at
        #: every chunk boundary between admission and decode (completions
        #: release capacity before step() returns, so callers can't see
        #: it). Reset by assigning 0; bench_paged's in-flight-per-byte
        #: numerator.
        self.peak_active: int = 0

    def _rules(self):
        """Logical-axis rules context for the jitted stages: on a mesh the
        attention code's ``constrain`` annotations bind to the engine axes
        at trace time (rules naming axes the mesh doesn't carry replicate);
        solo it's a no-op, so the stage programs are unchanged."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return shd.use_rules(self.mesh, shd.ENGINE_RULES)

    def _region_len(self, n_tokens: int, max_new: int) -> int:
        return region_len(n_tokens, max_new, self.ecfg.chunk)

    def _region_cap(self, n_tokens: int, max_new: int) -> int:
        """Worst-case region a request may ever need. Lifetime reservation:
        its own ``region_len``. Initial reservation additionally covers the
        worst RESUME point — a request preempted after k emitted tokens
        re-prefills n_tokens + k in ITS pow2 bucket, and the largest k at
        which a resume can still happen is the last chunk boundary before
        max_new. Admitting only requests whose worst resume bucket fits
        guarantees every preempted request stays resumable and a lone
        request always completes (no preemption livelock)."""
        region = self._region_len(n_tokens, max_new)
        if self.ecfg.page_size and self.ecfg.reserve == "initial":
            chunk = self.ecfg.chunk
            k_max = (-(-max_new // chunk) - 1) * chunk
            region = max(region, next_pow2(n_tokens + k_max))
        return region

    def fits(self, n_tokens: int, max_new: int) -> bool:
        """Whether a request can ever be admitted: its written region must
        stay inside ``max_seq`` (the page-table width on paged lanes, the
        slot region on uniform ones), and on paged lanes its page count
        must not exceed the whole pool. Under ``reserve="initial"`` the
        region also covers the worst resume-point prefill bucket (see
        ``_region_cap``) — slightly stricter, so preempted requests are
        always resumable."""
        region = self._region_cap(n_tokens, max_new)
        if region > self.ecfg.max_seq:
            return False
        if self.ecfg.page_size:
            need = -(-region // self.ecfg.page_size)
            return need <= self.ecfg.resolved_pages
        return True

    def kv_pool_bytes(self) -> int:
        """Bytes held by every lane's persistent KV pool (paged pools
        include the trash page)."""
        return sum(leaf.nbytes for lane in self._lanes.values()
                   for leaf in jax.tree.leaves(lane.pool))

    def n_active(self) -> int:
        """Requests currently holding decode capacity (all lanes)."""
        return sum(len(lane.active) for lane in self._lanes.values())

    def _resolve_draft(self, model_idx: int, draft, pm) -> int:
        """Pick and validate a request's drafter (spec mode): the explicit
        ``submit(draft=)`` override, else ``EngineConfig.draft``, else the
        target itself (degenerate lookahead — always correct, never
        faster). The drafter must share the target's token space and be an
        attention arch (its cache rolls back positionally)."""
        d = int(draft if draft is not None
                else (self.ecfg.draft if self.ecfg.draft is not None
                      else model_idx))
        if not 0 <= d < len(self.pool):
            raise ValueError(f"draft={d}: not a model pool index "
                             f"(pool has {len(self.pool)} models)")
        dcfg = self.pool[d].cfg
        if dcfg.arch_type in ("ssm", "hybrid"):
            raise TypeError(f"{dcfg.name}: SSM/hybrid drafters cannot roll "
                            "back a rejected suffix (state is not "
                            "positional) — pick an attention drafter")
        if dcfg.vocab != pm.cfg.vocab:
            raise ValueError(
                f"drafter {dcfg.name} (vocab {dcfg.vocab}) and target "
                f"{pm.cfg.name} (vocab {pm.cfg.vocab}) don't share a token "
                "space — drafted tokens would be meaningless to verify")
        return d

    # ------------------------------------------------------------- submit
    def submit(self, model_idx: int, toks: np.ndarray, max_new: int, *,
               deadline: Optional[int] = None,
               draft: Optional[int] = None) -> int:
        """Enqueue a request; returns its rid. ``deadline`` bounds its
        lifetime in engine steps: after that many further ``step()`` calls
        an unfinished request EXPIREs (slot and pages released between
        chunks, partial tokens surfaced in its ``Outcome``). None = never.
        A full lane queue (``queue_cap`` / ``lane_quotas``) SHEDs per
        ``shed_policy`` — the shed request's rid still comes back here and
        its typed ``Outcome`` surfaces through the next step()/drain().
        ``draft`` (speculative mode only) picks this request's drafter by
        model pool index, overriding ``EngineConfig.draft``; the gateway
        passes the router's utility-ranked choice here."""
        pm = self.pool[int(model_idx)]
        if pm.cfg.arch_type in ("ssm", "hybrid"):
            raise TypeError(
                f"{pm.cfg.name}: SSM/hybrid archs integrate state over pad "
                "positions and can't share right-padded slot buckets — use "
                "RoutedServer.generate (it falls back per request)")
        toks = np.asarray(toks, np.int32).reshape(-1)
        if not self.fits(len(toks), max_new):
            raise ValueError(
                f"prompt ({len(toks)} tokens, pow2 bucket "
                f"{next_pow2(len(toks))}) + whole decode chunks for "
                f"max_new={max_new} exceed the per-request region "
                f"max_seq={self.ecfg.max_seq}"
                + (f" or the page pool ({self.ecfg.resolved_pages} pages of "
                   f"{self.ecfg.page_size})" if self.ecfg.page_size else "")
                + " — raise EngineConfig.max_seq/pages or shorten the "
                "request (RoutedServer.generate falls back to the per-call "
                "path automatically)")
        if deadline is not None and int(deadline) < 1:
            raise ValueError(f"deadline={deadline}: a request needs at "
                             "least one engine step to make progress")
        if self.ecfg.spec_k > 0:
            draft_idx = self._resolve_draft(int(model_idx), draft, pm)
        elif draft is not None:
            raise ValueError("submit(draft=...) needs EngineConfig.spec_k "
                             "> 0 — the non-speculative engine has no "
                             "drafter")
        else:
            draft_idx = -1
        rid = self._next_rid
        self._next_rid += 1
        lane = self._lanes.get(int(model_idx))
        if lane is None:
            lane = self._lanes[int(model_idx)] = _Lane(pm, self.ecfg,
                                                       self.mesh)
        pend = _Pending(rid, toks, max_new, t_submit=time.perf_counter(),
                        deadline=(self._steps + int(deadline)
                                  if deadline is not None else None),
                        draft=draft_idx)
        cap = self._lane_caps.get(int(model_idx), self.ecfg.queue_cap)
        if cap is not None and len(lane.queue) >= cap:
            victim = pend
            if self.ecfg.shed_policy == "reject-latest-deadline":
                # shed whichever of queue ∪ {incoming} can best afford it:
                # latest effective deadline, newest rid on ties — so the
                # incoming request sheds only when ITS priority is lowest
                qv = max(lane.queue, key=lambda q: (q.eff_deadline(), q.rid))
                if ((qv.eff_deadline(), qv.rid)
                        > (pend.eff_deadline(), pend.rid)):
                    lane.queue.remove(qv)
                    lane.queue.append(pend)
                    victim = qv
            self.sheds += 1
            self._record(victim.rid, SHED,
                         tokens=(victim.prefix.copy()
                                 if len(victim.prefix) else None))
        else:
            lane.queue.append(pend)
        depth = sum(len(l.queue) for l in self._lanes.values())
        self.queue_depth_hw = max(self.queue_depth_hw, depth)
        return rid

    # ---------------------------------------------------------- lifecycle
    def _record(self, rid: int, status: str, tokens=None) -> None:
        """Write a request's single terminal record: its result payload
        (np tokens for completions, a typed Outcome otherwise) lands in the
        step()-return event buffer and the drain() buffer, its status in
        the bounded status map."""
        payload = (tokens if status in (DONE, PREEMPTED_RESUMED)
                   else Outcome(rid, status, tokens))
        self._events.append((rid, payload))
        self._done[rid] = payload
        self._status[rid] = status
        while len(self._status) > 4 * self.ecfg.done_buffer:
            self._status.pop(next(iter(self._status)))

    @staticmethod
    def _partial_tokens(st: _Active) -> Optional[np.ndarray]:
        parts = ([st.prefix] if len(st.prefix) else []) + st.chunks
        if not parts or st.emitted == 0:
            return None
        return np.concatenate(parts)[:st.emitted]

    def _release_slot(self, lane: _Lane, slot: int) -> None:
        """Free a slot's capacity between chunks: slot to the free list,
        pages to the page free list, carry zeroed. Pure host bookkeeping —
        the decode program's shapes don't change, so no retrace."""
        del lane.active[slot]
        lane.free.append(slot)
        if lane.paged:
            lane.pt.release(slot)
        lane.tok[slot] = 0
        lane.pos[slot] = 0

    def cancel(self, rid: int) -> str:
        """Cancel a request wherever it is: queued/preempted requests
        leave the queue; an active one releases its slot and pages at this
        chunk boundary (no decode retrace). Already-terminal rids are a
        no-op returning their existing status; unknown rids raise KeyError.
        The CANCELLED record (with any partial tokens) surfaces through
        the next ``step()``/``drain()``."""
        if rid in self._status:
            return self._status[rid]
        for lane in self._lanes.values():
            for q in lane.queue:
                if q.rid == rid:
                    lane.queue.remove(q)
                    self.cancels += 1
                    self._record(rid, CANCELLED,
                                 tokens=(q.prefix.copy()
                                         if len(q.prefix) else None))
                    return CANCELLED
            for slot, st in list(lane.active.items()):
                if st.rid == rid:
                    toks = self._partial_tokens(st)
                    self._release_slot(lane, slot)
                    self.cancels += 1
                    self._record(rid, CANCELLED, tokens=toks)
                    return CANCELLED
        raise KeyError(f"unknown request id {rid}")

    def status(self, rid: int) -> str:
        """Typed lifecycle status: one of the terminal statuses once the
        request ended, else "ACTIVE" (holding a slot), "PREEMPTED"
        (evicted, queued for recompute-resume) or "QUEUED". KeyError for a
        rid the engine never saw (or whose terminal record aged out of the
        bounded status buffer)."""
        if rid in self._status:
            return self._status[rid]
        for lane in self._lanes.values():
            for st in lane.active.values():
                if st.rid == rid:
                    return "ACTIVE"
            for q in lane.queue:
                if q.rid == rid:
                    return "PREEMPTED" if q.preempts else "QUEUED"
        raise KeyError(f"unknown request id {rid} (never submitted, or its "
                       "terminal record aged out of the status buffer)")

    def counters(self) -> Dict[str, int]:
        """Snapshot of the resilience counters (threaded into FedLoop sync
        history and the chaos bench)."""
        return {"sheds": self.sheds, "preemptions": self.preemptions,
                "expiries": self.expiries, "cancels": self.cancels,
                "resume_recompute_toks": self.resume_recompute_toks,
                "queue_depth_hw": self.queue_depth_hw,
                "peak_active": self.peak_active,
                "spec_rounds": self.spec_rounds,
                "spec_drafted": self.spec_drafted,
                "spec_accepted": self.spec_accepted,
                "spec_rejected": self.spec_rejected}

    def _expire(self, lane: _Lane) -> None:
        """EXPIRE every request (active or queued) whose deadline has
        passed — slot and pages release immediately, partial tokens ride
        in the Outcome."""
        now = self._steps
        for slot, st in sorted(lane.active.items()):
            if st.deadline is not None and now >= st.deadline:
                toks = self._partial_tokens(st)
                self._release_slot(lane, slot)
                self.expiries += 1
                self._record(st.rid, EXPIRED, tokens=toks)
        if any(q.deadline is not None and now >= q.deadline
               for q in lane.queue):
            keep: Deque[_Pending] = collections.deque()
            for q in lane.queue:
                if q.deadline is not None and now >= q.deadline:
                    self.expiries += 1
                    self._record(q.rid, EXPIRED,
                                 tokens=(q.prefix.copy()
                                         if len(q.prefix) else None))
                else:
                    keep.append(q)
            lane.queue = keep

    # --------------------------------------------------------------- step
    def step(self) -> List[Tuple[int, object]]:
        """Expire, admit (preempting under page pressure in "initial"
        mode), grow page reservations, then decode one chunk on every busy
        lane. Returns every request that reached a TERMINAL state this
        step as (rid, result): completions (DONE / PREEMPTED-resumed)
        carry their np token array, EXPIRED/CANCELLED/SHED carry a typed
        ``Outcome``. Results are also buffered for ``drain()`` — up to
        ``EngineConfig.done_buffer`` of them, oldest evicted first, so a
        server that consumes step()'s return value and never drains can
        run forever without growing memory."""
        for lane in self._lanes.values():
            self._expire(lane)
        for lane in self._lanes.values():
            self._admit(lane)
        self.peak_active = max(self.peak_active, self.n_active())
        for lane in self._lanes.values():
            if lane.active and lane.paged and self.ecfg.reserve == "initial":
                self._grow_for_chunk(lane)
            if lane.active:
                if self.ecfg.spec_k:
                    self._decode_spec_round(lane)
                else:
                    self._decode_chunk(lane)
        self._steps += 1
        finished = self._events
        self._events = []
        while len(self._done) > self.ecfg.done_buffer:
            self._done.pop(next(iter(self._done)))
        return finished

    @property
    def busy(self) -> bool:
        return any(l.queue or l.active for l in self._lanes.values())

    def drain(self, rids=None) -> Dict[int, object]:
        """Step until completion and return {rid: result} — np tokens for
        completed requests, a typed ``Outcome`` for expired / cancelled /
        shed ones. With rids=None, runs until every lane is idle and
        returns (and clears) everything; with an iterable of request ids,
        runs until exactly those reach a terminal state and leaves other
        results in place (so interleaved ``submit`` streams keep their
        results). A wanted rid that already terminated — cancelled,
        expired, shed — returns its typed record instead of hanging or
        KeyError-ing; only a rid the engine has no record of raises
        KeyError."""
        if rids is None:
            # capture from step() returns as requests finish — like the
            # rids branch below, immune to done-buffer eviction when more
            # than done_buffer requests are in flight
            out = dict(self._done)
            while self.busy:
                out.update(self.step())
            out.update(self._done)
            self._done = {}
            self._events = []
            return out
        want = set(rids)
        # collect straight from step() results (not only the _done buffer,
        # whose oldest entries step() may evict) — a wanted rid is captured
        # the moment it finishes, so any batch size is safe
        out = {r: self._done.pop(r) for r in want if r in self._done}
        # a terminal rid whose payload was evicted from the done buffer
        # still resolves through the status map (tokens lost to eviction)
        for r in want - out.keys():
            if r in self._status and self._status[r] not in (
                    DONE, PREEMPTED_RESUMED):
                out[r] = Outcome(r, self._status[r])
        self._events = [(r, p) for r, p in self._events if r not in out]
        while want - out.keys():
            if not self.busy:
                raise KeyError(f"unknown request ids: "
                               f"{sorted(want - out.keys())}")
            for rid, payload in self.step():
                if rid in want:
                    out[rid] = payload
                    self._done.pop(rid, None)
        return out

    # ------------------------------------------------------------ internals
    @staticmethod
    def _full_prompt(req: _Pending) -> np.ndarray:
        """The token sequence admission actually prefills: the original
        prompt, plus — after a preemption — every token the request had
        already emitted (recompute-on-resume; greedy decode's prefix
        stability makes the continuation bit-identical)."""
        if len(req.prefix):
            return np.concatenate([req.toks, req.prefix])
        return req.toks

    def _activate(self, req: _Pending, S: int) -> _Active:
        if req.preempts:
            self.resume_recompute_toks += S
        return _Active(req.rid, req.max_new, toks=req.toks,
                       deadline=req.deadline, t_submit=req.t_submit,
                       prefix=req.prefix, emitted=len(req.prefix),
                       preempts=req.preempts, draft=req.draft,
                       region=len(req.toks) + req.max_new)

    def _pick_victim(self, lane: _Lane,
                     before: Optional[float] = None) -> Optional[int]:
        """The eviction policy: latest effective deadline first (None →
        +inf), then fewest tokens generated (least recompute thrown away),
        then the youngest rid — deterministic. With ``before`` set
        (admission preemption) only a victim whose deadline is STRICTLY
        later qualifies: a deadline burst displaces lower-priority work
        but never equal-or-higher-priority work, and deadline-less traffic
        never triggers admission preemption at all. Returns the victim's
        slot, or None."""
        best_key, best_slot = None, None
        for slot, st in sorted(lane.active.items()):
            dl = _INF if st.deadline is None else float(st.deadline)
            if before is not None and not dl > before:
                continue
            key = (dl, -st.emitted, st.rid)
            if best_key is None or key > best_key:
                best_key, best_slot = key, slot
        return best_slot

    def _preempt(self, lane: _Lane, slot: int) -> None:
        """Evict one in-flight request: pages back to the free list, slot
        freed, request re-queued (queue back) as a prefill of
        prompt + tokens-so-far. Host bookkeeping only — no decode-program
        retrace (TRACE_LOG-pinned)."""
        st = lane.active[slot]
        prefix = self._partial_tokens(st)
        self._release_slot(lane, slot)
        self.preemptions += 1
        lane.queue.append(_Pending(
            st.rid, st.toks, st.max_new, t_submit=st.t_submit,
            deadline=st.deadline,
            prefix=(np.asarray(prefix, np.int32) if prefix is not None
                    else _empty_toks()),
            preempts=st.preempts + 1, draft=st.draft))

    def _grow_for_chunk(self, lane: _Lane) -> None:
        """Initial-reservation lanes, right before a decode chunk: every
        active slot's page table must cover its next writes — [pos,
        pos + chunk) for the plain scan, [pos, pos + spec_k) for a
        speculative round, clamped to the request's commit-bound region
        (write-ahead past it scatters into the trash page by design and
        must not claim pages that could never hold a committed position).
        Grow reservations on demand; under pool pressure preempt victims
        (``_pick_victim`` policy) until the survivors fit. ``fits()``'s
        resumable-region bound guarantees a lone request always covers
        itself, so this terminates with at least zero active slots and
        never deadlocks."""
        ps = self.ecfg.page_size
        span = self.ecfg.spec_k or self.ecfg.chunk
        while lane.active:
            need: Dict[int, int] = {}
            for slot in sorted(lane.active):
                hi = int(lane.pos[slot]) + span
                if self.ecfg.spec_k:
                    hi = min(hi, lane.active[slot].region)
                want = -(-hi // ps)
                short = want - lane.pt.held(slot)
                if short > 0:
                    need[slot] = short
            if sum(need.values()) <= lane.pt.available:
                for slot, n in sorted(need.items()):
                    lane.pt.grow(slot, n)
                return
            self._preempt(lane, self._pick_victim(lane))

    def _admit_draft(self, lane: _Lane, slot: int, draft_idx: int,
                     full: np.ndarray) -> None:
        """Speculative admission sidecar: prefill the request's prompt
        through its DRAFTER and write the K/V into the drafter's slot pool
        (lazily allocated per lane — uniform, spec_k headroom past the
        target region so sequential drafting never clamps at the edge).
        The draft's own first-token output is discarded: drafting always
        starts from the target-committed ``lane.tok``."""
        dpm = self.pool[draft_idx]
        if draft_idx not in lane.draft_pools:
            dpool = alloc_draft_pool(dpm.cfg, self.ecfg.slots,
                                     self.ecfg.max_seq, self.ecfg.spec_k)
            if self.mesh is not None:
                dpool = shd.shard_kv_pool(dpool, self.mesh)
                lane.draft_params[draft_idx] = shd.replicate(dpm.params,
                                                             self.mesh)
            else:
                lane.draft_params[draft_idx] = dpm.params
            lane.draft_pools[draft_idx] = dpool
        S = len(full)
        S_b = next_pow2(S)
        toks_p = np.zeros((1, S_b), np.int32)
        toks_p[0, :S] = full
        with self._rules():
            _, kv = _prefill_fn(dpm.cfg)(lane.draft_params[draft_idx],
                                         jnp.asarray(toks_p),
                                         jnp.int32(S - 1))
            lane.draft_pools[draft_idx] = _admit_fn(dpm.cfg)(
                lane.draft_pools[draft_idx], kv, jnp.int32(slot))

    def _admit(self, lane: _Lane) -> None:
        if lane.paged:
            self._admit_paged(lane)
            return
        cfg = lane.pm.cfg
        while lane.free and lane.queue:
            req = lane.queue.popleft()
            slot = lane.free.pop()
            full = self._full_prompt(req)
            S = len(full)
            S_b = next_pow2(S)
            toks_p = np.zeros((1, S_b), np.int32)
            toks_p[0, :S] = full
            with self._rules():
                tok0, kv = _prefill_fn(cfg)(lane.params,
                                            jnp.asarray(toks_p),
                                            jnp.int32(S - 1))
                lane.pool = _admit_fn(cfg)(lane.pool, kv, jnp.int32(slot))
            if self.ecfg.spec_k:
                self._admit_draft(lane, slot, req.draft, full)
            self.admission_lat.append(time.perf_counter() - req.t_submit)
            lane.tok[slot] = int(tok0[0])
            lane.pos[slot] = S          # first decode token writes K/V at S
            lane.active[slot] = self._activate(req, S)

    def _admit_paged(self, lane: _Lane) -> None:
        """Paged admission: claim a decode slot + pages (FIFO — the head
        waits for pages rather than being overtaken), then COALESCE
        everything admitted this boundary by prompt bucket: one (B_b, S_b)
        prefill dispatch per bucket with per-row ``last_pos``, one donated
        page scatter. Pad rows of a non-pow2 group prefill garbage into
        the trash page. Lifetime reservation claims the whole region up
        front; initial reservation claims only the prefill bucket's pages
        (growth happens chunk-by-chunk) and may PREEMPT a strictly
        later-deadline victim to admit a deadline-pressed queue head.
        Preemption resumes re-prefill prompt + emitted tokens — they
        coalesce into their (larger) bucket like any fresh request."""
        ecfg = self.ecfg
        ps = ecfg.page_size
        initial = ecfg.reserve == "initial"
        admitted = []                   # (req, slot, S, S_b, pages)
        while lane.queue:
            req = lane.queue[0]
            S = len(req.toks) + len(req.prefix)
            S_b = next_pow2(S)
            if initial:
                need = lane.pt.pages_needed(S_b)
            else:
                need = lane.pt.pages_needed(
                    self._region_len(S, req.max_new - len(req.prefix)))
            if not lane.free or need > lane.pt.available:
                if not initial:
                    break
                victim = self._pick_victim(lane, before=req.eff_deadline())
                if victim is None:
                    break
                self._preempt(lane, victim)
                continue
            lane.queue.popleft()
            slot = lane.free.pop()
            pages = lane.pt.alloc(slot, need)
            admitted.append((req, slot, S, S_b, pages))
        if not admitted:
            return
        cfg = lane.pm.cfg
        groups: Dict[int, list] = {}
        for item in admitted:
            groups.setdefault(item[3], []).append(item)
        for S_b, items in sorted(groups.items()):
            B = len(items)
            B_b = next_pow2(B)
            n_pp = -(-S_b // ps)        # pages the prefill bucket covers
            toks_p = np.zeros((B_b, S_b), np.int32)
            last = np.zeros((B_b,), np.int32)
            pages_mat = np.zeros((B_b, n_pp), np.int32)   # pad rows → trash
            for r, (req, slot, S, _, pages) in enumerate(items):
                toks_p[r, :S] = self._full_prompt(req)
                last[r] = S - 1
                pages_mat[r] = pages[:n_pp]
            with self._rules():
                tok0, kv = _prefill_fn(cfg)(lane.params,
                                            jnp.asarray(toks_p),
                                            jnp.asarray(last))
                lane.pool = _write_pages_fn(cfg)(lane.pool, kv,
                                                 jnp.asarray(pages_mat))
            tok0 = np.asarray(tok0)
            now = time.perf_counter()
            for r, (req, slot, S, _, pages) in enumerate(items):
                if self.ecfg.spec_k:
                    self._admit_draft(lane, slot, req.draft,
                                      self._full_prompt(req))
                self.admission_lat.append(now - req.t_submit)
                lane.tok[slot] = int(tok0[r])
                lane.pos[slot] = S      # first decode token writes K/V at S
                lane.active[slot] = self._activate(req, S)

    def _decode_spec_round(self, lane: _Lane) -> None:
        """One speculative draft/verify round (replaces ``_decode_chunk``
        when ``spec_k > 0``):

        1. **draft** — group active slots by drafter; each drafter's pool
           decodes ``spec_k`` tokens ahead in one cached sequential scan.
           Rows outside a group run masked (tok 0 at pos 0 — their writes
           land below the next occupant's prefill, the same free-row
           convention as the plain chunk).
        2. **verify** — ONE target dispatch over ``spec_k`` positions per
           row: the pending committed token plus the first spec_k - 1
           drafts, each position attending only below its own causal
           bound, so position j's argmax is bitwise what the sequential
           chain would produce there.
        3. **commit / roll back** (host) — the longest prefix of drafts
           matching the verify argmax commits, plus the verify's own
           correction token on a mismatch — between 1 (all drafts
           rejected: exactly one plain decode step) and spec_k tokens per
           row. On FULL acceptance the carry becomes the last draft
           rather than the verify's bonus token: taking the bonus would
           advance past a position the draft model never ingested (it
           drafts only spec_k - 1 tokens past the carry), silently
           corrupting the draft cache and collapsing acceptance from the
           next round on. Capping at spec_k keeps the draft and target
           streams aligned with zero catch-up dispatches. The rejected
           suffix rolls back by simply NOT advancing ``pos`` past the
           accepted point: stale drafted K/V above it stays masked by
           validity and is overwritten before it could ever be attended
           (write-before-validity). Only committed tokens enter
           ``st.chunks``/``st.emitted``, so partial tokens under
           cancel/expire/preempt remain exact solo prefixes.
        """
        cfg, ecfg = lane.pm.cfg, self.ecfg
        k = ecfg.spec_k
        T = k     # verify positions: carry token + first k - 1 drafts
        drafted = np.zeros((ecfg.slots, k), np.int32)
        by_draft: Dict[int, List[int]] = {}
        for slot, st in lane.active.items():
            by_draft.setdefault(st.draft, []).append(slot)
        for d, slots in sorted(by_draft.items()):
            dpm = self.pool[d]
            mask = np.zeros((ecfg.slots,), bool)
            mask[slots] = True
            tok_m = np.where(mask, lane.tok, 0).astype(np.int32)
            pos_m = np.where(mask, lane.pos, 0).astype(np.int32)
            with self._rules():
                lane.draft_pools[d], dr = _draft_fn(dpm.cfg, k)(
                    lane.draft_params[d], lane.draft_pools[d],
                    jnp.asarray(tok_m), jnp.asarray(pos_m))
            dr = np.asarray(dr)
            drafted[slots] = dr[slots]
        ver_tok = np.concatenate([lane.tok[:, None], drafted[:, :k - 1]],
                                 axis=1)
        with self._rules():
            if lane.paged:
                lane.pool, g = _verify_paged_fn(cfg, T)(
                    lane.params, lane.pool, jnp.asarray(lane.pt.table),
                    jnp.asarray(ver_tok), jnp.asarray(lane.pos))
            else:
                lane.pool, g = _verify_fn(cfg, T)(
                    lane.params, lane.pool, jnp.asarray(ver_tok),
                    jnp.asarray(lane.pos))
        g = np.asarray(g)                                 # (slots, T)
        self.spec_rounds += 1
        for slot in list(lane.active):
            st = lane.active[slot]
            ds, gs = drafted[slot], g[slot]
            m = 0
            while m < k and ds[m] == gs[m]:
                m += 1
            self.spec_drafted += k
            self.spec_accepted += m
            self.spec_rejected += k - m
            if m < k:
                # correction: gs[m] is the argmax after the last accepted
                # draft — carry it as the next feed, roll the rest back
                adv = m + 1
                committed = np.concatenate(
                    ([np.int32(lane.tok[slot])], ds[:m])).astype(np.int32)
                lane.tok[slot] = gs[m]
            else:
                # full acceptance: carry the last draft (verified: it
                # equals gs[k-1]), not the bonus gs[k] — the draft cache
                # only extends spec_k - 1 past the carry (see docstring)
                adv = k
                committed = np.concatenate(
                    ([np.int32(lane.tok[slot])],
                     ds[:k - 1])).astype(np.int32)
                lane.tok[slot] = ds[k - 1]
            lane.pos[slot] = int(lane.pos[slot]) + adv
            st.chunks.append(committed)
            st.emitted += adv
            if st.emitted >= st.max_new:
                parts = ([st.prefix] if len(st.prefix) else []) + st.chunks
                tokens = np.concatenate(parts)[:st.max_new]
                status = PREEMPTED_RESUMED if st.preempts else DONE
                self._release_slot(lane, slot)
                self._record(st.rid, status, tokens=tokens)

    def _decode_chunk(self, lane: _Lane) -> None:
        cfg, ecfg = lane.pm.cfg, self.ecfg
        with self._rules():
            if lane.paged:
                lane.pool, tok, pos, out = _chunk_paged_fn(cfg, ecfg.chunk)(
                    lane.params, lane.pool, jnp.asarray(lane.pt.table),
                    jnp.asarray(lane.tok), jnp.asarray(lane.pos))
            else:
                lane.pool, tok, pos, out = _chunk_fn(cfg, ecfg.chunk)(
                    lane.params, lane.pool, jnp.asarray(lane.tok),
                    jnp.asarray(lane.pos))
        out = np.asarray(out)
        active_mask = np.zeros((ecfg.slots,), bool)
        active_mask[list(lane.active)] = True
        # free slots keep (tok=0, pos=0). Their garbage K/V writes are safe
        # by the write-before-validity invariant: a slot's valid region
        # [0, pos+1) is always entirely written by its CURRENT occupant —
        # prefill covers [0, S_b), and each decode step writes position p
        # before validity reaches p — so stale leftovers are never attended.
        # (Paged lanes scatter free rows' garbage into the trash page, whose
        # contents no request's page table maps below its validity bound.)
        lane.tok = np.where(active_mask, np.asarray(tok), 0).astype(np.int32)
        lane.pos = np.where(active_mask, np.asarray(pos), 0).astype(np.int32)
        for slot in list(lane.active):
            st = lane.active[slot]
            st.chunks.append(out[slot])
            st.emitted += ecfg.chunk
            if st.emitted >= st.max_new:
                parts = ([st.prefix] if len(st.prefix) else []) + st.chunks
                tokens = np.concatenate(parts)[:st.max_new]
                status = PREEMPTED_RESUMED if st.preempts else DONE
                self._release_slot(lane, slot)
                self._record(st.rid, status, tokens=tokens)
