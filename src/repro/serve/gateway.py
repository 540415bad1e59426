"""RoutedServer: the paper's router in front of an actual model pool.

A request is (i) embedded by the encoder stub, (ii) routed by one
``repro.routers.Router`` — the MLP family decides via the fused Pallas
``router_utility`` kernel, the K-means family via the ``kmeans_assign``
kernel + cluster-level utility — and (iii) served by the chosen model.
This is the deployment shape the paper targets: per-request model
selection under an accuracy/cost trade-off λ chosen at inference time
(§3).

Serving runs through the continuous-batching engine by default
(``repro.serve.engine``): concurrent requests share one paged KV pool per
model (vLLM-style fixed-size pages + per-request page tables — each
request reserves only what its own length needs), same-bucket admissions
coalesce into one batched prefill, and everything decodes together in
chunked scans. ``EngineConfig(page_size=None)`` selects the uniform slot
pool (every slot reserves ``max_seq``). ``generate(engine=False)`` keeps
the original per-call path — the whole prompt batch group-padded per
model and decoded as one ``lax.scan`` (``scan_decode=False`` further
drops to the per-token debugging loop). SSM/hybrid archs always take the
per-call path (their state integrates over pad positions, so prompts are
served unpadded).

Hot-path discipline: every jitted function here is built ONCE per
(model config, static shape) and cached at module level — nothing is
re-jitted per request. Batch sizes and prompt lengths are bucketed to
powers of two so repeated traffic reuses compiled programs, and greedy
decode returns whole token matrices in one device→host transfer (no
per-token sync).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
import zlib
from typing import TYPE_CHECKING, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.data.encoder import encode
from repro.models import model as mdl

if TYPE_CHECKING:  # repro.fed is the higher layer — type-only import keeps
    from repro.fed.faults import FaultPlan  # serve → fed one-directional
    from repro.fed.harvest import HarvestStore
from repro.routers import Router
# TRACE_LOG lives in engine.py (bounded deque) and is re-exported here so
# `gateway.TRACE_LOG` keeps working for tests and callers; same for
# reset_trace_log.
from repro.serve.engine import (CANCELLED, EXPIRED, SHED, EngineConfig,
                                Outcome, ServeEngine, TRACE_LOG)
from repro.serve.engine import next_pow2 as _next_pow2
from repro.serve.engine import reset_trace_log  # noqa: F401
from repro.serve.kv_cache import extend_cache

#: un-reported harvest entries kept per server (submit → report_outcome);
#: oldest evicted beyond this so feedback-less traffic can't grow memory.
PENDING_EVAL_CAP = 8192


@dataclasses.dataclass
class PoolModel:
    name: str
    cfg: ModelConfig
    params: dict
    cost_per_token: float


@functools.lru_cache(maxsize=None)
def _decode_step_fn(cfg: ModelConfig):
    """Jitted single-token decode step, cached per model config (the
    per-token fallback path — never rebuilt per request batch)."""
    def step(params, cache, tok, pos):
        TRACE_LOG.append(("decode_step", cfg.name, tok.shape))
        return mdl.decode_step(params, cache, cfg, tokens=tok, pos=pos)
    return jax.jit(step)


@functools.lru_cache(maxsize=None)
def _serve_fn(cfg: ModelConfig, max_new: int):
    """Jitted prefill + greedy ``lax.scan`` decode, cached per
    (model config, max_new); distinct (B, S) buckets land in the jit
    tracing cache, so same-bucket traffic compiles nothing.

    ``last_pos`` (traced) is the true last prompt position inside the
    padded S bucket; decode continues from ``last_pos + 1`` and the cache
    slots holding pad prefill K/V are overwritten before they ever become
    attention-valid (validity is ``pos + 1``).
    """
    def serve(params, toks, last_pos):
        TRACE_LOG.append(("serve", cfg.name, toks.shape, max_new))
        S = toks.shape[1]
        logits, _, cache = mdl.forward(params, cfg, tokens=toks,
                                       logits_last_only=True,
                                       last_pos=last_pos,
                                       return_cache=True, q_chunk=64)
        cache = extend_cache(cache, S + max_new)
        tok0 = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)

        def body(carry, t):
            tok, cache = carry
            logits_t, cache = mdl.decode_step(params, cache, cfg,
                                              tokens=tok,
                                              pos=last_pos + 1 + t)
            nxt = jnp.argmax(logits_t[:, 0], axis=-1)[:, None]
            return (nxt.astype(jnp.int32), cache), tok[:, 0]

        _, out = jax.lax.scan(body, (tok0, cache),
                              jnp.arange(max_new, dtype=jnp.int32))
        return out.T                                  # (B, max_new)

    return jax.jit(serve)


class RoutedServer:
    """λ is a per-request knob — no router retraining needed (§3).

    Takes ONE fitted ``Router`` (any registered family); the router's model
    dimension M must match the pool, checked at construction so a mismatch
    fails loudly instead of silently wrapping indices at serve time.
    """

    def __init__(self, pool: List[PoolModel], router: Router,
                 d_emb: Optional[int] = None,
                 engine_cfg: Optional[EngineConfig] = None,
                 harvest: "Optional[HarvestStore]" = None,
                 fault_plan: "Optional[FaultPlan]" = None,
                 max_retries: int = 2, retry_backoff: float = 0.0,
                 mesh=None):
        if not isinstance(router, Router):
            raise TypeError(
                "RoutedServer takes a repro.routers.Router — build one with "
                "routers.make(...) + routers.fit_federated(...) or "
                "routers.load(...)")
        if not router.initialized:
            raise ValueError("router has no fitted state — fit or load it "
                             "before serving")
        if router.num_models != len(pool):
            raise ValueError(
                f"router predicts over M={router.num_models} models but the "
                f"pool has {len(pool)} — onboard the missing models "
                "(router.onboard_model) or fix the pool")
        if d_emb is not None and d_emb != router.rcfg.d_emb:
            raise ValueError(
                f"d_emb={d_emb} does not match the router's embedding "
                f"dimension {router.rcfg.d_emb} — drop d_emb= to use the "
                "router's own")
        self.pool = pool
        self.router = router
        self.d_emb = router.rcfg.d_emb
        # One jitted decision function per router object. State and λ are
        # traced arguments — not baked-in constants — so in-place state
        # swaps and per-request λ never recompile or go stale; batch sizes
        # are bucketed below so repeat traffic hits the tracing cache.
        # A replaced router object (e.g. a different family swapped in)
        # rebuilds the function on the next route().
        self._route_fn = self._make_route_fn(router)
        self._route_fn_router = router
        # One continuous-batching engine per server: per-model slot pools
        # are allocated lazily on first traffic to that model. ``mesh``
        # selects cross-silo execution — KV pools sharded over the mesh's
        # "data"/"heads" axes, decode dispatched as one mesh program (see
        # ServeEngine); the per-request fallback path stays solo.
        self.mesh = mesh
        self.engine = ServeEngine(pool, engine_cfg, mesh=mesh)
        # Harvest layer (repro.fed): per-client EvalBuffers fed by routed
        # traffic. Outcome scores arrive asynchronously via
        # report_outcome(); un-reported entries wait (bounded) in
        # _pending_evals.
        self.harvest = harvest
        self._pending_evals: Dict[int, tuple] = {}
        # Bounded tombstones so unknown-rid errors can say WHY the rid is
        # gone (evicted by the pending-cap vs already reported) instead of
        # a bare KeyError.
        self._evicted_rids = collections.deque(maxlen=4096)
        self._reported_rids = collections.deque(maxlen=4096)
        #: bumped by every swap_router_state/add_model — the "versioned
        #: router state" the FedLoop publishes into the route path.
        self.router_version = 0
        # Fault tolerance: an optional FaultPlan (repro.fed.faults, duck-
        # typed — serve stays importable without fed) makes submit()
        # consult backend_fails() per attempt; failures retry with
        # exponential backoff, then degrade gracefully by re-routing to
        # the next-best model under the router's own utility.
        self.fault_plan = fault_plan
        self.max_retries = int(max_retries)
        self.retry_backoff = float(retry_backoff)
        self._submit_seq = 0
        #: observability counters for the resilience bench/tests.
        self.backend_failures = 0
        self.retries = 0
        self.failovers = 0
        #: expiries count as backend failures for harvest purposes (the
        #: router should learn an overloaded backend the same way it
        #: learns a crashed one); the tombstones make unknown-rid errors
        #: actionable and dedupe the expiry→failure accounting.
        self.expiry_failures = 0
        self._failed_rids = collections.deque(maxlen=4096)
        self._terminated_rids = collections.deque(maxlen=4096)

    @staticmethod
    def _make_route_fn(router: Router):
        def gateway_route(state, x, lam):
            TRACE_LOG.append(("route", type(router).__name__, x.shape))
            return router.with_state(state).route(x, lam)
        return jax.jit(gateway_route)

    def _route_x(self, x: np.ndarray, lam: float) -> np.ndarray:
        """Route pre-encoded query embeddings x: (B, d_emb) → (B,) model
        indices. The jitted decision fn takes state and λ as traced
        arguments, so hot-swapped router state and per-request λ hit the
        same compiled program (TRACE_LOG-pinned). The ``gateway.route``
        span runs from dispatch through the fetch of the choices."""
        if self.router is not self._route_fn_router:
            self._route_fn = self._make_route_fn(self.router)
            self._route_fn_router = self.router
        B = x.shape[0]
        B_b = _next_pow2(B)
        if B_b != B:
            x = np.concatenate([x, np.zeros((B_b - B, x.shape[1]),
                                            x.dtype)])
        with jax.profiler.TraceAnnotation("gateway.route"):
            choice = self._route_fn(self.router.state, jnp.asarray(x),
                                    jnp.float32(lam))
            choice = np.asarray(choice)
        return choice[:B]

    def route(self, prompts: List[str], lam: float) -> np.ndarray:
        return self._route_x(encode(prompts, self.d_emb), lam)

    # ----------------------------------------------------- router lifecycle
    def swap_router_state(self, state) -> None:
        """Hot-swap fitted router state under live traffic. The new state
        must be the same family and pytree structure (same-shape buffers),
        so it enters the cached route jit as a traced argument — ZERO
        retraces, no decode interruption; in-flight requests keep decoding
        against their already-routed models. Bumps ``router_version``."""
        new_router = self.router.with_state(state)
        old_l, old_s = jax.tree.flatten(self.router.state)
        new_l, new_s = jax.tree.flatten(state)
        shapes_match = (old_s == new_s and len(old_l) == len(new_l) and all(
            getattr(a, "shape", None) == getattr(b, "shape", None)
            and getattr(a, "dtype", None) == getattr(b, "dtype", None)
            for a, b in zip(old_l, new_l)))
        if not shapes_match:
            raise ValueError(
                "swap_router_state got a different state structure or "
                "buffer shapes — a structural change (new family, expanded "
                "pool) is an add_model/replacement, not a hot swap")
        self.router = new_router
        # keep the cached jit: the route fn closes over the old router
        # object only for with_state(), which rebuilds by class + rcfg —
        # identical for a same-family swap.
        self._route_fn_router = new_router
        self.router_version += 1

    def add_model(self, pm: PoolModel, router: Router) -> None:
        """Onboard a new pool model mid-run (§6.3): append it to the pool
        (the engine shares the list — its lane and compiled programs build
        lazily on first traffic) and install the expanded router. The route
        program re-traces ONCE for the new head shape; every decode
        program of existing models is untouched."""
        if router.num_models != len(self.pool) + 1:
            raise ValueError(
                f"add_model expects a router expanded to M={len(self.pool) + 1}"
                f" (got M={router.num_models}) — onboard the router first "
                "(router.onboard_model)")
        self.pool.append(pm)
        self.router = router
        # rebuild the route program for the new router object — the head
        # shape changed, so a retrace is due anyway, and a replacement of a
        # different family must not run through the old closure
        self._route_fn = self._make_route_fn(router)
        self._route_fn_router = router
        self.router_version += 1

    # -------------------------------------------------- engine streaming API
    def submit(self, prompt: str, *, lam: float = 0.5,
               max_new_tokens: int = 16,
               tokenize: Optional[Callable] = None,
               client_id: Optional[int] = None,
               x: Optional[np.ndarray] = None,
               deadline: Optional[int] = None,
               draft_model: Optional[int] = None) -> int:
        """Route one prompt and enqueue it on the continuous-batching
        engine; returns a request id. The request joins the routed model's
        shared decode batch at the next free slot — call ``step()`` to
        advance in-flight decoding or ``drain()`` to run to completion.

        ``x`` supplies a pre-computed query embedding (simulators, callers
        with a real encoder) instead of the stub ``encode``. With a
        ``harvest`` store attached and ``client_id`` given, the request is
        registered for evaluation harvesting: ``routed_model(rid)`` exposes
        the choice and ``report_outcome(rid, ...)`` appends the completed
        (x, model, outcome, cost) observation to that client's EvalBuffer.

        With a ``fault_plan`` attached, a failing backend is retried
        ``max_retries`` times with exponential backoff, then the request
        degrades gracefully: it re-routes to the next-best model under the
        router's own utility A − λ·C (excluding failed backends), counts
        the failover, and the harvest records the model that actually
        served it — the realized outcome, not the intended route.

        On a speculative engine (``EngineConfig.spec_k > 0``) the request
        is paired with a **drafter** from the same pool: ``draft_model``
        pins one by pool index; otherwise the gateway walks the router's
        own utility ranking A − λ·C over the pool and picks the
        highest-utility model that is strictly cheaper than the target
        (vocab-compatible attention archs only — the engine's
        constraints), falling back to the target itself. The router
        already ranks models by predicted quality on THIS query, so its
        best cheap model is exactly the drafter most likely to agree with
        the target and keep acceptance high.

        ``deadline`` bounds the request's lifetime in engine steps (see
        ``ServeEngine.submit``); an EXPIRED request counts as a backend
        failure for harvest purposes (zero-score outcome recorded against
        the routed model). A submit SHED by a full lane queue still
        returns its rid but is never harvest-registered — nothing was
        served, nothing to learn."""
        x_arr = (encode([prompt], self.d_emb)[0] if x is None
                 else np.asarray(x, np.float32).reshape(self.d_emb))
        m_idx = int(self._route_x(x_arr[None], lam)[0])
        if self.fault_plan is not None:
            m_idx = self._submit_with_failover(m_idx, x_arr, lam)
        toks = self._tokenize([prompt], self.pool[m_idx].cfg, tokenize)[0]
        if self.engine.ecfg.spec_k:
            draft = (int(draft_model) if draft_model is not None
                     else self._pick_draft(m_idx, x_arr, lam))
        elif draft_model is not None:
            raise ValueError("submit(draft_model=...) needs a speculative "
                             "engine — set EngineConfig.spec_k > 0")
        else:
            draft = None
        rid = self.engine.submit(m_idx, toks, max_new_tokens,
                                 deadline=deadline, draft=draft)
        if self.engine._status.get(rid) == SHED:
            self._terminated_rids.append(rid)
            return rid
        if self.harvest is not None and client_id is not None:
            cost_est = self.pool[m_idx].cost_per_token * max_new_tokens
            self._pending_evals[rid] = (int(client_id), x_arr, m_idx,
                                        cost_est)
            while len(self._pending_evals) > PENDING_EVAL_CAP:
                old = next(iter(self._pending_evals))
                self._pending_evals.pop(old)
                self._evicted_rids.append(old)
        return rid

    def _submit_with_failover(self, m_idx: int, x_arr: np.ndarray,
                              lam: float) -> int:
        """Resolve the backend that will actually serve this submission:
        retry transient failures with backoff, then walk down the router's
        utility ranking past hard failures. Raises RuntimeError only when
        every pool backend has failed."""
        seq = self._submit_seq
        self._submit_seq += 1
        plan = self.fault_plan
        failed: set = set()
        order = None
        attempt = 0
        while plan.backend_fails(m_idx, seq, attempt):
            self.backend_failures += 1
            if attempt < self.max_retries:
                attempt += 1
                self.retries += 1
                if self.retry_backoff > 0.0:
                    time.sleep(self.retry_backoff * 2.0 ** (attempt - 1))
                continue
            failed.add(m_idx)
            if len(failed) == len(self.pool):
                raise RuntimeError(
                    f"all {len(self.pool)} pool backends failed request "
                    f"#{seq} — nothing left to re-route to")
            if order is None:  # rank once, off the hot path
                A, C = self.router.predict(jnp.asarray(x_arr[None]))
                util = np.asarray(A[0] - lam * C[0])
                order = [int(i) for i in np.argsort(-util)]
            m_idx = next(i for i in order if i not in failed)
            self.failovers += 1
            attempt = 0
        return m_idx

    def _pick_draft(self, m_idx: int, x_arr: np.ndarray,
                    lam: float) -> int:
        """Router-paired drafter selection (speculative engines): among
        pool models that can legally draft for the target — attention
        archs sharing its vocab — and are strictly cheaper per token, pick
        the one the router itself ranks highest under A − λ·C on this
        query. Falls back to the target (self-speculation: always correct,
        never faster) when nothing cheaper qualifies. One predict() call
        per submit, same ranking the failover path uses."""
        tgt = self.pool[m_idx]
        cand = [i for i, pm in enumerate(self.pool)
                if i != m_idx
                and pm.cost_per_token < tgt.cost_per_token
                and pm.cfg.vocab == tgt.cfg.vocab
                and pm.cfg.arch_type not in ("ssm", "hybrid")]
        if not cand:
            return m_idx
        A, C = self.router.predict(jnp.asarray(x_arr[None]))
        util = np.asarray(A[0] - lam * C[0])
        return max(cand, key=lambda i: util[i])

    def _unknown_rid(self, rid: int) -> ValueError:
        """A specific, actionable error for a rid with no pending eval:
        says which rid and *why* it is unknown."""
        if rid in self._evicted_rids:
            why = (f"it was evicted by the pending-eval cap "
                   f"(PENDING_EVAL_CAP={PENDING_EVAL_CAP}) — report "
                   "outcomes sooner or raise the cap")
        elif rid in self._reported_rids:
            why = "its outcome was already reported (each rid reports once)"
        elif rid in self._failed_rids:
            why = ("it EXPIRED past its deadline — the gateway already "
                   "recorded the expiry as a zero-score backend failure")
        elif rid in self._terminated_rids:
            why = ("it was cancelled or shed before serving — nothing was "
                   "generated, so there is no outcome to report")
        else:
            why = ("it was never harvest-registered — submit() it with "
                   "client_id= and attach a HarvestStore to track routing "
                   "outcomes")
        return ValueError(f"request {rid} has no pending evaluation: {why}")

    def routed_model(self, rid: int) -> int:
        """Model index a harvest-registered request was routed to.
        Raises ValueError for an unknown/already-reported/evicted rid."""
        try:
            return self._pending_evals[rid][2]
        except KeyError:
            raise self._unknown_rid(rid) from None

    def report_outcome(self, rid: int, score: float,
                       cost: Optional[float] = None) -> None:
        """Client feedback closes the harvest loop: append the completed
        (query embedding, routed model, outcome score, cost) observation to
        the submitting client's EvalBuffer. ``cost`` defaults to the
        submit-time estimate (cost_per_token × max_new). Raises ValueError
        for an unknown/already-reported/evicted rid."""
        try:
            client_id, x_arr, m_idx, cost_est = self._pending_evals.pop(rid)
        except KeyError:
            raise self._unknown_rid(rid) from None
        self._reported_rids.append(rid)
        self.harvest.record(client_id, x_arr, m_idx, float(score),
                            float(cost if cost is not None else cost_est))

    def cancel(self, rid: int) -> str:
        """Cancel an engine request (see ``ServeEngine.cancel``) and drop
        its pending harvest registration — nothing was served, so there is
        no outcome to report. Returns the request's typed status."""
        status = self.engine.cancel(rid)
        if self._pending_evals.pop(rid, None) is not None:
            self._terminated_rids.append(rid)
        return status

    def status(self, rid: int) -> str:
        """Typed lifecycle status of an engine request (see
        ``ServeEngine.status``)."""
        return self.engine.status(rid)

    def _absorb_outcomes(self, results) -> None:
        """React to typed non-completion terminals from the engine.
        EXPIRED is a backend failure for harvest purposes: the overloaded
        backend gets a zero-score outcome recorded against it (the router
        learns to avoid it, exactly like a crashed backend in the PR 7
        failover path) and ``backend_failures``/``expiry_failures`` bump.
        CANCELLED / SHED just drop the pending registration — nothing was
        served, nothing to learn."""
        for rid, payload in results:
            if not isinstance(payload, Outcome):
                continue
            if payload.status == EXPIRED:
                if rid in self._failed_rids:
                    continue
                self._failed_rids.append(rid)
                self.backend_failures += 1
                self.expiry_failures += 1
                ent = self._pending_evals.pop(rid, None)
                if ent is not None and self.harvest is not None:
                    client_id, x_arr, m_idx, cost_est = ent
                    self.harvest.record(client_id, x_arr, m_idx, 0.0,
                                        cost_est)
            elif payload.status in (CANCELLED, SHED):
                if self._pending_evals.pop(rid, None) is not None:
                    self._terminated_rids.append(rid)

    def step(self):
        """Advance every busy engine lane one chunk (admissions happen at
        chunk boundaries). Returns [(request id, result)] for requests
        that reached a terminal state — np tokens for completions, a typed
        ``Outcome`` for expired/cancelled/shed requests (absorbed into the
        harvest accounting, see ``_absorb_outcomes``)."""
        finished = self.engine.step()
        self._absorb_outcomes(finished)
        return finished

    def drain(self, rids=None) -> Dict[int, np.ndarray]:
        """Run the engine until idle; returns {request id: result} (np
        tokens, or a typed ``Outcome`` for non-completions). ``rids``
        passes through to ``ServeEngine.drain``: an iterable of request
        ids drains until exactly those terminate, leaving other in-flight
        streams' results in place. (The passthrough was dropped when the
        engine grew the parameter — callers interleaving submit streams
        through the gateway silently drained, and CLEARED, everything.)"""
        out = self.engine.drain(rids)
        self._absorb_outcomes(out.items())
        return out

    # ------------------------------------------------------------- generate
    def generate(self, prompts: List[str], *, lam: float = 0.5,
                 max_new_tokens: int = 16,
                 tokenize: Optional[Callable] = None,
                 scan_decode: bool = True, engine: bool = True) -> Dict:
        """Route, then serve every prompt as its own request through the
        continuous-batching engine (per-model slot pools, chunked shared
        decode). Each prompt is prefilled at its own pow2 length bucket, so
        results are bit-identical to serving it alone.

        engine=False restores the per-call grouped path: each model's
        prompts are padded to one (B, S) batch and decoded together —
        shorter prompts then attend pad positions of the group's longest.
        scan_decode=False (with engine=False) further selects the
        per-token fallback loop (one host sync per token) — same tokens as
        the grouped scan, kept for debugging/comparison. SSM/hybrid models
        always take the per-call path (no padded slot sharing).
        """
        choice = self.route(prompts, lam)
        results = [None] * len(prompts)
        cost = 0.0
        rid_to_slot = {}
        for m_idx in np.unique(choice):
            pm = self.pool[int(m_idx)]
            idx = np.where(choice == m_idx)[0]
            use_engine = (engine and scan_decode
                          and pm.cfg.arch_type not in ("ssm", "hybrid"))
            if use_engine:
                for i in idx:
                    toks_i = self._tokenize([prompts[i]], pm.cfg, tokenize)[0]
                    if not self.engine.fits(len(toks_i), max_new_tokens):
                        # request exceeds a slot region — serve it per-call
                        # (extend_cache path), like the pre-engine gateway
                        out = self._serve_batch(pm, toks_i[None],
                                                max_new_tokens)
                        results[i] = {"model": pm.name,
                                      "tokens": out[0].tolist()}
                        continue
                    rid = self.engine.submit(int(m_idx), toks_i,
                                             max_new_tokens)
                    rid_to_slot[rid] = (int(i), pm.name)
            else:
                toks = self._tokenize([prompts[i] for i in idx], pm.cfg,
                                      tokenize)
                out = self._serve_batch(pm, toks, max_new_tokens,
                                        scan_decode=scan_decode)
                for j, i in enumerate(idx):
                    results[i] = {"model": pm.name, "tokens": out[j].tolist()}
            cost += pm.cost_per_token * max_new_tokens * len(idx)
        if rid_to_slot:
            for rid, toks in self.engine.drain(rid_to_slot).items():
                i, name = rid_to_slot[rid]
                results[i] = {"model": name, "tokens": toks.tolist()}
        return {"results": results, "total_cost": cost,
                "routing": choice.tolist()}

    @staticmethod
    def _tokenize(prompts, cfg, tokenize):
        if tokenize is not None:
            return tokenize(prompts)
        # stub tokenizer: crc32 is stable across processes (unlike builtin
        # hash, which varies with PYTHONHASHSEED)
        L = max(max(len(p.split()) for p in prompts), 1)
        out = np.zeros((len(prompts), L), np.int32)
        for i, p in enumerate(prompts):
            for j, w in enumerate(p.split()):
                out[i, j] = zlib.crc32(w.encode("utf-8")) % (cfg.vocab - 1) + 1
        return out

    @staticmethod
    def _serve_batch(pm: PoolModel, toks: np.ndarray, max_new: int, *,
                     scan_decode: bool = True):
        cfg = pm.cfg
        B, S = toks.shape
        if scan_decode:
            # Bucket (B, S, max_new) to powers of two so repeat traffic
            # reuses the compiled program and the program cache stays
            # bounded. Greedy decode is prefix-stable, so decoding to the
            # bucket length and slicing changes nothing. SSM/hybrid states
            # integrate over every prefill position, so their prompts are
            # served unpadded (cache hits still cover repeated lengths).
            B_b = _next_pow2(B)
            S_b = S if cfg.arch_type in ("ssm", "hybrid") else _next_pow2(S)
            toks_p = np.zeros((B_b, S_b), np.int32)
            toks_p[:B, :S] = toks
            out = _serve_fn(cfg, _next_pow2(max_new))(
                pm.params, jnp.asarray(toks_p), jnp.int32(S - 1))
            return np.asarray(out)[:B, :max_new]

        # fallback: per-token Python loop (cached jitted step)
        step = _decode_step_fn(cfg)
        toks_j = jnp.asarray(toks)
        logits, _, cache = mdl.forward(pm.params, cfg, tokens=toks_j,
                                       logits_last_only=True,
                                       return_cache=True, q_chunk=64)
        cache = extend_cache(cache, S + max_new)
        out = np.zeros((B, max_new), np.int32)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        for t in range(max_new):
            out[:, t] = np.asarray(tok[:, 0])
            logits_t, cache = step(pm.params, cache, tok, jnp.int32(S + t))
            tok = jnp.argmax(logits_t[:, 0], axis=-1)[:, None].astype(jnp.int32)
        return out
