"""Loop layer: the FedLoop scheduler — serve → harvest → federate → swap.

A ``FedLoop`` wraps a ``RoutedServer`` (with a ``HarvestStore`` attached)
and interleaves federated refits over the harvested client buffers with the
engine's decode chunks:

  * ``step()`` advances every busy engine lane one chunk (exactly
    ``RoutedServer.step``) and, at ``sync_every``-chunk boundaries with
    enough harvested samples, runs a federated sync.
  * ``sync()`` is literally ``routers.fit_federated`` over
    ``harvest.as_federated_data()`` starting from the live router's state —
    so an offline fit over the same buffers with the same key reproduces an
    online sync bit-for-bit (test-enforced) — followed by
    ``server.swap_router_state``: the refit state enters the cached route
    jit as a traced argument, ZERO retraces, while decode keeps running.
  * ``onboard_model()`` admits a new ``PoolModel`` mid-run (§6.3): new head
    columns trained on calibration evals, pool extended, expanded router
    installed (one route retrace for the new head shape — decode programs
    untouched).

Padding harvested data to the buffer capacity (``pad_to_capacity``, the
default) keeps the federated stack's shapes static across syncs, so the
compiled scan fit from ``core/federated.py`` is built once and every later
sync is a pure cache hit.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

import repro.sharding as shd
from repro import routers
from repro.config import FedConfig
from repro.core import federated as F
from repro.train import checkpoint as ckpt

#: FedLoop.save() payload format version (bumped on layout changes).
CHECKPOINT_FORMAT = 1


@dataclasses.dataclass(frozen=True)
class FedLoopConfig:
    sync_every: int = 16      #: engine chunks between federated syncs
    rounds_per_sync: int = 4  #: FedAvg rounds per sync (ignored by one-shot
    #: families, which refit from scratch each sync)
    min_samples: int = 16     #: total harvested samples required to sync
    pad_to_capacity: bool = True  #: pad the federated stack to the buffer
    #: capacity — static shapes, one compile for every sync
    cohort: Optional[int] = None  #: per-round client sampling inside each
    #: sync's fit (parametric families; see core/federated.fedavg)
    mesh: Optional[Mesh] = None  #: cross-silo mesh for the sync fits: the
    #: harvested client stack is padded to the "clients" axis (zero-weight
    #: rows — they never move the params), sharded across devices, and the
    #: whole fit runs under shard_map (core/federated.fedavg mesh path) —
    #: bit-for-bit the in-process fit on the same padded stack. The padded
    #: slab is donated into the compiled fit on parametric families. The
    #: hot-swap after each sync is mesh-agnostic (state enters the route
    #: jit as a traced argument either way)


class FedLoop:
    """Online federation runtime over one ``RoutedServer``.

    Owns the PRNG stream for the online refits, so a loop run is exactly
    reproducible from its seed; ``history`` records one entry per sync
    (router version, per-round losses, harvested sample count).
    """

    def __init__(self, server, fcfg: FedConfig, *, key,
                 aggregator=None, cfg: Optional[FedLoopConfig] = None):
        if server.harvest is None:
            raise ValueError("FedLoop needs a RoutedServer with a "
                             "HarvestStore attached (harvest=...)")
        self.server = server
        self.fcfg = fcfg
        self.aggregator = aggregator
        self.cfg = cfg or FedLoopConfig()
        self._key = key
        self._chunks = 0
        self.history: List[Dict[str, Any]] = []
        # Staleness bookkeeping for buffered-async aggregators: per client,
        # the lifetime sample count at the previous sync and the sync index
        # at which it last contributed fresh data.
        self._syncs = 0
        self._seen_at_sync: Dict[int, int] = {}
        self._fresh_at_sync: Dict[int, int] = {}

    @property
    def version(self) -> int:
        """The served router version (bumped by syncs and onboarding)."""
        return self.server.router_version

    # ------------------------------------------------------------- serving
    def step(self) -> List[Tuple[int, np.ndarray]]:
        """One engine chunk on every busy lane; a federated sync fires at
        ``sync_every`` boundaries once ``min_samples`` are harvested.
        Returns the requests finished this chunk, like ``server.step``."""
        finished = self.server.step()
        self._chunks += 1
        if self._chunks % self.cfg.sync_every == 0:
            self.maybe_sync()
        return finished

    def drain(self) -> Dict[int, np.ndarray]:
        """Step (with sync boundaries) until every lane is idle."""
        out: Dict[int, np.ndarray] = {}
        while self.server.engine.busy:
            out.update(self.step())
        return out

    # ---------------------------------------------------------- federation
    def maybe_sync(self):
        """Sync iff enough evaluations are harvested; None otherwise."""
        if len(self.server.harvest) < self.cfg.min_samples:
            return None
        return self.sync()

    def sync(self, *, key=None) -> dict:
        """One federated refit over the harvested buffers + hot-swap.

        Exactly ``routers.fit_federated(server.router, harvested, fcfg)``
        from the live router's state — deterministically harvested buffers
        therefore reproduce an offline fit bit-for-bit (test-enforced).
        Returns the fit history. Host spans: ``fed.sync`` the whole call,
        ``fed.stack`` the harvest stacked into the client slab, ``fed.fit``
        the fit through the fetch of its losses."""
        with jax.profiler.TraceAnnotation("fed.sync"):
            return self._sync(key)

    def _sync(self, key) -> dict:
        harvest = self.server.harvest
        if len(harvest) == 0:
            raise ValueError("sync() with empty harvest buffers would "
                             "aggregate zero-weight clients — serve some "
                             "traffic first (maybe_sync gates on "
                             "min_samples)")
        if key is None:
            self._key, key = jax.random.split(self._key)
        with jax.profiler.TraceAnnotation("fed.stack"):
            data = harvest.as_federated_data(
                pad_to=harvest.capacity if self.cfg.pad_to_capacity
                else None)
        kw = {} if self.aggregator is None else {
            "aggregator": self.aggregator}
        if self.cfg.cohort is not None:
            kw["cohort"] = self.cfg.cohort
        if getattr(self.aggregator, "needs_staleness", False):
            ids = harvest.client_ids()
            if not self.cfg.pad_to_capacity:  # unpadded stacks skip empties
                ids = [c for c in ids if len(harvest.buffer(c)) > 0]
            kw["staleness"] = self._staleness_vector(ids)
        if self.cfg.mesh is not None:
            # pad the stack to the clients axis (zero-weight rows), place
            # it sharded, and run the whole sync fit on the mesh; the slab
            # is freshly built each sync, so parametric fits may donate it
            data, stal = F.pad_client_axis(
                data, self.cfg.mesh.shape["clients"], kw.get("staleness"))
            if stal is not None:
                kw["staleness"] = stal
            data = shd.shard_clients(data, self.cfg.mesh)
            kw["mesh"] = self.cfg.mesh
            if self.server.router.parametric:
                kw["donate_data"] = True
        with jax.profiler.TraceAnnotation("fed.fit"):
            new_router, hist = routers.fit_federated(
                self.server.router, data, self.fcfg, key=key,
                rounds=self.cfg.rounds_per_sync, **kw)
        self.server.swap_router_state(new_router.state)
        self._note_sync()
        # snapshot the engine's resilience counters alongside each sync so
        # a history trace shows how much shedding/preemption/expiry the
        # serving layer absorbed while this router version was learned
        self.history.append({"version": self.version,
                             "loss": hist["loss"],
                             "samples": len(harvest),
                             "engine": self.server.engine.counters()})
        return hist

    def _staleness_vector(self, ids) -> np.ndarray:
        """(N,) syncs since each stacked client (sorted ids — the
        ``as_federated_data`` order) last contributed fresh samples; 0 for
        clients with new data since the previous sync."""
        out = []
        for c in ids:
            seen = self.server.harvest.buffer(c).total_seen
            if seen > self._seen_at_sync.get(c, 0):
                out.append(0)
            else:
                out.append(self._syncs - self._fresh_at_sync.get(c, 0))
        return np.asarray(out, np.float32)

    def _note_sync(self) -> None:
        """Advance the staleness bookkeeping after a completed sync."""
        for c in self.server.harvest.client_ids():
            seen = self.server.harvest.buffer(c).total_seen
            if seen > self._seen_at_sync.get(c, 0):
                self._fresh_at_sync[c] = self._syncs
            self._seen_at_sync[c] = seen
        self._syncs += 1

    # -------------------------------------------------- checkpoint / resume
    def save(self, path) -> None:
        """Checkpoint the WHOLE loop — router state + version, every
        harvest ring (verbatim: write heads, lifetime counters, LRU
        order), the loop's PRNG key, chunk counter, staleness bookkeeping,
        sync history, pending evaluations, and the engine's rid counter —
        via ``train/checkpoint`` (msgpack, atomic write). A loop restored
        from this file continues BIT-IDENTICALLY to one that was never
        interrupted (test-enforced).

        Requires an idle engine: in-flight KV state is not checkpointable,
        so ``drain()`` first. Pending evaluations (submitted, outcome not
        yet reported) survive: they are host-side tuples."""
        if self.server.engine.busy:
            raise ValueError("save() needs an idle engine — drain() "
                             "in-flight requests first (decode KV state "
                             "is not checkpointable; queued, active, and "
                             "preempted-awaiting-resume requests all count "
                             "as in-flight)")
        srv = self.server
        payload = {
            "format": CHECKPOINT_FORMAT,
            "family": srv.router.name,
            "router_state": srv.router.state,
            "router_version": int(srv.router_version),
            "key": self._key,
            "chunks": int(self._chunks),
            "syncs": int(self._syncs),
            "seen_at_sync": [[int(c), int(v)]
                             for c, v in self._seen_at_sync.items()],
            "fresh_at_sync": [[int(c), int(v)]
                              for c, v in self._fresh_at_sync.items()],
            "history": self.history,
            "harvest": srv.harvest.state(),
            "pending": [[int(rid), int(c), x, int(m), float(co)]
                        for rid, (c, x, m, co)
                        in srv._pending_evals.items()],
            "next_rid": int(srv.engine._next_rid),
        }
        ckpt.save(path, payload)

    def restore(self, path) -> "FedLoop":
        """Load a ``save()`` checkpoint into this (freshly constructed,
        structurally identical) loop: same pool, same router family/config,
        same harvest d_emb/capacity. Returns self. The restored loop's
        subsequent routes, syncs, and history are bit-identical to the
        uninterrupted run's."""
        blob = ckpt.restore(path)
        fmt = blob.get("format")
        if fmt != CHECKPOINT_FORMAT:
            raise ValueError(f"unsupported FedLoop checkpoint format {fmt} "
                             f"(this build reads {CHECKPOINT_FORMAT})")
        srv = self.server
        if blob["family"] != srv.router.name:
            raise ValueError(
                f"checkpoint holds a {blob['family']!r} router, this loop "
                f"serves {srv.router.name!r} — construct the server with "
                "the matching family")
        if srv.engine.busy:
            raise ValueError("restore() into a server with in-flight "
                             "requests — use a freshly built server")
        srv.router = srv.router.with_state(blob["router_state"])
        # keep the cached route jit: with_state rebuilds by class + rcfg,
        # identical for the same family (mirrors swap_router_state)
        srv._route_fn_router = srv.router
        srv.router_version = int(blob["router_version"])
        srv.harvest.load_state(blob["harvest"])
        srv._pending_evals = {
            int(rid): (int(c), np.asarray(x, np.float32), int(m), float(co))
            for rid, c, x, m, co in blob["pending"]}
        srv.engine._next_rid = int(blob["next_rid"])
        self._key = blob["key"]
        self._chunks = int(blob["chunks"])
        self._syncs = int(blob["syncs"])
        self._seen_at_sync = {int(c): int(v)
                              for c, v in blob["seen_at_sync"]}
        self._fresh_at_sync = {int(c): int(v)
                               for c, v in blob["fresh_at_sync"]}
        self.history = [dict(h) for h in blob["history"]]
        return self

    def onboard_model(self, pm, calib: dict, *, key,
                      steps: int = 100) -> None:
        """Mid-run pool expansion (§6.3): train the new model's head
        column on the calibration evals, then install model + expanded
        router. One model per call — ``server.add_model`` admits exactly
        one PoolModel."""
        router = self.server.router.onboard_model(
            calib, key=key, fcfg=self.fcfg, n_new=1, steps=steps)
        self.server.add_model(pm, router)


def personalize_client(fed_router, local_router, data_i: dict):
    """§6.4 composed with the loop: mix the FedLoop-produced global router
    with a client's locally fitted router, weighted per model by
    calibration errors on the client's own harvested samples
    (``EvalBuffer.as_client_data()``). Returns (predict_fn, (w_acc,
    w_cost)) exactly like ``core.personalization.make_personalized``."""
    from repro.core import personalization as P
    return P.make_personalized(fed_router.predict, local_router.predict,
                               data_i, fed_router.num_models)
