"""Federated MLP-Router training — paper Algorithm 1 (+ Appendix C.1).

Clients are simulated as a stacked, padded pytree so one ``vmap`` runs every
client's local epoch in parallel; on a multi-device mesh the same round is
``shard_map``-ped over a 1-D ``"clients"`` axis
(``fedavg_round_sharded``): each device trains its own block of the
stacked slab, cohort slabs are exchanged with masked ``psum``s, and the
updates return to the (replicated) server aggregation through a sorted
``all_gather`` — so every ``Aggregator`` strategy runs verbatim on the
full global-order stack and the sharded fit matches the in-process one on
a fixed key within ``MESH_PARAM_ATOL`` / ``MESH_LOSS_RTOL`` (the mesh
parity contract below). ``fedavg(mesh=...)`` selects it.

Client dataset layout (N clients, padded to D_max rows):
  {"x": (N, D, d_emb), "m": (N, D) int32, "acc": (N, D), "cost": (N, D),
   "w": (N, D) ∈ {0,1} valid-row mask}
"""
from __future__ import annotations

import collections
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.config import FedConfig, RouterConfig
from repro.core import mlp_router as R
from repro.sharding import shard_map
from repro.train.optim import SGD, AdamW

# Appended at *trace* time from inside ``fedavg_round`` — one entry per
# compile, none per execution. Mirrors ``serve.engine.TRACE_LOG`` (layering
# keeps core/ from importing serve/, so the fit path gets its own log).
# Tests pin that cohort-sampled fits never retrace across rounds/syncs.
FIT_TRACE_LOG = collections.deque(maxlen=4096)

#: Mesh parity contract: the mesh fit against the in-process fit on the
#: same key and client stack. Sampling, the gathered update stack and the
#: aggregation run the in-process code verbatim, but the compiler lowers
#: the per-device batch of N / n_dev client updates, and the reduction of
#: the per-round loss diagnostic, in an order of its own. So params agree
#: within MESH_PARAM_ATOL (absolute) and the per-round loss within
#: MESH_LOSS_RTOL (relative: a few float32 ulps). On the CPU backend the
#: params come out bit-for-bit whenever each device trains >= 2 clients.
MESH_PARAM_ATOL = 1e-5
MESH_LOSS_RTOL = 1e-6


def mesh_fit_gap(ref_params, ref_loss, params, loss) -> tuple[float, float]:
    """(largest absolute param difference, largest relative per-round loss
    difference) between two fits — what the mesh parity contract bounds."""
    dp = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
             for a, b in zip(jax.tree.leaves(ref_params),
                             jax.tree.leaves(params)))
    r, g = np.asarray(ref_loss, np.float64), np.asarray(loss, np.float64)
    dl = float(np.max(np.abs(r - g) / np.maximum(np.abs(r), 1e-30)))
    return dp, dl


def reset_fit_trace_log() -> None:
    FIT_TRACE_LOG.clear()


def dataset_sizes(data) -> jnp.ndarray:
    return jnp.sum(data["w"], axis=-1)  # (N,)


def _make_opt(fcfg: FedConfig, optimizer: str):
    if optimizer == "adamw":
        return AdamW(lr=fcfg.lr, weight_decay=fcfg.weight_decay,
                     clip_norm=fcfg.clip_norm)
    if optimizer == "sgd":
        return SGD(lr=fcfg.lr, clip_norm=None)
    raise ValueError(optimizer)


def _distill_loss(params, theta0, x, w, apply_fn=None):
    """App. D.3 regularizer: match the frozen base router's predictions.
    ``apply_fn(params, x) -> (A, C)`` selects the family's forward pass
    (default: the MLP router)."""
    apply_fn = apply_fn if apply_fn is not None else R.apply_mlp_router
    A, C = apply_fn(params, x)
    A0, C0 = apply_fn(theta0, x)
    per = jnp.mean((A - A0) ** 2 + (C - C0) ** 2, axis=-1)  # mean over models
    return jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1.0)


def client_update(params, data_i, key, rcfg: RouterConfig, fcfg: FedConfig,
                  opt, max_steps: int, *, full_batch: bool = False,
                  freeze=None, distill: Optional[tuple] = None,
                  loss_fn: Optional[Callable] = None):
    """τ local steps (≈1 epoch: ⌈D_i/batch⌉ active steps) on one client.

    ``loss_fn(params, batch, rcfg, rng=...)`` selects the family's training
    loss — None keeps the MLP router loss (bit-for-bit the legacy path),
    so any parametric family rides the same FedAvg machinery.
    ``distill`` is ``(theta0, beta)`` or ``(theta0, beta, apply_fn)``; the
    3-tuple form points the App. D.3 regularizer at a non-MLP forward pass.
    """
    base_loss = loss_fn if loss_fn is not None else R.router_loss
    D_i = jnp.sum(data_i["w"]).astype(jnp.int32)
    n_steps_i = jnp.ceil(D_i / fcfg.batch_size).astype(jnp.int32)
    opt_state = opt.init(params)

    def loss_fn(p, batch, rng):  # noqa: F811 — resolved family loss
        loss = base_loss(p, batch, rcfg, rng=rng)
        if distill is not None:
            theta0, beta = distill[0], distill[1]
            apply_fn = distill[2] if len(distill) > 2 else None
            w = batch.get("w")
            if w is None:  # don't build the all-ones fallback eagerly
                w = jnp.ones(batch["x"].shape[0])
            loss = loss + beta * _distill_loss(p, theta0, batch["x"], w,
                                               apply_fn)
        return loss

    def step(carry, s):
        params, opt_state, key = carry
        key, k_idx, k_drop = jax.random.split(key, 3)
        if full_batch:
            batch = data_i
            rng = None
        else:
            idx = jax.random.randint(k_idx, (fcfg.batch_size,), 0,
                                     jnp.maximum(D_i, 1))
            batch = jax.tree.map(lambda a: jnp.take(a, idx, axis=0), data_i)
            rng = k_drop
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, rng)
        if freeze is not None:
            grads = jax.tree.map(lambda g, f: g * f, grads, freeze)
        new_params, new_opt = opt.update(grads, opt_state, params)
        if freeze is not None:  # gate the whole delta: weight decay too
            new_params = jax.tree.map(
                lambda n, o, f: n * f + o * (1 - f), new_params, params,
                freeze)
        active = s < n_steps_i
        sel = lambda a, b: jax.tree.map(
            lambda u, v: jnp.where(active, u, v), a, b)
        return (sel(new_params, params), sel(new_opt, opt_state), key), loss

    (params, _, _), losses = jax.lax.scan(
        step, (params, opt_state, key), jnp.arange(max_steps))
    return params, jnp.mean(losses)


def _default_aggregator(dp_sigma: float):
    """The pre-refactor behaviour as a strategy object: plain weighted
    FedAvg, wrapped in central-DP noise when dp_sigma > 0 (bit-for-bit the
    old inline branch — same tensordot, same noise keys). Imported lazily:
    repro.fed is the higher layer."""
    from repro.fed.aggregators import FedAvgAggregator, GaussianDPAggregator
    if dp_sigma > 0.0:
        return GaussianDPAggregator(sigma=dp_sigma)
    return FedAvgAggregator()


def fedavg_round(params, data, key, rcfg: RouterConfig, fcfg: FedConfig,
                 opt, max_steps: int, *, full_batch=False, freeze=None,
                 distill=None, client_mask=None, dp_sigma: float = 0.0,
                 aggregator=None, loss_fn=None, cohort: Optional[int] = None,
                 staleness=None):
    """One communication round: local updates on active clients + server
    aggregation (Alg. 1 lines 3–11) through a pluggable strategy
    (``repro.fed.aggregators``). The default is plain weighted FedAvg;
    pass ``aggregator=`` for secure-agg masking or custom strategies.
    dp_sigma > 0 wraps whichever strategy runs in server-side Gaussian
    noise on the aggregate (central-DP flavour of the paper's privacy
    motivation — bit-for-bit the old inline branch on the default path,
    and composing over explicit strategies instead of being dropped).

    ``cohort=C`` samples C of the N stacked clients per round and gathers
    their stacks into a fixed ``(C, ...)`` slab *inside* the traced
    function — shapes stay static, so the scan-fused fit compiles once and
    never retraces across cohorts, and only C local updates run per round
    (the production sampled-participation shape: C ≪ N).
    ``fcfg.participation`` then applies within the cohort.

    ``staleness`` is an optional traced ``(N,)`` vector (rounds since each
    client's last contribution) forwarded to aggregators that declare
    ``needs_staleness`` (buffered-async / FedBuffer-style strategies);
    aggregators declaring ``needs_prev`` additionally receive the round's
    input server params (norm-clipped and delta-based strategies)."""
    N = data["x"].shape[0]
    FIT_TRACE_LOG.append(("fedavg_round", N, cohort,
                          type(aggregator).__name__ if aggregator is not None
                          else "default"))
    if cohort is not None:
        # Static-shape cohort gather: permutation + static slice keeps the
        # compiled round independent of *which* clients were drawn.
        key, k_coh = jax.random.split(key)
        idx = jax.random.permutation(k_coh, N)[:cohort]
        data = jax.tree.map(lambda a: jnp.take(a, idx, axis=0), data)
        if staleness is not None:
            staleness = jnp.take(staleness, idx, axis=0)
        N = cohort
    key, k_sel, k_cli, k_agg = jax.random.split(key, 4)
    n_active = max(1, int(round(fcfg.participation * N)))
    perm = jax.random.permutation(k_sel, N)
    active = jnp.zeros((N,)).at[perm[:n_active]].set(1.0)
    if client_mask is not None:  # restrict the eligible pool (App. D.3)
        active = active * client_mask
        active = jnp.where(jnp.sum(active) > 0, active, client_mask)

    upd = functools.partial(client_update, rcfg=rcfg, fcfg=fcfg, opt=opt,
                            max_steps=max_steps, full_batch=full_batch,
                            freeze=freeze, distill=distill, loss_fn=loss_fn)
    client_params, client_loss = jax.vmap(upd, in_axes=(None, 0, 0))(
        params, data, jax.random.split(k_cli, N))

    wts = dataset_sizes(data) * active
    if aggregator is None:
        agg = _default_aggregator(dp_sigma)
    elif dp_sigma > 0.0:
        # dp composes over any strategy (it is server-side noise on the
        # aggregate) — never silently drop the privacy knob
        from repro.fed.aggregators import GaussianDPAggregator
        agg = GaussianDPAggregator(sigma=dp_sigma, inner=aggregator)
    else:
        agg = aggregator
    # Strategy extras are declared, not positional: plain 3-arg strategies
    # (including any custom callable) keep their exact legacy call.
    extras = {}
    if getattr(agg, "needs_prev", False):
        extras["prev"] = params
    if getattr(agg, "needs_staleness", False):
        extras["staleness"] = (jnp.zeros_like(wts) if staleness is None
                               else staleness.astype(jnp.float32))
    new_params = agg(client_params, wts, k_agg, **extras)
    wn = wts / jnp.maximum(jnp.sum(wts), 1e-12)
    avg_loss = jnp.sum(client_loss * wn)
    return new_params, avg_loss


def pad_client_axis(data, multiple: int, staleness=None):
    """Pad the stacked client axis up to a multiple of ``multiple`` with
    empty clients (all-zero rows, ``w = 0`` — zero aggregation weight, so
    they never move the params). Returns ``(data, staleness)`` — the
    staleness vector, when given, pads with zeros. Used by mesh callers
    whose organic client count doesn't divide the device axis."""
    N = jax.tree.leaves(data)[0].shape[0]
    pad = (-N) % int(multiple)
    if pad == 0:
        return data, staleness
    data = jax.tree.map(
        lambda a: jnp.concatenate(
            [jnp.asarray(a),
             jnp.zeros((pad,) + a.shape[1:], jnp.asarray(a).dtype)]), data)
    if staleness is not None:
        staleness = jnp.concatenate(
            [jnp.asarray(staleness, jnp.float32), jnp.zeros((pad,))])
    return data, staleness


def fedavg_round_sharded(params, data, key, rcfg: RouterConfig,
                         fcfg: FedConfig, opt, max_steps: int, *,
                         mesh: Mesh, full_batch=False, dp_sigma: float = 0.0,
                         aggregator=None, loss_fn=None,
                         cohort: Optional[int] = None, staleness=None):
    """``fedavg_round`` under ``shard_map`` over a 1-D ``"clients"`` mesh:
    the stacked slab stays sharded (N/n_dev clients per device), local
    updates run device-parallel, and the server aggregation is replicated.

    Bit-for-bit contract: every random draw (cohort permutation, active
    mask, client keys, aggregation key) is computed *replicated* with the
    exact key splits of the in-process round, and the client-update stacks
    return to the aggregation through a tiled ``all_gather`` in global
    client order — pure data movement, no arithmetic — so every
    ``Aggregator`` strategy (including the sort-based robust ones and
    secure-agg's pairwise masks) sees exactly the stack the in-process
    path sees and the fit matches it bit-for-bit on a fixed key, for any
    mesh shape.

    ``cohort=C`` gathers the round's C-client slab across devices with a
    masked ``psum`` exchange (each device contributes the cohort rows it
    owns; adding zeros is exact), then splits it C/n_dev per device — the
    compiled round stays independent of which clients were drawn, same as
    in-process. The expensive stage — τ local steps × clients — is what
    parallelizes; aggregation is O(N · |params|) and runs replicated.
    """
    N = jax.tree.leaves(data)[0].shape[0]
    n_dev = mesh.shape["clients"]
    Np = cohort if cohort is not None else N      # clients trained per round
    L = Np // n_dev                               # ... per device
    FIT_TRACE_LOG.append(("fedavg_round_sharded", N, cohort, n_dev,
                          type(aggregator).__name__ if aggregator is not None
                          else "default"))
    upd = functools.partial(client_update, rcfg=rcfg, fcfg=fcfg, opt=opt,
                            max_steps=max_steps, full_batch=full_batch,
                            loss_fn=loss_fn)
    if aggregator is None:
        agg = _default_aggregator(dp_sigma)
    elif dp_sigma > 0.0:
        from repro.fed.aggregators import GaussianDPAggregator
        agg = GaussianDPAggregator(sigma=dp_sigma, inner=aggregator)
    else:
        agg = aggregator
    n_active = max(1, int(round(fcfg.participation * Np)))

    def body(params, data_loc, key, stal):
        d = jax.lax.axis_index("clients")
        if cohort is not None:
            key, k_coh = jax.random.split(key)
            idx = jax.random.permutation(k_coh, N)[:cohort]   # replicated
            lo = d * (N // n_dev)

            def exchange(a):
                # masked-psum cohort exchange: each device contributes the
                # cohort rows it owns; zeros elsewhere add exactly.
                rel = jnp.clip(idx - lo, 0, a.shape[0] - 1)
                own = (idx >= lo) & (idx < lo + a.shape[0])
                g = jnp.take(a, rel, axis=0)
                g = jnp.where(own.reshape((cohort,) + (1,) * (a.ndim - 1)),
                              g, jnp.zeros((), a.dtype))
                return jax.lax.psum(g, "clients")

            slab = jax.tree.map(exchange, data_loc)
            data_loc = jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, d * L, L, 0), slab)
            if stal is not None:
                stal = jnp.take(stal, idx, axis=0)
        key, k_sel, k_cli, k_agg = jax.random.split(key, 4)
        perm = jax.random.permutation(k_sel, Np)
        active = jnp.zeros((Np,)).at[perm[:n_active]].set(1.0)
        keys = jax.random.split(k_cli, Np)
        keys_loc = jax.lax.dynamic_slice_in_dim(keys, d * L, L, 0)
        cp_loc, closs_loc = jax.vmap(upd, in_axes=(None, 0, 0))(
            params, data_loc, keys_loc)
        # sorted gather: updates return to the server in global client
        # order — pure data movement, so the aggregation below is the
        # in-process code running on the in-process stack, verbatim.
        cp = jax.tree.map(
            lambda a: jax.lax.all_gather(a, "clients", axis=0, tiled=True),
            cp_loc)
        closs = jax.lax.all_gather(closs_loc, "clients", axis=0, tiled=True)
        w_loc = jnp.sum(data_loc["w"], axis=-1)
        wts = jax.lax.all_gather(w_loc, "clients", axis=0,
                                 tiled=True) * active
        extras = {}
        if getattr(agg, "needs_prev", False):
            extras["prev"] = params
        if getattr(agg, "needs_staleness", False):
            extras["staleness"] = (jnp.zeros_like(wts) if stal is None
                                   else stal.astype(jnp.float32))
        new_params = agg(cp, wts, k_agg, **extras)
        wn = wts / jnp.maximum(jnp.sum(wts), 1e-12)
        avg_loss = jnp.sum(closs * wn)
        return new_params, avg_loss

    if staleness is None:
        fn = shard_map(lambda p, dt, k: body(p, dt, k, None), mesh,
                       in_specs=(P(), P("clients"), P()),
                       out_specs=(P(), P()))
        return fn(params, data, key)
    fn = shard_map(body, mesh,
                   in_specs=(P(), P("clients"), P(), P()),
                   out_specs=(P(), P()))
    return fn(params, data, key, staleness)


def fedavg(key, data, rcfg: RouterConfig, fcfg: FedConfig, *,
           rounds: Optional[int] = None, optimizer: str = "adamw",
           init=None, full_batch: bool = False, freeze=None, distill=None,
           client_mask=None, dp_sigma: float = 0.0, aggregator=None,
           loss_fn: Optional[Callable] = None, cohort: Optional[int] = None,
           staleness=None, mesh: Optional[Mesh] = None,
           donate_data: bool = False,
           eval_fn: Optional[Callable] = None, eval_every: int = 1):
    """Run T rounds of Algorithm 1. Returns (params, history dict).

    Without ``eval_fn`` the T-round loop is fused into one ``lax.scan`` —
    a single dispatch and one host sync for the whole fit, bit-for-bit
    equal to the per-round loop on the same key. ``eval_fn`` needs params
    on the host, so it falls back to a host loop — per round by default;
    ``eval_every=E > 1`` scans E rounds per eval sync instead (one
    dispatch + one host sync per E rounds — most of the fusion win while
    keeping a round-level loss curve and an every-E eval curve). Params
    and losses stay bit-for-bit identical to the per-round loop; the eval
    list gets one entry per chunk boundary (after rounds E, 2E, ..., T).

    ``aggregator`` selects the server aggregation strategy
    (``repro.fed.aggregators``); None keeps the plain-FedAvg (+ optional
    dp_sigma noise) default. Hashable strategies (the built-in frozen
    dataclasses) ride the module-level compiled-fit caches.

    ``loss_fn`` selects the family's training loss (see ``client_update``);
    module-level functions are hashable, so non-default families ride the
    same compiled-fit caches as the MLP default.

    ``cohort=C`` enables per-round client sampling (see ``fedavg_round``):
    C is part of the compiled-fit cache key, so every cohort draw reuses
    the same compiled scan. ``staleness`` is an optional ``(N,)`` vector
    consumed by aggregators declaring ``needs_staleness``; providing it to
    a strategy that ignores it is an error (silent drops would fake
    async-tolerance).

    ``mesh=Mesh(..., ("clients",))`` runs every round through
    ``fedavg_round_sharded`` — the client slab sharded across devices,
    bit-for-bit the in-process fit on a fixed key (pass the data through
    ``sharding.shard_clients`` to keep the slab distributed end to end).
    The mesh path supports every knob except the pytree-carrying ones
    (freeze/distill/client_mask), which are rejected rather than silently
    replicated. ``donate_data=True`` hands the stacked client slab to the
    fit: once the fit drains, the caller's device buffers are released
    (``is_deleted()`` turns true) instead of living until GC — safe only
    when the caller won't reuse the slab, e.g. a per-sync harvest stack;
    incompatible with ``eval_fn``, whose chunked driver reuses the slab
    across chunks. (A jit donation annotation would be a no-op here: the
    slab is read by every scan round, so XLA can never alias it.)
    """
    rounds = rounds if rounds is not None else fcfg.rounds
    N = data["x"].shape[0]
    if mesh is not None:
        pytree_kw = [n for n, v in (("freeze", freeze), ("distill", distill),
                                    ("client_mask", client_mask))
                     if v is not None]
        if pytree_kw:
            raise ValueError(
                f"the mesh path supports only hashable knobs — "
                f"{', '.join(pytree_kw)} carry pytrees that would pin the "
                "sharded round to one fit; drop mesh= to use the "
                "in-process simulation with those")
        n_dev = mesh.shape["clients"]
        if N % n_dev != 0:
            raise ValueError(
                f"N={N} stacked clients do not divide the {n_dev}-device "
                "clients mesh — pad the stack (pad_client_axis) or resize "
                "the mesh")
        if cohort is not None and cohort < N and cohort % n_dev != 0:
            raise ValueError(
                f"cohort={cohort} does not divide the {n_dev}-device "
                "clients mesh — each device trains cohort/n_dev clients "
                "per round, so pick a multiple")
    if donate_data and eval_fn is not None:
        raise ValueError(
            "donate_data=True with eval_fn: the chunked-eval driver "
            "reuses the client slab across chunks, so it cannot be "
            "donated — drop one of the two")
    if cohort is not None:
        if client_mask is not None:
            raise ValueError(
                "cohort sampling and client_mask are mutually exclusive: "
                "the mask is indexed by the full client axis, the cohort "
                "gather re-indexes it per round")
        cohort = int(cohort)
        if cohort < 1:
            raise ValueError(f"cohort must be >= 1, got {cohort}")
        if cohort >= N:
            cohort = None  # full participation — keep the legacy path
    if staleness is not None:
        # GaussianDP delegates needs_staleness to its inner strategy, so
        # checking the user's aggregator covers the dp_sigma>0 wrap too.
        if not getattr(aggregator, "needs_staleness", False):
            name = type(aggregator).__name__ if aggregator is not None \
                else "default FedAvg"
            raise ValueError(
                f"staleness= was provided but the aggregator ({name}) does "
                "not consume it — use a buffered-async strategy (e.g. "
                "BufferedAsyncAggregator) or drop the argument")
        staleness = jnp.asarray(staleness, jnp.float32)
        if staleness.shape != (N,):
            raise ValueError(
                f"staleness must have shape ({N},) — one entry per stacked "
                f"client — got {staleness.shape}")
    D_max = data["x"].shape[1]
    max_steps = 1 if full_batch else max(
        1, int(np.ceil(D_max / fcfg.batch_size))) * fcfg.local_epochs
    key, k_init = jax.random.split(key)
    params = init if init is not None else R.init_mlp_router(key=k_init,
                                                             cfg=rcfg)
    # Hashable-config fits reuse module-level compiled functions (repeated
    # fits — restarts, sweeps, benchmarks — compile once per config+shape);
    # pytree-carrying knobs (freeze/distill/client_mask) and unhashable
    # custom aggregators build a fresh jit.
    # Keep `simple`/`cfg_key` in sync with _round_partial's signature.
    try:
        hash(aggregator)
        agg_hashable = True
    except TypeError:
        agg_hashable = False
    simple = (freeze is None and distill is None and client_mask is None
              and agg_hashable)
    cfg_key = (rcfg, fcfg, optimizer, max_steps, full_batch, dp_sigma,
               aggregator, loss_fn, cohort, mesh)

    if eval_fn is None:
        if simple:
            fit = _scan_fit_cached(*cfg_key, rounds, init is None)
        else:
            fit = _make_scan_fit(
                _round_partial(*cfg_key, freeze, distill, client_mask),
                rounds, donate=init is None)
        params, _, losses = _call_fit(fit, params, key, data, staleness)
        hist = {"loss": np.asarray(losses).tolist(), "eval": []}
        if donate_data:
            # A jit-level donation annotation can never alias the slab —
            # every scan round reads it, so XLA has no dead window to
            # reuse (the in-process path warns "not usable", shard_map
            # drops the annotation). Honor the contract at the array
            # level instead: np.asarray(losses) above drained the fit,
            # so release the caller's buffers now — not at GC time.
            for a in jax.tree.leaves(data):
                if isinstance(a, jax.Array):
                    a.delete()
        return params, hist

    if eval_every > 1:
        def chunk_fn(E):
            return (_scan_fit_cached(*cfg_key, E, False) if simple
                    else _make_scan_fit(
                _round_partial(*cfg_key, freeze, distill, client_mask),
                E, donate=False))

        return chunked_eval_fit(chunk_fn, params, key, data, rounds,
                                eval_every, eval_fn, staleness=staleness)

    round_jit = (_round_fn_cached(*cfg_key) if simple else
                 jax.jit(_round_partial(*cfg_key, freeze, distill,
                                        client_mask)))
    hist = {"loss": [], "eval": []}
    for t in range(rounds):
        key, k_r = jax.random.split(key)
        if staleness is None:
            params, loss = round_jit(params, data, k_r)
        else:
            params, loss = round_jit(params, data, k_r, staleness=staleness)
        hist["loss"].append(float(loss))
        hist["eval"].append(eval_fn(params))
    return params, hist


def _call_fit(fit, params, key, data, staleness):
    """Invoke a scan fit with/without the optional staleness operand.
    ``staleness is None`` keeps the legacy 3-arg call so fits whose
    ``run`` predates the knob (the sharded mesh path) stay valid."""
    if staleness is None:
        return fit(params, key, data)
    return fit(params, key, data, staleness)


def chunked_eval_fit(chunk_fn, params, key, data, rounds: int,
                     eval_every: int, eval_fn, staleness=None):
    """Drive a fit that scans E rounds between eval syncs: one dispatch +
    one host sync per chunk instead of per round. ``chunk_fn(E)`` returns
    a compiled ``(params, key, data) -> (params, key, losses)`` scan fit
    of E rounds (built at most once per distinct length — E and the
    tail). The scan body splits the key exactly like the per-round loop
    and the carry key threads across chunks, so the trajectory is
    bit-for-bit the per-round loop; history gets every per-round loss and
    one eval entry per chunk boundary. Shared by the in-process and the
    ``shard_map`` mesh paths so their bookkeeping can't diverge. No
    donation: eval_fn may hold onto the params it was handed."""
    hist = {"loss": [], "eval": []}
    chunk_fns = {}
    done = 0
    while done < rounds:
        E = min(eval_every, rounds - done)
        if E not in chunk_fns:
            chunk_fns[E] = chunk_fn(E)
        params, key, losses = _call_fit(chunk_fns[E], params, key, data,
                                        staleness)
        hist["loss"].extend(float(l) for l in np.asarray(losses))
        hist["eval"].append(eval_fn(params))
        done += E
    return params, hist


def _make_scan_fit(round_fn, rounds: int, *, donate: bool = True):
    """Fuse T communication rounds into one ``lax.scan``: per-step key
    handling replicates the per-round loop exactly (split → round), so the
    result is bit-for-bit identical on a fixed key. Params are donated when
    the caller does not hold the initial buffer (fresh init); the client
    slab is deliberately NOT in donate_argnums — every scan round reads
    it, so the annotation can never alias (``fedavg(donate_data=True)``
    releases the caller's buffers after the fit drains instead). Returns
    (params, advanced key, per-round losses) so chunked-eval fits can
    thread the key across chunks. ``staleness`` is an optional extra
    operand; the None default is resolved at trace time, so 3-arg callers
    are bit-for-bit the legacy scan."""
    def run(params, key, data, staleness=None):
        def body(carry, _):
            params, key = carry
            key, k_r = jax.random.split(key)
            if staleness is None:
                params, loss = round_fn(params, data, k_r)
            else:
                params, loss = round_fn(params, data, k_r,
                                        staleness=staleness)
            return (params, key), loss

        (params, key), losses = jax.lax.scan(body, (params, key), None,
                                             length=rounds)
        return params, key, losses

    return jax.jit(run, donate_argnums=(0,) if donate else ())


def _round_partial(rcfg, fcfg, optimizer, max_steps, full_batch, dp_sigma,
                   aggregator, loss_fn=None, cohort=None, mesh=None,
                   freeze=None, distill=None, client_mask=None):
    """The one place a fedavg_round closure is built — every fit path
    (cached or not, in-process or mesh-sharded) goes through it, so a new
    knob can't silently diverge between the variants. ``mesh`` selects the
    ``shard_map`` round; its unsupported pytree knobs were rejected by
    ``fedavg`` before this point."""
    if mesh is not None:
        return functools.partial(
            fedavg_round_sharded, rcfg=rcfg, fcfg=fcfg,
            opt=_make_opt(fcfg, optimizer), max_steps=max_steps, mesh=mesh,
            full_batch=full_batch, dp_sigma=dp_sigma, aggregator=aggregator,
            loss_fn=loss_fn, cohort=cohort)
    return functools.partial(
        fedavg_round, rcfg=rcfg, fcfg=fcfg, opt=_make_opt(fcfg, optimizer),
        max_steps=max_steps, full_batch=full_batch, freeze=freeze,
        distill=distill, client_mask=client_mask, dp_sigma=dp_sigma,
        aggregator=aggregator, loss_fn=loss_fn, cohort=cohort)


@functools.lru_cache(maxsize=64)
def _round_fn_cached(rcfg, fcfg, optimizer, max_steps, full_batch, dp_sigma,
                     aggregator, loss_fn, cohort=None, mesh=None):
    return jax.jit(_round_partial(rcfg, fcfg, optimizer, max_steps,
                                  full_batch, dp_sigma, aggregator, loss_fn,
                                  cohort, mesh))


@functools.lru_cache(maxsize=64)
def _scan_fit_cached(rcfg, fcfg, optimizer, max_steps, full_batch, dp_sigma,
                     aggregator, loss_fn, cohort, mesh, rounds, donate):
    return _make_scan_fit(
        _round_partial(rcfg, fcfg, optimizer, max_steps, full_batch,
                       dp_sigma, aggregator, loss_fn, cohort, mesh),
        rounds, donate=donate)


# ---------------------------------------------------------------------------
# Non-federated baselines (client-local / centralized ERM)
# ---------------------------------------------------------------------------


def sgd_train(key, data_i, rcfg: RouterConfig, fcfg: FedConfig, *,
              steps: int, optimizer: str = "adamw", init=None, freeze=None,
              loss_fn: Optional[Callable] = None):
    """Plain minibatch training on a single (flat) dataset
    {"x": (D,d), "m", "acc", "cost", "w"} — the no-FL baseline.
    ``loss_fn`` selects the family loss (None → MLP, the legacy path)."""
    base_loss = loss_fn if loss_fn is not None else R.router_loss
    opt = _make_opt(fcfg, optimizer)
    key, k_init = jax.random.split(key)
    params = init if init is not None else R.init_mlp_router(key=k_init,
                                                             cfg=rcfg)
    D_i = jnp.sum(data_i["w"]).astype(jnp.int32)
    opt_state = opt.init(params)

    @jax.jit
    def step(carry, _):
        params, opt_state, key = carry
        key, k_idx, k_drop = jax.random.split(key, 3)
        idx = jax.random.randint(k_idx, (fcfg.batch_size,), 0,
                                 jnp.maximum(D_i, 1))
        batch = jax.tree.map(lambda a: jnp.take(a, idx, axis=0), data_i)
        loss, grads = jax.value_and_grad(
            lambda p: base_loss(p, batch, rcfg, rng=k_drop))(params)
        if freeze is not None:
            grads = jax.tree.map(lambda g, f: g * f, grads, freeze)
        new_params, opt_state = opt.update(grads, opt_state, params)
        if freeze is not None:  # gate the whole delta: weight decay too
            new_params = jax.tree.map(
                lambda n, o, f: n * f + o * (1 - f), new_params, params,
                freeze)
        return (new_params, opt_state, key), loss

    (params, _, _), losses = jax.lax.scan(
        step, (params, opt_state, key), None, length=steps)
    return params, losses
