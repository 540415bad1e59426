"""Bring-up check of the routed pool on the TPU, through its normal entry
points.

One chip (the default) drives the main path once:

1. device check — a TPU, and Pallas kernels compiled (not interpreted);
2. a two-lane pool of qwen2-1.5b at its published widths (28 layers,
   d_model 1536, 12/2 heads, d_ff 8960, vocab 151936, bf16) with random
   weights: lane 0 from ``--seed``, lane 1 from ``--seed + 1`` (the same
   config, so both lanes share every compiled program; 2 x 3.6 GB of
   weights beside a few MB of paged KV);
3. both router families fitted through ``routers.fit_federated`` at
   ``RouterConfig`` defaults (d_emb 768, hidden (512, 512)) on a seeded
   synthetic slab of the paper's 10 clients x 1024 evaluations: the MLP
   family's scan-fused FedAvg and the K-means family's one-shot protocol
   (``kmeans_assign_reduce`` / ``kmeans_assign``), each kernel checked
   against its jnp reference;
4. traffic through ``RoutedServer(harvest=...)`` and ``FedLoop``: mixed
   prompt lengths (1 to 4 KV pages) over several clients and lambda
   values, ``step()`` until idle, ``report_outcome`` per request, one
   ``FedLoop.sync()`` that hot-swaps the router, then a second batch; a
   K-means-routed server and the per-call grouped scan (the uniform
   decode kernel) serve a few more;
5. checks: every request DONE with its token count, the router version
   advanced, no decode or route retrace after warm-up, and for a few
   requests a teacher-forced ``forward`` over prompt + generated tokens
   agrees with the engine's greedy tokens. A token agrees when its
   teacher-forced logit is within TIE_TOL of the row's top logit: bf16
   logits of a 151936-word vocabulary hold near-ties a few bf16 ulps
   apart, and the engine's decode kernels and the forward's chunked
   attention round differently.

``--chips 4`` runs only what exists across chips, each against its
one-device run: the federated fit on ``client_mesh(4)`` (held to the mesh
parity contract of ``repro.core.federated``) and the engine with its KV
pool sharded over ``data_mesh(4)`` (tokens held to the solo engine's).

  python chip_smoke.py [--seed 0] [--chips 4]

Compile seconds, wall seconds and token counts go to earlier lines; the
last line of stdout is ``{"ok": true, "device": {...}}``. Any failed check
exits non-zero before it. Without a TPU, with ``REPRO_KERNELS=ref``, or
without the repository's ``src/`` beside it, the script exits non-zero at
once and prints no result.
"""
from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

N_CLIENTS = 10          #: the paper's client count
ROWS = 1024             #: evaluations per client in the fit slab
COSTS = (1.0, 0.25)     #: cost per token of lane 0 / lane 1
LAMS = (0.0, 0.5, 2.0)
TIE_TOL = 0.125         #: logit gap within which two tokens are a near-tie
ROUTE_TOL = 2e-2        #: utility gap within which two models tie
MIN_EXACT = 0.99        #: share of route decisions equal to the reference's
FIT_ROUNDS = 8
PAGE = 16


class Failed(Exception):
    """A check of this run did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Failed(what)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, summed from its
    own monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


def device_info() -> dict:
    """The chip JAX reports, or Failed when there is none — and when the
    Pallas kernels would not run compiled on it."""
    import jax
    from repro.kernels import ops
    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"no TPU chip: JAX's first device is {dev.platform!r} "
          f"({dev.device_kind}); this check runs only on a TPU")
    impl = ops._default_impl()
    check(impl == "pallas",
          f"kernel implementation resolves to {impl!r} (REPRO_KERNELS="
          "ref?) — the chip path must run the Pallas kernels")
    check(not ops._interpret(), "Pallas kernels would run interpreted")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


# ---------------------------------------------------------------------------
# inputs made from the seed
# ---------------------------------------------------------------------------


def make_pool(cfg, seed: int):
    import jax
    from repro.models import init_params
    from repro.serve.gateway import PoolModel
    init = jax.jit(init_params, static_argnums=1)
    pool = []
    for i, cost in enumerate(COSTS):
        params = jax.block_until_ready(init(jax.random.PRNGKey(seed + i), cfg))
        pool.append(PoolModel(f"{cfg.name}/seed{seed + i}", cfg, params,
                              cost))
    return pool


def make_slab(seed: int, d_emb: int, rows: int = ROWS,
              n_clients: int = N_CLIENTS) -> dict:
    """Stacked federated evaluations: lane 0 is accurate everywhere, lane 1
    only on half of the embedding space, so routing depends on the query
    and on lambda."""
    import numpy as np
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n_clients, rows, d_emb), dtype=np.float32)
    m = rng.integers(0, len(COSTS), (n_clients, rows)).astype(np.int32)
    p = np.where(m == 0, 0.85, np.where(x[..., 0] > 0, 0.3, 0.8))
    return {"x": x, "m": m,
            "acc": (rng.random((n_clients, rows)) < p).astype(np.float32),
            "cost": np.asarray(COSTS, np.float32)[m],
            "w": np.ones((n_clients, rows), np.float32)}


def make_requests(rng, n: int):
    """(prompt, client, lambda, max_new): 12/16-word prompts fill one
    16-position page, 40/64-word prompts three or four."""
    words = [f"tok{i}" for i in range(997)]
    out = []
    for _ in range(n):
        k = int(rng.choice([12, 16, 40, 64]))
        out.append((" ".join(rng.choice(words, k)),
                    int(rng.integers(N_CLIENTS)), float(rng.choice(LAMS)),
                    int(rng.choice([8, 16]))))
    return out


# ---------------------------------------------------------------------------
# checks against references
# ---------------------------------------------------------------------------


def check_routers(mlp, km, slab) -> None:
    """Both families' Pallas route paths against their jnp references
    (float32 at the highest matmul precision) on client 0's queries: at
    least MIN_EXACT of the choices equal the reference's, and every other
    one is a near-tie — within ROUTE_TOL of the best utility, or for a
    centroid within one bf16-pass dot product's rounding bound
    (2^-7 |x| max|mu|) of the nearest."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    x = jnp.asarray(slab["x"][0])
    w = jnp.asarray(slab["w"][0])
    for lam in LAMS:
        got = np.asarray(mlp.route(x, lam))
        with jax.default_matmul_precision("highest"):
            A, C = mlp.predict(x)
        U = np.asarray(A - lam * C)
        gap = U.max(1) - U[np.arange(len(got)), got]
        share = np.bincount(got, minlength=len(COSTS)).tolist()
        log(f"route mlp lam={lam}: {np.mean(gap == 0):.4f} exact, "
            f"max gap {gap.max():.3g}, lane share {share}")
        check(gap.max() <= ROUTE_TOL and np.mean(gap == 0) >= MIN_EXACT,
              f"mlp route at lam={lam} picks a model {gap.max():.3g} "
              "below the best utility")
    cents = km.state["centroids"]
    with jax.default_matmul_precision("highest"):
        d = np.asarray(jnp.sum(cents ** 2, -1)[None] - 2 * x @ cents.T)
    bound = 2.0 ** -7 * (np.linalg.norm(slab["x"][0], axis=1)
                         * float(jnp.max(jnp.linalg.norm(cents, axis=1))))
    fused = ops.kmeans_assign_reduce(x, cents, w)
    for name, assign in (("kmeans_assign", ops.kmeans_assign(x, cents)),
                         ("kmeans_assign_reduce", fused[0])):
        a = np.asarray(assign)
        gap = d[np.arange(len(a)), a] - d.min(1)
        log(f"{name}: {np.mean(gap == 0):.4f} exact, max gap {gap.max():.3g}")
        check(bool(np.all(gap <= bound)) and np.mean(gap == 0) >= MIN_EXACT,
              f"{name} assigns a centroid {gap.max():.3g} farther than the "
              "nearest")
    a, sums, cnts = fused
    with jax.default_matmul_precision("highest"):
        oh = jax.nn.one_hot(a, cents.shape[0]) * w[:, None]
        want_s, want_c = oh.T @ x, oh.sum(0)
    err = float(jnp.max(jnp.abs(sums - want_s)) /
                (1 + jnp.max(jnp.abs(want_s))))
    log(f"kmeans_assign_reduce sums rel err {err:.3g}")
    check(np.array_equal(np.asarray(cnts), np.asarray(want_c)),
          "kmeans_assign_reduce counts differ from the one-hot reference")
    # one bf16 rounding of x (2^-8 relative) bounds a bf16-pass matmul
    check(err <= 2.0 ** -8, f"kmeans_assign_reduce sums off by {err:.3g}")


def teacher_forced_gaps(pm, prompts_toks, generated) -> list:
    """For each (prompt tokens, engine tokens): per generated position, the
    gap between the top teacher-forced logit and the engine token's logit
    (0 where the engine took the argmax). One padded forward call."""
    import jax.numpy as jnp
    import numpy as np
    L = max(len(p) + len(g) for p, g in zip(prompts_toks, generated))
    L = -(-L // 64) * 64
    toks = np.zeros((len(generated), L), np.int32)
    for r, (p, g) in enumerate(zip(prompts_toks, generated)):
        seq = np.concatenate([p, g[:-1]])
        toks[r, :len(seq)] = seq
    fwd = _forward_fn(pm.cfg)
    logits = fwd(pm.params, jnp.asarray(toks))
    out = []
    for r, (p, g) in enumerate(zip(prompts_toks, generated)):
        S = len(p)
        row = np.asarray(logits[r, S - 1:S - 1 + len(g)], np.float32)
        out.append(row.max(1) - row[np.arange(len(g)), np.asarray(g)])
    return out


@functools.lru_cache(maxsize=None)
def _forward_fn(cfg):
    """Jitted full-sequence logits, one per model config."""
    import jax
    from repro.models import model as mdl
    return jax.jit(lambda params, toks: mdl.forward(
        params, cfg, tokens=toks, q_chunk=64)[0])


def check_teacher_forced(label, pool, cases) -> None:
    """cases: (lane, prompt tokens, engine tokens)."""
    import numpy as np
    for lane in sorted({c[0] for c in cases}):
        sel = [c for c in cases if c[0] == lane]
        gaps = teacher_forced_gaps(pool[lane], [c[1] for c in sel],
                                   [c[2] for c in sel])
        allg = np.concatenate(gaps)
        log(f"teacher-forced {label} lane {lane}: {len(sel)} requests, "
            f"{allg.size} tokens, {np.mean(allg == 0):.4f} argmax, "
            f"max gap {allg.max():.4g} (tolerance {TIE_TOL})")
        check(allg.max() <= TIE_TOL,
              f"{label}: lane {lane} emitted a token {allg.max():.4g} below "
              "the teacher-forced top logit")


# ---------------------------------------------------------------------------
# the main path on one chip
# ---------------------------------------------------------------------------


def serve_batch(srv, loop, reqs):
    """Submit, step the FedLoop until idle, report outcomes. Returns
    [(rid, lane, prompt tokens, max_new, result)]."""
    import numpy as np
    from repro.serve.engine import DONE
    subs = []
    for prompt, client, lam, max_new in reqs:
        rid = srv.submit(prompt, lam=lam, max_new_tokens=max_new,
                         client_id=client)
        lane = srv.routed_model(rid)
        toks = srv._tokenize([prompt], srv.pool[lane].cfg, None)[0]
        subs.append((rid, lane, toks, max_new))
    results = {}
    while srv.engine.busy:
        results.update(loop.step())
    results.update(srv.drain())
    out = []
    for rid, lane, toks, max_new in subs:
        res = results.get(rid)
        check(srv.status(rid) == DONE and isinstance(res, np.ndarray),
              f"request {rid} ended {srv.status(rid)}, not DONE")
        check(res.shape == (max_new,), f"request {rid}: {res.shape[0]} "
              f"tokens, expected {max_new}")
        vocab = srv.pool[lane].cfg.vocab
        check(bool(np.all((res >= 0) & (res < vocab))),
              f"request {rid}: token outside the vocabulary")
        srv.report_outcome(rid, float(lane == 0 or max_new == 8),
                           cost=COSTS[lane])
        out.append((rid, lane, toks, max_new, res))
    return out


def decode_traces():
    from repro.serve.engine import TRACE_LOG
    return [t for t in TRACE_LOG
            if t[0] in ("engine_chunk_paged", "engine_chunk", "route")]


def run_one_chip(cfg, *, seed: int, rows: int = ROWS, n_req: int = 24):
    """The main path, driven once. Returns the token counts served."""
    import jax
    import numpy as np
    from repro import routers
    from repro.config import FedConfig, RouterConfig
    from repro.fed.harvest import HarvestStore
    from repro.fed.loop import FedLoop, FedLoopConfig
    from repro.serve.engine import EngineConfig
    from repro.serve.gateway import RoutedServer

    t0 = time.perf_counter()
    pool = make_pool(cfg, seed)
    log(f"pool: 2 lanes of {cfg.name} ({cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, vocab {cfg.vocab}, {cfg.dtype}) in "
        f"{time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    rcfg = RouterConfig(num_models=len(pool))
    fcfg = FedConfig(num_clients=N_CLIENTS, rounds=FIT_ROUNDS)
    slab = make_slab(seed, rcfg.d_emb, rows)
    mlp, hist = routers.fit_federated(
        routers.make("mlp", rcfg).init(jax.random.PRNGKey(seed)), slab,
        fcfg, key=jax.random.PRNGKey(seed + 1))
    loss = np.asarray(hist["loss"])
    log(f"fit mlp: {N_CLIENTS} clients x {rows} rows, loss "
        f"{loss[0]:.4f} -> {loss[-1]:.4f}")
    check(bool(np.all(np.isfinite(loss))) and loss[-1] < loss[0],
          f"mlp fit loss did not fall: {loss.tolist()}")
    km, _ = routers.fit_federated(routers.make("kmeans", rcfg), slab, fcfg,
                                  key=jax.random.PRNGKey(seed + 2))
    n = np.asarray(km.state["n"])
    log(f"fit kmeans: {km.state['centroids'].shape[0]} global centroids, "
        f"{int(n.sum())} evaluations counted")
    check(int(n.sum()) == int(slab["w"].sum()),
          "kmeans statistics lost evaluations")
    check(bool(np.all(np.isfinite(np.asarray(km.state["centroids"])))),
          "kmeans centroids are not finite")
    check_routers(mlp, km, slab)
    log(f"routers fitted and checked in {time.perf_counter() - t0:.1f}s")

    ecfg = EngineConfig(slots=8, max_seq=256, chunk=8, page_size=PAGE)
    harvest = HarvestStore(rcfg.d_emb, capacity=rows,
                           clients=range(N_CLIENTS))
    srv = RoutedServer(pool, mlp, harvest=harvest, engine_cfg=ecfg)
    loop = FedLoop(srv, fcfg, key=jax.random.PRNGKey(seed + 3),
                   cfg=FedLoopConfig(sync_every=10 ** 9, min_samples=1))
    rng = np.random.default_rng(seed)

    t0 = time.perf_counter()
    first = serve_batch(srv, loop, make_requests(rng, n_req))
    wall1 = time.perf_counter() - t0
    warm = len(decode_traces())
    v0 = srv.router_version
    t0 = time.perf_counter()
    hist = loop.sync()
    log(f"FedLoop.sync over {len(harvest)} harvested evaluations in "
        f"{time.perf_counter() - t0:.1f}s, loss {hist['loss'][-1]:.4f}")
    check(srv.router_version == v0 + 1,
          f"router_version {v0} -> {srv.router_version} after one sync")
    t0 = time.perf_counter()
    second = serve_batch(srv, loop, make_requests(rng, n_req))
    wall2 = time.perf_counter() - t0
    check(len(decode_traces()) == warm,
          f"decode/route retraced after warm-up: {decode_traces()[warm:]}")
    lanes = np.bincount([r[1] for r in first + second], minlength=2)
    ntok = sum(r[3] for r in first + second)
    log(f"served {len(first)} + {len(second)} requests ({ntok} tokens, "
        f"lanes {lanes.tolist()}): warm-up batch {wall1:.1f}s, "
        f"post-sync batch {wall2:.1f}s")

    picks = []
    for lane in (0, 1):
        picks += [r for r in first + second if r[1] == lane][:3]
    check_teacher_forced("engine", pool,
                         [(r[1], r[2], r[4]) for r in picks])

    # the K-means family serving, and the per-call grouped scan (uniform
    # decode kernel) against the same teacher-forced reference
    srv_km = RoutedServer(pool, km, engine_cfg=ecfg)
    reqs = make_requests(rng, 4)
    rids = [srv_km.submit(p, lam=lam, max_new_tokens=mn)
            for p, _, lam, mn in reqs]
    done = srv_km.drain()
    for rid, (_, _, _, mn) in zip(rids, reqs):
        check(isinstance(done.get(rid), np.ndarray)
              and done[rid].shape == (mn,),
              f"kmeans-routed request {rid} did not complete")
    scan = []
    for p, _, _, _ in make_requests(rng, 2):
        out = srv.generate([p], lam=0.0, max_new_tokens=8, engine=False)
        lane = out["routing"][0]
        scan.append((lane, srv._tokenize([p], pool[lane].cfg, None)[0],
                     np.asarray(out["results"][0]["tokens"], np.int32)))
    check_teacher_forced("grouped scan", pool, scan)
    return ntok + sum(r[3] for r in reqs) + 16


# ---------------------------------------------------------------------------
# the paths across four chips
# ---------------------------------------------------------------------------


def run_four_chips(cfg, *, seed: int, rows: int = ROWS):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import repro.sharding as shd
    from repro import routers
    from repro.config import FedConfig, RouterConfig
    from repro.core import federated as F
    from repro.serve.engine import EngineConfig
    from repro.serve.gateway import RoutedServer

    n_dev = len(jax.devices())
    check(n_dev == 4, f"--chips 4 needs 4 devices, JAX sees {n_dev}")

    # federated fit: client_mesh(4) against one device, same key and stack
    rcfg = RouterConfig(num_models=len(COSTS))
    fcfg = FedConfig(num_clients=N_CLIENTS, rounds=FIT_ROUNDS)
    data, _ = F.pad_client_axis(make_slab(seed, rcfg.d_emb, rows), n_dev)
    r0 = routers.make("mlp", rcfg).init(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)
    t0 = time.perf_counter()
    ref, ref_h = routers.fit_federated(r0, jax.tree.map(jnp.asarray, data),
                                       fcfg, key=key)
    mesh = shd.client_mesh(n_dev)
    got, got_h = routers.fit_federated(r0, shd.shard_clients(data, mesh),
                                       fcfg, key=key, mesh=mesh)
    dp, dl = F.mesh_fit_gap(ref.state, ref_h["loss"], got.state,
                            got_h["loss"])
    log(f"mesh fit: {data['x'].shape[0]} clients x {rows} rows on "
        f"client_mesh({n_dev}) vs one device: max |param diff| {dp:.3g} "
        f"(contract {F.MESH_PARAM_ATOL}), max rel loss diff {dl:.3g} "
        f"(contract {F.MESH_LOSS_RTOL}), {time.perf_counter() - t0:.1f}s")
    check(dp <= F.MESH_PARAM_ATOL and dl <= F.MESH_LOSS_RTOL,
          "mesh fit breaks the parity contract")

    # engine: KV pool sharded slot-parallel over data_mesh(4) vs solo
    pool = make_pool(cfg, seed)[:1]
    router = routers.make(
        "kmeans", RouterConfig(num_models=1),
        state={"centroids": jnp.zeros((1, 768)), "A": jnp.ones((1, 1)),
               "C": jnp.zeros((1, 1)), "n": jnp.ones((1, 1))})
    ecfg = EngineConfig(slots=8, max_seq=256, chunk=8, page_size=PAGE)
    reqs = make_requests(np.random.default_rng(seed), 12)

    def serve(mesh):
        srv = RoutedServer(pool, router, engine_cfg=ecfg, mesh=mesh)
        rids = [srv.submit(p, lam=0.0, max_new_tokens=mn)
                for p, _, _, mn in reqs]
        done = srv.drain()
        return srv, [np.asarray(done[r]) for r in rids]

    t0 = time.perf_counter()
    srv, solo = serve(None)
    _, sharded = serve(shd.data_mesh(n_dev))
    same = sum(np.array_equal(a, b) for a, b in zip(solo, sharded))
    ntok = sum(len(a) for a in solo)
    log(f"mesh engine: {same}/{len(reqs)} requests token-identical to the "
        f"solo engine ({ntok} tokens each), {time.perf_counter() - t0:.1f}s")
    # where a sharded stream parts from the solo one, it must part at a
    # near-tie of the teacher-forced logits over the shared prefix
    cases = []
    for (p, _, _, _), a, b in zip(reqs, solo, sharded):
        if not np.array_equal(a, b):
            j = int(np.argmax(a != b))
            cases.append((0, srv._tokenize([p], cfg, None)[0],
                          np.concatenate([a[:j], b[j:j + 1]])))
    if cases:
        check_teacher_forced("sharded engine divergence", pool, cases)
    return ntok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    try:
        from repro import compile_cache
        from repro.configs import get_config
    except ImportError as e:
        print(f"chip_smoke: FAIL: the repository's src/ is not beside this "
              f"script ({e})", file=sys.stderr)
        return 2
    clock = CompileClock()
    t_start = time.perf_counter()
    try:
        dev = device_info()
        log(f"device: {dev}; compile cache {compile_cache.enable()}")
        cfg = get_config("qwen2-1.5b")
        run = run_four_chips if args.chips == 4 else run_one_chip
        ntok = run(cfg, seed=args.seed)
    except Failed as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    log(f"compile seconds {clock.seconds:.1f}, wall seconds "
        f"{time.perf_counter() - t_start:.1f}, tokens served {ntok}")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
