"""Hot-path perf suite → BENCH_{train,route,serve,engine}.json.

Measures the wall-clock consumers this repo optimizes — federated
training rounds, the K-means routing math, the serving gateway, and the
continuous-batching engine under Poisson traffic — each against its
pre-fusion baseline, with warmup-then-measure methodology and
``block_until_ready``-correct timers (see benchmarks/common.timeit).

  PYTHONPATH=src python -m benchmarks.perf_suite            # full run
  PYTHONPATH=src python -m benchmarks.perf_suite --smoke    # CI: tiny +
                                                            # JSON validity

``--smoke`` shrinks every workload so the suite finishes in minutes. CI
asserts the JSON files are produced and well-formed; absolute CPU CI
timing is too noisy for thresholds, so the one *relative* floor enforced
is that the engine's traffic throughput never drops below the
per-request gateway path on the same trace (BENCH_engine.smoke.json
speedup >= 1).
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common as C
from repro import routers
from repro.config import FedConfig, RouterConfig
from repro.core import federated as F
from repro.data.partition import federated_split
from repro.data.synthetic import make_eval_corpus
from repro.kernels import ops as kops


def _bench_file(section: str, smoke: bool) -> str:
    """Smoke runs write *.smoke.json so they can never clobber the
    git-tracked full-run trajectory files."""
    return f"BENCH_{section}{'.smoke' if smoke else ''}.json"


# ---------------------------------------------------------------------------
# train: scan-fused FedAvg vs the per-round loop
# ---------------------------------------------------------------------------


def bench_train(smoke: bool) -> None:
    import functools

    from repro.core import mlp_router as R

    rounds = 5 if smoke else 30
    rcfg = RouterConfig(d_emb=16, num_models=8, hidden=(32, 32))
    fcfg = FedConfig(num_clients=8, batch_size=128, rounds=rounds)
    corpus = make_eval_corpus(jax.random.PRNGKey(0),
                              n_queries=200 if smoke else 400,
                              n_tasks=4, n_models=8, d_emb=16)
    data = federated_split(jax.random.PRNGKey(1), corpus, fcfg)["train"]
    key = jax.random.PRNGKey(2)
    max_steps = max(1, int(np.ceil(data["x"].shape[1] / fcfg.batch_size))) \
        * fcfg.local_epochs

    def prepr_fit():
        """The pre-scan driver verbatim: a FRESH jit per fit (recompiles
        every call) + one host sync per round."""
        opt = F._make_opt(fcfg, "adamw")
        k, k_init = jax.random.split(key)
        params = R.init_mlp_router(key=k_init, cfg=rcfg)
        round_fn = jax.jit(functools.partial(
            F.fedavg_round, rcfg=rcfg, fcfg=fcfg, opt=opt,
            max_steps=max_steps))
        for _ in range(rounds):
            k, k_r = jax.random.split(k)
            params, loss = round_fn(params, data, k_r)
            float(loss)
        return params

    def loop_fit():  # cached per-round jit, still one dispatch+sync/round
        return F.fedavg(key, data, rcfg, fcfg, eval_fn=lambda p: None)[0]

    def scan_fit():  # the fused path: one dispatch, one sync per fit
        return F.fedavg(key, data, rcfg, fcfg)[0]

    repeats = 2 if smoke else 5
    prepr = C.timeit(prepr_fit, warmup=1, iters=1, repeats=repeats)
    loop = C.timeit(loop_fit, warmup=1, iters=1, repeats=repeats)
    fused = C.timeit(scan_fit, warmup=1, iters=1, repeats=repeats)
    C.emit(f"fedavg_prepr_{rounds}r", prepr,
           "pre-PR driver: jit per fit + sync per round")
    C.emit(f"fedavg_loop_{rounds}r", loop,
           "cached per-round jit + sync per round",
           speedup_vs_baseline=prepr / loop)
    C.emit(f"fedavg_scan_{rounds}r", fused, "lax.scan-fused rounds",
           speedup_vs_baseline=prepr / fused)
    C.emit(f"fedavg_scan_vs_loop_{rounds}r", fused,
           "scan fusion alone (vs cached loop)",
           speedup_vs_baseline=loop / fused)
    C.write_bench(_bench_file("train", smoke), meta={"rounds": rounds,
                                                     "smoke": smoke})


# ---------------------------------------------------------------------------
# route: fused assign-reduce + incremental k-means++ vs their baselines
# ---------------------------------------------------------------------------


def bench_route(smoke: bool) -> None:
    n, d, K = (512, 32, 8) if smoke else (8192, 64, 32)
    kx, kc = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (n, d))
    cents = jax.random.normal(kc, (K, d))
    w = jnp.ones((n,))

    # Lloyd's step, pre-fusion: assign kernel + host-visible one-hot scatter
    @jax.jit
    def lloyd_step_onehot(x, cents, w):
        assign = kops.kmeans_assign(x, cents)
        onehot = jax.nn.one_hot(assign, K, dtype=x.dtype)
        wv = onehot * w[:, None]
        return wv.T @ x, jnp.sum(wv, axis=0)

    @jax.jit
    def lloyd_step_fused(x, cents, w):
        _, sums, cnts = kops.kmeans_assign_reduce(x, cents, w)
        return sums, cnts

    base = C.timeit(lloyd_step_onehot, x, cents, w, repeats=5)
    fused = C.timeit(lloyd_step_fused, x, cents, w, repeats=5)
    C.emit(f"lloyd_step_onehot_{n}x{d}x{K}", base, "assign + one-hot scatter")
    C.emit(f"lloyd_step_fused_{n}x{d}x{K}", fused,
           "fused assign-reduce (on CPU both run the jnp oracle — expect "
           "~1x; the fusion win is the Pallas TPU kernel)",
           speedup_vs_baseline=base / fused)

    # k-means++ seeding: O(n·K·d) broadcast (pre-change) vs incremental
    from repro.core.kmeans import _plusplus_init

    def plusplus_broadcast(key, X, w):  # the replaced implementation
        n = X.shape[0]
        k0, key = jax.random.split(key)
        first = jax.random.choice(k0, n, p=w / jnp.sum(w))
        cents0 = jnp.zeros((K, X.shape[1]), X.dtype).at[0].set(X[first])

        def body(i, carry):
            cents, key = carry
            d2 = jnp.min(
                jnp.sum((X[:, None, :] - cents[None, :, :]) ** 2, -1)
                + jnp.where(jnp.arange(K)[None, :] < i, 0.0, jnp.inf),
                axis=1)
            p = d2 * w
            p = jnp.where(jnp.isfinite(p), p, 0.0)
            p = p / jnp.maximum(jnp.sum(p), 1e-12)
            key, sub = jax.random.split(key)
            nxt = jax.random.choice(sub, n, p=p)
            return cents.at[i].set(X[nxt]), key

        cents, _ = jax.lax.fori_loop(1, K, body, (cents0, key))
        return cents

    k = jax.random.PRNGKey(3)
    base_pp = C.timeit(jax.jit(plusplus_broadcast), k, x, w)
    fast_pp = C.timeit(jax.jit(lambda k, X, w: _plusplus_init(k, X, w, K)),
                       k, x, w)
    C.emit(f"plusplus_broadcast_{n}x{d}x{K}", base_pp, "O(n*K*d) per step")
    C.emit(f"plusplus_incremental_{n}x{d}x{K}", fast_pp,
           "O(n*d) per step", speedup_vs_baseline=base_pp / fast_pp)
    C.write_bench(_bench_file("route", smoke), meta={"n": n, "d": d,
                                                     "K": K, "smoke": smoke})


# ---------------------------------------------------------------------------
# serve: scan-fused decode + cached jit vs the per-token loop
# ---------------------------------------------------------------------------


def bench_serve(smoke: bool) -> None:
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve.gateway import PoolModel, RoutedServer

    cfg = get_config("qwen2-1.5b").reduced()
    pool = [PoolModel("qwen2-1.5b", cfg,
                      init_params(jax.random.PRNGKey(0), cfg), 0.1)]
    router = routers.make(
        "kmeans", RouterConfig(d_emb=64, num_models=1),
        state={"centroids": jnp.zeros((1, 64)),
               "A": jnp.array([[0.9]]), "C": jnp.array([[0.1]]),
               "n": jnp.ones((1, 1))})
    srv = RoutedServer(pool, router)
    prompts = ["write a poem about the sea", "solve this integral now",
               "summarize the meeting notes", "prove the theorem carefully"]
    max_new = 4 if smoke else 32
    iters = 1 if smoke else 5

    base = C.timeit(lambda: srv.generate(prompts, lam=0.5,
                                         max_new_tokens=max_new,
                                         engine=False, scan_decode=False),
                    warmup=1, iters=iters)
    fused = C.timeit(lambda: srv.generate(prompts, lam=0.5,
                                          max_new_tokens=max_new,
                                          engine=False),
                     warmup=1, iters=iters)
    C.emit(f"generate_token_loop_b4_t{max_new}", base,
           "per-token dispatch + host sync")
    C.emit(f"generate_scan_decode_b4_t{max_new}", fused,
           "scan decode, one transfer", speedup_vs_baseline=base / fused)

    route_us = C.timeit(lambda: srv.route(prompts, 0.5), warmup=2,
                        iters=max(iters, 3))
    C.emit("route_batch4", route_us, "encode + cached-jit route")
    C.write_bench(_bench_file("serve", smoke),
                  meta={"model": cfg.name, "max_new": max_new,
                        "smoke": smoke})


# ---------------------------------------------------------------------------
# engine: continuous batching under Poisson traffic vs per-request serving
# ---------------------------------------------------------------------------


_WORDS = ("write solve prove summarize explain draft the a of this that "
          "integral poem theorem meeting notes carefully quickly now "
          "report plan code review data model chart essay story").split()


def _make_traffic(seed: int, n_req: int, rate_per_s: float,
                  longtail: bool = False):
    """Poisson arrivals (Exp inter-arrival at ``rate_per_s``) with a
    per-request routing λ. Default prompt mix is 2–12 words; ``longtail``
    draws the production-shaped mix instead — mostly short prompts with a
    heavy tail of long ones (~15% at 24–56 words), the regime where
    uniform max_seq slot reservation wastes most of the KV pool."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_per_s, n_req))
    reqs = []
    for i in range(n_req):
        if longtail and rng.random() < 0.15:
            n_words = int(rng.integers(24, 57))
        else:
            n_words = int(rng.integers(2, 13))
        prompt = " ".join(rng.choice(_WORDS, n_words))
        lam = float(rng.choice([0.2, 0.5, 2.0]))
        reqs.append({"prompt": prompt, "lam": lam,
                     "arrival": float(arrivals[i])})
    return reqs


def _run_engine_traffic(srv, reqs, max_new):
    """Replay the trace against the engine: submit each request when its
    arrival time passes, step the in-flight batch between admissions.
    Returns (tokens/sec over the busy window, per-request latencies)."""
    import time
    pending = sorted(reqs, key=lambda r: r["arrival"])
    arrival_of, completion = {}, {}
    t0 = time.perf_counter()
    i = 0
    while i < len(pending) or srv.engine.busy:
        now = time.perf_counter() - t0
        while i < len(pending) and pending[i]["arrival"] <= now:
            rid = srv.submit(pending[i]["prompt"], lam=pending[i]["lam"],
                             max_new_tokens=max_new)
            arrival_of[rid] = pending[i]["arrival"]
            i += 1
        if srv.engine.busy:
            for rid, _ in srv.step():
                completion[rid] = time.perf_counter() - t0
        elif i < len(pending):
            time.sleep(min(pending[i]["arrival"] - now, 1e-3))
    makespan = max(completion.values())
    srv.drain()              # clear the engine's buffered results
    lat = np.array([completion[r] - arrival_of[r] for r in completion])
    return len(reqs) * max_new / makespan, lat


def _run_per_request_traffic(srv, reqs, max_new):
    """The same trace served one request at a time on the legacy scan path
    (requests queue behind each other — the pre-engine deployment)."""
    import time
    lat = []
    t0 = time.perf_counter()
    for r in sorted(reqs, key=lambda q: q["arrival"]):
        now = time.perf_counter() - t0
        if r["arrival"] > now:
            time.sleep(r["arrival"] - now)
        srv.generate([r["prompt"]], lam=r["lam"], max_new_tokens=max_new,
                     engine=False)
        lat.append(time.perf_counter() - t0 - r["arrival"])
    makespan = time.perf_counter() - t0
    return len(reqs) * max_new / makespan, np.array(lat)


def bench_engine(smoke: bool) -> None:
    """Traffic simulation: Poisson arrivals into the continuous-batching
    engine vs the same trace served per-request. Reports decode tokens/sec
    and p50/p99 request latency for both; the acceptance bar is ≥2×
    tokens/sec at concurrency ≥ 8 (slots)."""
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve.engine import EngineConfig
    from repro.serve.gateway import PoolModel, RoutedServer

    cfg = get_config("qwen2-1.5b").reduced()
    pool = [PoolModel("qwen2-1.5b", cfg,
                      init_params(jax.random.PRNGKey(0), cfg), 0.1)]
    router = routers.make(
        "kmeans", RouterConfig(d_emb=64, num_models=1),
        state={"centroids": jnp.zeros((1, 64)),
               "A": jnp.array([[0.9]]), "C": jnp.array([[0.1]]),
               "n": jnp.ones((1, 1))})
    n_req, max_new, chunk = (10, 8, 4) if smoke else (24, 32, 8)
    ecfg = EngineConfig(slots=8, max_seq=64, chunk=chunk)
    srv = RoutedServer(pool, router, engine_cfg=ecfg)

    # arrival rate: an (over)saturating Poisson stream so the offered
    # concurrency exceeds the 8 slots and admissions happen mid-flight
    reqs = _make_traffic(0, n_req, rate_per_s=200.0 if smoke else 50.0)

    # warm every (config, bucket) program on both paths, off the clock
    warm = {r["prompt"]: None for r in reqs}
    for p in warm:
        srv.submit(p, lam=0.5, max_new_tokens=max_new)
    srv.drain()
    for p in warm:
        srv.generate([p], lam=0.5, max_new_tokens=max_new, engine=False)

    # best-of-repeats per path: a traffic replay can't run under timeit,
    # so repeat the whole scenario (scheduler-noise resistance, same
    # statistic as benchmarks.common.timeit)
    repeats = 2
    eng_tps, eng_lat = max((_run_engine_traffic(srv, reqs, max_new)
                            for _ in range(repeats)), key=lambda r: r[0])
    base_tps, base_lat = max((_run_per_request_traffic(srv, reqs, max_new)
                              for _ in range(repeats)), key=lambda r: r[0])

    C.emit(f"engine_traffic_{n_req}req_t{max_new}", 1e6 / eng_tps,
           f"continuous batching, {ecfg.slots} slots: us per decoded token "
           f"(= {eng_tps:.0f} tok/s); p50/p99 latency "
           f"{np.percentile(eng_lat, 50) * 1e3:.0f}/"
           f"{np.percentile(eng_lat, 99) * 1e3:.0f} ms",
           speedup_vs_baseline=eng_tps / base_tps)
    C.emit(f"per_request_traffic_{n_req}req_t{max_new}", 1e6 / base_tps,
           f"per-request gateway path (= {base_tps:.0f} tok/s); p50/p99 "
           f"latency {np.percentile(base_lat, 50) * 1e3:.0f}/"
           f"{np.percentile(base_lat, 99) * 1e3:.0f} ms")
    C.write_bench(_bench_file("engine", smoke), meta={
        "model": cfg.name, "n_req": n_req, "max_new": max_new,
        "slots": ecfg.slots, "chunk": chunk, "smoke": smoke,
        "engine_tokens_per_s": round(eng_tps, 1),
        "per_request_tokens_per_s": round(base_tps, 1),
        "speedup": round(eng_tps / base_tps, 3),
        "engine_latency_ms": {
            "p50": round(float(np.percentile(eng_lat, 50)) * 1e3, 1),
            "p99": round(float(np.percentile(eng_lat, 99)) * 1e3, 1)},
        "per_request_latency_ms": {
            "p50": round(float(np.percentile(base_lat, 50)) * 1e3, 1),
            "p99": round(float(np.percentile(base_lat, 99)) * 1e3, 1)},
    })


# ---------------------------------------------------------------------------
# paged: paged pool + coalesced prefill vs the uniform-slot engine at
# (near-)equal KV bytes under long-tail Poisson traffic
# ---------------------------------------------------------------------------


def _run_traffic_instrumented(srv, reqs, max_new):
    """Replay the trace against an engine and also record what the paged
    comparison needs: peak in-flight concurrency (sampled every step) and
    per-request admission latency (engine.admission_lat deltas).
    Returns (tokens/sec, completion latencies, max in-flight, admission
    latencies)."""
    import time
    pending = sorted(reqs, key=lambda r: r["arrival"])
    arrival_of, completion = {}, {}
    adm0 = len(srv.engine.admission_lat)
    srv.engine.peak_active = 0
    t0 = time.perf_counter()
    i = 0
    while i < len(pending) or srv.engine.busy:
        now = time.perf_counter() - t0
        while i < len(pending) and pending[i]["arrival"] <= now:
            rid = srv.submit(pending[i]["prompt"], lam=pending[i]["lam"],
                             max_new_tokens=max_new)
            arrival_of[rid] = pending[i]["arrival"]
            i += 1
        if srv.engine.busy:
            for rid, _ in srv.step():
                completion[rid] = time.perf_counter() - t0
        elif i < len(pending):
            time.sleep(min(pending[i]["arrival"] - now, 1e-3))
    makespan = max(completion.values())
    srv.drain()
    lat = np.array([completion[r] - arrival_of[r] for r in completion])
    adm = np.array(list(srv.engine.admission_lat)[adm0:])
    return (len(reqs) * max_new / makespan, lat, srv.engine.peak_active,
            adm)


def bench_paged(smoke: bool) -> None:
    """Long-tail traffic sim: the paged engine (page-granular reservation,
    coalesced prefill) vs the PR 3 uniform-slot engine holding the SAME KV
    pool bytes — uniform must spend them on worst-case max_seq regions, so
    at equal memory it fields half the decode slots. Acceptance: strictly
    more peak in-flight requests per byte of KV pool, and lower p99
    admission latency under Poisson bursts (the queue drains through twice
    the admission capacity). Every request's tokens stay bit-identical to
    solo serving (property-tested in tests/, not re-asserted here)."""
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve.engine import EngineConfig
    from repro.serve.gateway import PoolModel, RoutedServer

    cfg = get_config("qwen2-1.5b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)

    def mk(ecfg):
        pool = [PoolModel("qwen2-1.5b", cfg, params, 0.1)]
        router = routers.make(
            "kmeans", RouterConfig(d_emb=64, num_models=1),
            state={"centroids": jnp.zeros((1, 64)),
                   "A": jnp.array([[0.9]]), "C": jnp.array([[0.1]]),
                   "n": jnp.ones((1, 1))})
        return RoutedServer(pool, router, engine_cfg=ecfg)

    if smoke:
        n_req, max_new, chunk, max_seq, ps = 12, 4, 4, 64, 16
        paged_cfg = EngineConfig(slots=8, max_seq=max_seq, chunk=chunk,
                                 page_size=ps, pages=16)   # 272 positions
        uni_cfg = EngineConfig(slots=4, max_seq=max_seq, chunk=chunk,
                               page_size=None)             # 256 positions
        # effectively a t=0 burst: every request is queued before the
        # first chunk, so BOTH engines deterministically saturate their
        # admission capacity (peak in-flight = slots) no matter how fast
        # the CI runner decodes — the in-flight-per-byte floor ci.yml
        # enforces is then capacity accounting, not a wall-clock race
        rate = 1e5
    else:
        n_req, max_new, chunk, max_seq, ps = 32, 16, 8, 128, 16
        paged_cfg = EngineConfig(slots=16, max_seq=max_seq, chunk=chunk,
                                 page_size=ps, pages=64)   # 1040 positions
        uni_cfg = EngineConfig(slots=8, max_seq=max_seq, chunk=chunk,
                               page_size=None)             # 1024 positions
        rate = 100.0

    reqs = _make_traffic(0, n_req, rate_per_s=rate, longtail=True)
    srv_p, srv_u = mk(paged_cfg), mk(uni_cfg)

    # warm every (config, bucket) program on both engines, off the clock
    for srv in (srv_p, srv_u):
        for p in {r["prompt"] for r in reqs}:
            srv.submit(p, lam=0.5, max_new_tokens=max_new)
        srv.drain()
    # the paged engine coalesces admissions, so its prefill/write trace
    # set is (B_b, S_b) PAIRS — which grouping the replay produces depends
    # on wall-clock arrival vs chunk boundaries. Warm every reachable pair
    # directly through the cached jit stages (writes target the trash
    # page), so no compile ever lands inside the timed replay.
    from repro.serve import engine as E
    lane = srv_p.engine._lanes[0]
    s_buckets = sorted({E.next_pow2(len(r["prompt"].split()))
                        for r in reqs})
    pf, wf = E._prefill_fn(cfg), E._write_pages_fn(cfg)
    B = 1
    while B <= paged_cfg.slots:
        for S_b in s_buckets:
            n_pp = -(-S_b // ps)
            _, kv = pf(params, jnp.zeros((B, S_b), jnp.int32),
                       jnp.zeros((B,), jnp.int32))
            lane.pool = wf(lane.pool, kv, jnp.zeros((B, n_pp), jnp.int32))
        B *= 2

    repeats = 2
    p_tps, p_lat, p_inf, p_adm = max(
        (_run_traffic_instrumented(srv_p, reqs, max_new)
         for _ in range(repeats)), key=lambda r: r[0])
    u_tps, u_lat, u_inf, u_adm = max(
        (_run_traffic_instrumented(srv_u, reqs, max_new)
         for _ in range(repeats)), key=lambda r: r[0])

    p_bytes, u_bytes = srv_p.engine.kv_pool_bytes(), \
        srv_u.engine.kv_pool_bytes()
    p_per_mb = p_inf / (p_bytes / 2 ** 20)
    u_per_mb = u_inf / (u_bytes / 2 ** 20)

    def _pcts(arr):
        """The JSON latency schema, defined once: {p50, p99} in ms."""
        return {"p50": round(float(np.percentile(arr, 50)) * 1e3, 1),
                "p99": round(float(np.percentile(arr, 99)) * 1e3, 1)}

    C.emit(f"paged_traffic_{n_req}req_t{max_new}", 1e6 / p_tps,
           f"paged pool ({paged_cfg.slots} slots, {paged_cfg.resolved_pages}"
           f" pages of {ps}) + coalesced prefill: us/decoded token "
           f"(= {p_tps:.0f} tok/s); peak in-flight {p_inf} on "
           f"{p_bytes / 2 ** 20:.1f} MB; admission p50/p99 "
           f"{np.percentile(p_adm, 50) * 1e3:.0f}/"
           f"{np.percentile(p_adm, 99) * 1e3:.0f} ms",
           speedup_vs_baseline=p_tps / u_tps)
    C.emit(f"uniform_traffic_{n_req}req_t{max_new}", 1e6 / u_tps,
           f"uniform slots ({uni_cfg.slots} x max_seq={max_seq}) at equal "
           f"KV bytes: us/decoded token (= {u_tps:.0f} tok/s); peak "
           f"in-flight {u_inf} on {u_bytes / 2 ** 20:.1f} MB; admission "
           f"p50/p99 {np.percentile(u_adm, 50) * 1e3:.0f}/"
           f"{np.percentile(u_adm, 99) * 1e3:.0f} ms")
    C.write_bench(_bench_file("paged", smoke), meta={
        "model": cfg.name, "n_req": n_req, "max_new": max_new,
        "smoke": smoke, "page_size": ps,
        "paged": {"slots": paged_cfg.slots,
                  "pages": paged_cfg.resolved_pages,
                  "kv_pool_bytes": int(p_bytes),
                  "tokens_per_s": round(p_tps, 1),
                  "max_inflight": int(p_inf),
                  "inflight_per_mb": round(p_per_mb, 3),
                  "admission_ms": _pcts(p_adm),
                  "latency_ms": _pcts(p_lat)},
        "uniform": {"slots": uni_cfg.slots,
                    "kv_pool_bytes": int(u_bytes),
                    "tokens_per_s": round(u_tps, 1),
                    "max_inflight": int(u_inf),
                    "inflight_per_mb": round(u_per_mb, 3),
                    "admission_ms": _pcts(u_adm),
                    "latency_ms": _pcts(u_lat)},
        "inflight_per_byte_ratio": round(p_per_mb / u_per_mb, 3),
        "admission_p99_ratio": round(
            float(np.percentile(p_adm, 99) / np.percentile(u_adm, 99)), 3),
    })


# ---------------------------------------------------------------------------
# preempt: deadline goodput under pool oversubscription — preemption with
# recompute-on-resume vs admission stalling vs load shedding
# ---------------------------------------------------------------------------


def _deadline_traffic(seed: int, n_req: int, max_new: int, chunk: int,
                      slack: int, scale: float = 1.0, tail: float = 0.3,
                      long_words: tuple = (24, 57)):
    """Long-tail Poisson arrivals on the ENGINE-STEP clock: exponential
    inter-arrival gaps (mean ``scale`` steps) and per-request deadlines of
    slack..2·slack service times. Step-clock arrivals make every run of
    the schedule deterministic — goodput differences between admission
    policies are scheduling accounting, not a wall-clock race CI could
    lose."""
    rng = np.random.default_rng(seed)
    steps = np.floor(np.cumsum(rng.exponential(scale, n_req))).astype(int)
    svc = -(-max_new // chunk)               # solo decode steps
    evs = []
    for i in range(n_req):
        long = rng.random() < tail           # the long tail
        n_words = int(rng.integers(*long_words) if long
                      else rng.integers(2, 13))
        # batch-style long jobs run with loose deadlines; interactive
        # shorts are tight — the regime where latest-deadline-first
        # eviction pays (shorts preempt longs, longs still finish)
        loose = 4 if long else 1
        evs.append({"prompt": " ".join(rng.choice(_WORDS, n_words)),
                    "step": int(steps[i]),
                    "deadline": int(svc * slack * loose
                                    + rng.integers(0, svc * slack))})
    return evs


def _run_deadline_traffic(srv, events, max_new):
    """Replay a step-clock deadline trace: submit each arrival at its
    step, advance one chunk per step, drain, and fold the engine's typed
    terminals into goodput accounting. Deadline-met tokens (requests that
    COMPLETED — the engine kills deadline-missers, so completion implies
    the deadline was met) are deterministic; wall time is informational."""
    import time
    from repro.serve.engine import DONE, PREEMPTED_RESUMED
    ev = sorted(events, key=lambda e: e["step"])
    adm0 = len(srv.engine.admission_lat)
    meta, i, step = {}, 0, 0
    t0 = time.perf_counter()
    while i < len(ev) or srv.engine.busy:
        while i < len(ev) and ev[i]["step"] <= step:
            rid = srv.submit(ev[i]["prompt"], lam=0.5,
                             max_new_tokens=max_new,
                             deadline=ev[i]["deadline"])
            meta[rid] = ev[i]
            i += 1
        srv.step()
        step += 1
    wall = time.perf_counter() - t0
    res = srv.drain()                        # whole done buffer — keep
    eng = srv.engine                         # only THIS run's rids
    completed = {r: res[r] for r in meta if r in res
                 and eng.status(r) in (DONE, PREEMPTED_RESUMED)}
    adm = np.array(list(eng.admission_lat)[adm0:] or [0.0])
    return {"meta": meta, "completed": completed,
            "met_tokens": int(sum(len(v) for v in completed.values())),
            "wall_s": wall,
            "admission_p99_ms": round(
                float(np.percentile(adm, 99)) * 1e3, 2)}


def bench_preempt(smoke: bool) -> None:
    """Overload policy comparison at 2× and 4× page-pool oversubscription
    (pool = what full concurrency needs, divided by the factor) under
    long-tail Poisson deadline traffic: ``stall`` (lifetime reservation —
    admission waits for worst-case pages), ``preempt`` (initial
    reservation + on-demand growth + latest-deadline-first eviction with
    recompute-on-resume), ``shed`` (lifetime + bounded queue,
    reject-latest-deadline). Reports deadline-met tokens (deterministic),
    wall goodput, p99 admission latency, and the resilience counters.
    Acceptance (ci.yml enforces on the smoke JSON): preempt's met tokens
    at 2× beat stall's, every completed request in preempt mode is
    bit-identical to solo serving (resume parity), and the measured
    replay of every (factor, policy) cell adds ZERO decode retraces."""
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve import gateway as G
    from repro.serve.engine import EngineConfig, PREEMPTED_RESUMED
    from repro.serve.gateway import PoolModel, RoutedServer

    cfg = get_config("qwen2-1.5b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)

    def mk(ecfg):
        pool = [PoolModel("qwen2-1.5b", cfg, params, 0.1)]
        router = routers.make(
            "kmeans", RouterConfig(d_emb=64, num_models=1),
            state={"centroids": jnp.zeros((1, 64)),
                   "A": jnp.array([[0.9]]), "C": jnp.array([[0.1]]),
                   "n": jnp.ones((1, 1))})
        return RoutedServer(pool, router, engine_cfg=ecfg)

    if smoke:
        n_req, max_new, chunk, max_seq, ps, slots = 16, 16, 4, 64, 8, 4
        slack, scale, long_words = 2, 0.25, (24, 41)   # region ≤ max_seq
    else:
        n_req, max_new, chunk, max_seq, ps, slots = 48, 32, 8, 128, 16, 8
        slack, scale, long_words = 2, 0.25, (24, 57)
    base_pages = slots * (max_seq // ps)     # full-concurrency worst case
    events = _deadline_traffic(0, n_req, max_new, chunk, slack=slack,
                               scale=scale, long_words=long_words)

    def cfg_for(mode, pages):
        kw = dict(slots=slots, max_seq=max_seq, chunk=chunk, page_size=ps,
                  pages=pages)
        if mode == "preempt":
            kw["reserve"] = "initial"
        elif mode == "shed":
            kw.update(queue_cap=slots,
                      shed_policy="reject-latest-deadline")
        return EngineConfig(**kw)

    factors, policies = (2, 4), ("stall", "preempt", "shed")
    # solo references (resume-parity oracle) — also warms the per-request
    # scan path BEFORE the trace-log snapshot below
    solo_srv, solo = mk(cfg_for("stall", base_pages)), {}
    for e in events:
        if e["prompt"] not in solo:
            solo[e["prompt"]] = np.asarray(solo_srv.generate(
                [e["prompt"]], lam=0.5, max_new_tokens=max_new,
                engine=False)["results"][0]["tokens"])
    # warm pass: every (factor, policy) cell once, off the books. The
    # measured replay reuses the SAME servers (route/prefill/decode jits
    # are warm per router instance), so any trace-log growth below is a
    # genuine decode retrace on the resilience path.
    servers = {(f, mode): mk(cfg_for(mode, base_pages // f))
               for f in factors for mode in policies}
    for srv in servers.values():
        _run_deadline_traffic(srv, events, max_new)
    trace0 = len(G.TRACE_LOG)

    oversub, parity = {}, True
    for f in factors:
        cell = {"pages": base_pages // f}
        for mode in policies:
            srv = servers[(f, mode)]
            c0 = srv.engine.counters()       # warm-pass totals to subtract
            r = _run_deadline_traffic(srv, events, max_new)
            c = {k: v - c0[k] for k, v in srv.engine.counters().items()}
            if mode == "preempt":
                for rid, toks in r["completed"].items():
                    parity &= bool(np.array_equal(
                        toks, solo[r["meta"][rid]["prompt"]]))
                    if srv.engine.status(rid) == PREEMPTED_RESUMED:
                        assert c["preemptions"] > 0
            goodput = r["met_tokens"] / max(r["wall_s"], 1e-9)
            cell[mode] = {
                "met_tokens": r["met_tokens"],
                "goodput_tok_s": round(goodput, 1),
                "admission_p99_ms": r["admission_p99_ms"],
                "completed": len(r["completed"]),
                "expiries": c["expiries"], "sheds": c["sheds"],
                "preemptions": c["preemptions"],
                "resume_recompute_toks": c["resume_recompute_toks"],
            }
            C.emit(
                f"preempt_{mode}_{f}x_{n_req}req",
                1e6 / max(goodput, 1e-9),
                f"{mode} policy at {f}x oversubscription "
                f"({base_pages // f} pages): {r['met_tokens']} deadline-met "
                f"tokens ({len(r['completed'])}/{n_req} requests), "
                f"admission p99 {r['admission_p99_ms']} ms, "
                f"{c['expiries']} expiries / {c['sheds']} sheds / "
                f"{c['preemptions']} preemptions")
        oversub[f"{f}x"] = cell
    decode_retraces = len(G.TRACE_LOG) - trace0

    C.write_bench(_bench_file("preempt", smoke), meta={
        "model": cfg.name, "n_req": n_req, "max_new": max_new,
        "chunk": chunk, "max_seq": max_seq, "page_size": ps,
        "slots": slots, "base_pages": base_pages, "smoke": smoke,
        "oversub": oversub,
        "resume_parity": bool(parity),
        "decode_retraces": int(decode_retraces),
    })


# ---------------------------------------------------------------------------
# spec: speculative multi-token decode (router-paired drafting) vs plain
# chunked decode on the same traffic
# ---------------------------------------------------------------------------


def _layer_skip_pair(key, cfg, skip_to):
    """A (target params, draft cfg, draft params) triple where the draft
    is the target's own first ``skip_to`` layers (shared embedding,
    unembedding and final norm — a LayerSkip-style self-drafter). The
    target's upper layers have their residual write-backs (attention
    ``wo``, SwiGLU ``wd``) zeroed, so its hidden state after N layers is
    bit-identical to the draft's after ``skip_to`` — greedy argmax agrees
    exactly and the drafter's acceptance rate is 1.0 by construction.
    This isolates the speculative pipeline's speedup at a *known*
    acceptance instead of entangling it with model quality."""
    import dataclasses
    from repro.models import init_params

    params = init_params(key, cfg)
    blocks = params["blocks"]
    u = skip_to
    blocks = dict(blocks)
    for lname in blocks:
        lp = dict(blocks[lname])
        mixer = dict(lp["mixer"])
        mixer["wo"] = mixer["wo"].at[u:].set(0.0)
        lp["mixer"] = mixer
        ffn = dict(lp["ffn"])
        ffn["wd"] = ffn["wd"].at[u:].set(0.0)
        lp["ffn"] = ffn
        blocks[lname] = lp
    params["blocks"] = blocks
    dcfg = dataclasses.replace(cfg, name=f"{cfg.name}-skip{u}", n_layers=u)
    dparams = {"embed": params["embed"], "final_norm": params["final_norm"],
               "blocks": jax.tree.map(lambda a: a[:u], params["blocks"])}
    return params, dcfg, dparams


def _run_spec_traffic(srv, reqs, max_new, draft_model=None):
    """`_run_engine_traffic` plus result capture: returns
    (tokens/sec, {prompt: np tokens}) so spec cells can be checked
    bit-identical against the non-speculative baseline."""
    import time
    pending = sorted(reqs, key=lambda r: r["arrival"])
    kw = {} if draft_model is None else {"draft_model": draft_model}
    prompt_of, completion = {}, {}
    t0 = time.perf_counter()
    i = 0
    while i < len(pending) or srv.engine.busy:
        now = time.perf_counter() - t0
        while i < len(pending) and pending[i]["arrival"] <= now:
            rid = srv.submit(pending[i]["prompt"], lam=pending[i]["lam"],
                             max_new_tokens=max_new, **kw)
            prompt_of[rid] = pending[i]["prompt"]
            i += 1
        if srv.engine.busy:
            for rid, _ in srv.step():
                completion[rid] = time.perf_counter() - t0
        elif i < len(pending):
            time.sleep(min(pending[i]["arrival"] - now, 1e-3))
    makespan = max(completion.values())
    out = srv.drain()
    toks = {prompt_of[r]: np.asarray(v) for r, v in out.items()}
    return len(reqs) * max_new / makespan, toks


def bench_spec(smoke: bool) -> None:
    """Speculative multi-token decode vs the plain chunked engine on the
    same Poisson trace. The pool holds the target, a LayerSkip-style
    self-drafter (first layer of the target — acceptance 1.0 by
    construction, see `_layer_skip_pair`), and a cheaper-but-useless tiny
    drafter (independent weights — acceptance ~1/vocab). The ``router``
    cells let the gateway pick the drafter by router utility A − λC,
    which ranks the layer-skip drafter above the tiny one despite its
    higher cost; the ``tiny`` cell forces the bad drafter via
    ``draft_model=`` to show the acceptance-rate dependence. Acceptance
    (ci.yml enforces on the smoke JSON): best spec cell's tokens/sec
    >= the non-spec baseline, every cell's tokens bit-identical to the
    baseline's, and the measured replays add ZERO decode retraces."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve import gateway as G
    from repro.serve.engine import EngineConfig
    from repro.serve.gateway import PoolModel, RoutedServer

    # Deeper/wider than the other benches: the speculative win comes from
    # verify batching T positions through weight-traversal-bound matmuls,
    # so compute must dominate per-dispatch overhead.
    cfg = dataclasses.replace(
        get_config("qwen2-1.5b").reduced(),
        n_layers=6, d_model=512, n_heads=8, n_kv_heads=2, d_ff=1024)
    key = jax.random.PRNGKey(0)
    params, dcfg, dparams = _layer_skip_pair(key, cfg, skip_to=1)
    tiny_cfg = dataclasses.replace(cfg, name=f"{cfg.name}-tiny", n_layers=1)
    tiny_params = init_params(jax.random.PRNGKey(99), tiny_cfg)

    pool = [PoolModel(cfg.name, cfg, params, 1.0),
            PoolModel(dcfg.name, dcfg, dparams, 0.25),
            PoolModel(tiny_cfg.name, tiny_cfg, tiny_params, 0.05)]
    # One cluster; A ranks target >> layer-skip >> tiny, so requests
    # route to the target at every λ in the trace while `_pick_draft`
    # (utility over the strictly-cheaper candidates) pairs it with the
    # layer-skip drafter, not the cheapest one.
    router = routers.make(
        "kmeans", RouterConfig(d_emb=64, num_models=3),
        state={"centroids": jnp.zeros((1, 64)),
               "A": jnp.array([[0.9, 0.6, 0.05]]),
               "C": jnp.array([[0.10, 0.025, 0.005]]),
               "n": jnp.ones((1, 3))})

    if smoke:
        n_req, max_new, max_seq, rate, longtail = 8, 12, 64, 200.0, False
        cells = [("router", 4)]
    else:
        n_req, max_new, max_seq, rate, longtail = 24, 32, 128, 50.0, True
        cells = [("router", 2), ("router", 4), ("router", 6), ("tiny", 4)]
    reqs = _make_traffic(0, n_req, rate_per_s=rate, longtail=longtail)

    def mk(spec_k):
        return RoutedServer(pool, router, engine_cfg=EngineConfig(
            slots=4, max_seq=max_seq, chunk=4, spec_k=spec_k))

    servers = {"base": mk(0)}
    for drafter, k in cells:
        servers[(drafter, k)] = mk(k)
    # warm pass on the SAME servers: every (cfg, bucket) prefill, draft
    # and verify program compiles off the books, so trace-log growth in
    # the measured replays below is a genuine speculative-path retrace
    for name, srv in servers.items():
        dm = 2 if name != "base" and name[0] == "tiny" else None
        _run_spec_traffic(srv, reqs, max_new, draft_model=dm)
    trace0 = len(G.TRACE_LOG)

    repeats = 2
    base_tps, base_toks = max(
        (_run_spec_traffic(servers["base"], reqs, max_new)
         for _ in range(repeats)), key=lambda r: r[0])
    parity, results = True, {}
    for drafter, k in cells:
        srv = servers[(drafter, k)]
        dm = 2 if drafter == "tiny" else None
        c0 = srv.engine.counters()
        tps, toks = max(
            (_run_spec_traffic(srv, reqs, max_new, draft_model=dm)
             for _ in range(repeats)), key=lambda r: r[0])
        c = {n: v - c0[n] for n, v in srv.engine.counters().items()}
        cell_parity = all(np.array_equal(toks[p], base_toks[p])
                          for p in base_toks)
        parity &= cell_parity
        acc = c["spec_accepted"] / max(c["spec_drafted"], 1)
        results[f"{drafter}_k{k}"] = {
            "tokens_per_s": round(tps, 1),
            "speedup": round(tps / base_tps, 3),
            "acceptance": round(acc, 3),
            "spec_rounds": c["spec_rounds"],
            "token_parity": bool(cell_parity),
        }
        C.emit(f"spec_{drafter}_k{k}_{n_req}req", 1e6 / tps,
               f"spec_k={k}, drafter={drafter}: {tps:.0f} tok/s "
               f"({tps / base_tps:.2f}x vs non-spec), acceptance "
               f"{acc:.2f} over {c['spec_rounds']} rounds",
               speedup_vs_baseline=tps / base_tps)
    C.emit(f"spec_baseline_{n_req}req", 1e6 / base_tps,
           f"non-speculative chunked engine: {base_tps:.0f} tok/s")
    decode_retraces = len(G.TRACE_LOG) - trace0

    best_name = max(results, key=lambda n: results[n]["speedup"])
    drafter, k = best_name.rsplit("_k", 1)
    C.write_bench(_bench_file("spec", smoke), meta={
        "model": cfg.name, "draft": dcfg.name, "n_req": n_req,
        "max_new": max_new, "slots": 4, "smoke": smoke,
        "baseline_tokens_per_s": round(base_tps, 1),
        "cells": results,
        "best": {"spec_k": int(k), "drafter": drafter,
                 "speedup": results[best_name]["speedup"],
                 "acceptance": results[best_name]["acceptance"]},
        "token_parity": bool(parity),
        "decode_retraces": int(decode_retraces),
    })


# ---------------------------------------------------------------------------
# fedloop: online federation (serve → harvest → federate → hot-swap) vs a
# frozen client-local router under distribution drift
# ---------------------------------------------------------------------------


def bench_fedloop(smoke: bool) -> None:
    """Drive live traffic through the engine while the FedLoop harvests
    per-client evaluations, runs federated syncs over the harvested
    buffers, and hot-swaps router state under the traffic. Scores the
    online-federated router against per-client routers frozen after
    phase 0 (the no-federation deployment) as mean frontier AUC over the
    clients' drifted query mixtures. Deterministic in its seeds, so the CI
    floor (online >= frozen-local under drift) is exact accounting, not a
    wall-clock race."""
    import time

    from repro.fed.scenarios import ScenarioConfig, run_online_vs_frozen
    from repro.serve.engine import TRACE_LOG

    if smoke:
        cfg = ScenarioConfig(queries_per_phase=64, phases=2, n_queries=800,
                             test_queries=48)
    else:
        cfg = ScenarioConfig(n_clients=8, queries_per_phase=256, phases=3,
                             n_queries=2000, test_queries=96)

    n_trace0 = len(TRACE_LOG)
    t0 = time.perf_counter()
    m = run_online_vs_frozen(cfg)
    wall = time.perf_counter() - t0
    # every sync after warmup swaps under the cached route jit — the trace
    # log only grows while programs warm, never per swap (tests pin the
    # zero-retrace guarantee; here we record the count for the trajectory)
    traces = len(TRACE_LOG) - n_trace0

    C.emit(f"fedloop_scenario_{cfg.phases}ph_{cfg.queries_per_phase}q",
           wall * 1e6 / max(m["requests_served"], 1),
           f"us per served request incl. {m['syncs']} federated syncs + "
           f"hot-swaps; final-phase frontier AUC online "
           f"{m['auc_online_final']:.3f} vs frozen client-local "
           f"{m['auc_frozen_local_final']:.3f} under drift",
           speedup_vs_baseline=(m["auc_online_final"]
                                / max(m["auc_frozen_local_final"], 1e-9)))
    C.write_bench(_bench_file("fedloop", smoke), meta={
        "smoke": smoke, "phases": cfg.phases,
        "queries_per_phase": cfg.queries_per_phase,
        "n_clients": cfg.n_clients,
        "auc_online": m["auc_online"],
        "auc_frozen_local": m["auc_frozen_local"],
        "auc_online_final": round(m["auc_online_final"], 4),
        "auc_frozen_local_final": round(m["auc_frozen_local_final"], 4),
        "auc_gap_final": round(m["auc_gap_final"], 4),
        "syncs": m["syncs"],
        "router_version": m["router_version"],
        "requests_served": m["requests_served"],
        "harvested_samples": m["harvested_samples"],
        "harvest_bytes": m["harvest_bytes"],
        "jit_traces_during_run": traces,
        "wall_seconds": round(wall, 2),
    })


# ---------------------------------------------------------------------------
# routerbench: the router zoo under the RouterBench-style harness —
# federated vs client-local AIQ per family, clean and perturbed, offline
# and live through the FedLoop
# ---------------------------------------------------------------------------


def bench_routerbench(smoke: bool) -> None:
    """Every registered router family fit federated vs per-client-local on
    one many-model pool, scored as frontier AIQ (normalized frontier AUC)
    under the clean, paraphrase-drift and adversarial routing-flip
    scenarios — plus the same comparison live (a FedLoop-maintained router
    vs frozen client-local fits under embedding drift). Deterministic in
    its seeds, so the CI floor — federated AIQ ≥ client-local AIQ for the
    mf family on EVERY scenario of the smoke run — is exact accounting,
    not a wall-clock race (see ci.yml)."""
    import time

    from repro.evalbench.harness import (offline_routerbench,
                                         online_routerbench)
    from repro.evalbench.pools import make_pool_corpus
    from repro.fed.scenarios import ScenarioConfig

    if smoke:
        rcfg = RouterConfig(d_emb=16, num_models=6, hidden=(48, 48),
                            dropout=0.0, k_local=5, k_global=8, mf_rank=12)
        fcfg = FedConfig(num_clients=4, rounds=30, batch_size=32, lr=3e-3,
                         seed=0)
        corpus = make_pool_corpus(jax.random.PRNGKey(1), n_models=6,
                                  n_queries=800, d_emb=16, n_tasks=5)
        local_steps, online_families = 200, ("mf",)
    else:
        rcfg = RouterConfig(d_emb=24, num_models=8, hidden=(48, 48),
                            dropout=0.0, k_local=6, k_global=10, mf_rank=16)
        fcfg = FedConfig(num_clients=6, rounds=40, batch_size=32, lr=3e-3,
                         seed=0)
        corpus = make_pool_corpus(jax.random.PRNGKey(1), n_models=8,
                                  n_queries=1200, d_emb=24, n_tasks=6)
        local_steps, online_families = 300, ("mf", "elo")

    t0 = time.perf_counter()
    off = offline_routerbench(jax.random.PRNGKey(0), rcfg=rcfg, fcfg=fcfg,
                              corpus=corpus, local_steps=local_steps)
    off_wall = time.perf_counter() - t0
    per_family_us = off_wall * 1e6 / max(len(off["families"]), 1)
    for name in sorted(off["families"]):
        fam = off["families"][name]
        fed, loc = fam["federated"], fam["client_local"]
        C.emit(f"routerbench_offline_{name}", per_family_us,
               "AIQ fed/local — " + "; ".join(
                   f"{sc} {fed[sc]['aiq']:.3f}/{loc[sc]['aiq']:.3f}"
                   for sc in ("clean", "paraphrase", "adversarial")),
               speedup_vs_baseline=(fed["clean"]["aiq"]
                                    / max(loc["clean"]["aiq"], 1e-9)))

    scen = ScenarioConfig(n_clients=4, n_models=3, d_emb=24, n_queries=800,
                          queries_per_phase=96, phases=2, embed_sigma=0.9,
                          test_queries=48, seed=0)
    online = {}
    for fam in online_families:
        t1 = time.perf_counter()
        res = online_routerbench(family=fam, cfg=scen, local_steps=150,
                                 capacity=256)
        wall = time.perf_counter() - t1
        C.emit(f"routerbench_online_{fam}",
               wall * 1e6 / max(res["requests_served"], 1),
               f"us per served request; final-phase AIQ online "
               f"{res['auc_online_final']:.3f} vs frozen client-local "
               f"{res['auc_frozen_local_final']:.3f} under embedding drift",
               speedup_vs_baseline=(res["auc_online_final"]
                                    / max(res["auc_frozen_local_final"],
                                          1e-9)))
        online[fam] = {
            "embed_sigma": res["embed_sigma"],
            "auc_online_final": round(res["auc_online_final"], 4),
            "auc_frozen_local_final": round(res["auc_frozen_local_final"],
                                            4),
            "auc_gap_final": round(res["auc_gap_final"], 4),
            "syncs": res["syncs"],
            "requests_served": res["requests_served"],
        }

    C.write_bench(_bench_file("routerbench", smoke), meta={
        "smoke": smoke,
        "n_models": off["n_models"],
        "n_clients": off["n_clients"],
        "rounds": fcfg.rounds,
        "local_steps": local_steps,
        "pool": off["pool"],
        "reference": {k: round(v, 4) for k, v in off["reference"].items()
                      if k != "models"},
        "families": off["families"],
        "online": online,
        "offline_wall_seconds": round(off_wall, 2),
    })


# ---------------------------------------------------------------------------
# resilience: Byzantine-robust aggregation under corrupted clients, sync
# latency vs cohort size, and FedLoop checkpoint/resume recovery
# ---------------------------------------------------------------------------


def _flip_labels(train, mask) -> dict:
    """Label-flip fault at the DATA layer: the masked clients report
    inverted accuracies (acc -> 1 - acc on their real rows) — the
    harvest-poisoning counterpart of the update-space corruptions."""
    acc = np.asarray(train["acc"]).copy()
    w = np.asarray(train["w"])
    for i, bad in enumerate(mask):
        if bad:
            acc[i] = np.where(w[i] > 0, 1.0 - acc[i], acc[i])
    out = dict(train)
    out["acc"] = jnp.asarray(acc)
    return out


def bench_resilience(smoke: bool) -> None:
    """Three fault-tolerance measurements, all exact accounting (seeded
    faults, deterministic fits) so ci.yml can enforce floors without a
    statistical fudge factor:

      * **corruption table** — frontier AUC of {fedavg, trimmed_mean,
        median, norm_clip} under 25% Byzantine clients for each fault
        class (sign-flip / scaled-noise update corruption via
        ``CorruptUpdates``, label-flip data poisoning) vs the clean fit.
        The CI floor: trimmed-mean under sign-flip stays within
        ``RESILIENCE_AUC_FLOOR`` of its clean AUC while plain FedAvg
        measurably degrades.
      * **sync latency vs cohort** — wall-clock of the scan-fused
        federated fit at full participation vs sampled cohorts (the
        static-slab gather keeps every cohort size on one compile).
      * **recovery** — a live FedLoop is killed after phase 0 (save),
        restored into a fresh process-alike (restore), and run to the end:
        reports save/restore wall time and whether the resumed router is
        bit-identical to the uninterrupted twin's.
    """
    import time

    from repro.core import policy
    from repro.fed.aggregators import (FedAvgAggregator, MedianAggregator,
                                       NormClipAggregator,
                                       TrimmedMeanAggregator)
    from repro.fed.faults import FaultPlan

    n_clients = 8
    rounds = 20 if smoke else 40
    rcfg = RouterConfig(d_emb=16, num_models=6, hidden=(32, 32), dropout=0.0)
    # full participation: every corrupted client is in every round, so the
    # 25%-Byzantine claim (and the trim capacity matched to it) is exact
    fcfg = FedConfig(num_clients=n_clients, participation=1.0, rounds=rounds,
                     batch_size=32, lr=3e-3)
    corpus = make_eval_corpus(jax.random.PRNGKey(0),
                              n_queries=600 if smoke else 1500,
                              n_tasks=5, n_models=6, d_emb=16)
    split = federated_split(jax.random.PRNGKey(1), corpus, fcfg)
    train, test = split["train"], split["test_global"]
    plan = FaultPlan(seed=3, corrupt_frac=0.25)
    mask = plan.corrupted_clients(n_clients)  # (n_clients,) bool

    aggs = {"fedavg": FedAvgAggregator(),
            "trimmed_mean": TrimmedMeanAggregator(trim_frac=0.25),
            "median": MedianAggregator(),
            "norm_clip": NormClipAggregator(clip=0.5)}

    def fit_auc(data, aggregator) -> float:
        router, _ = routers.fit_federated(
            routers.make("mlp", rcfg), data, fcfg,
            key=jax.random.PRNGKey(5), rounds=rounds, aggregator=aggregator)
        *_, auc = policy.eval_router(router.predict, test["x"],
                                     test["acc_table"], test["cost_table"])
        return float(auc)

    table: dict = {}
    t0 = time.perf_counter()
    for name, agg in aggs.items():
        row = {"clean": round(fit_auc(train, agg), 4)}
        for mode in ("sign_flip", "scaled_noise"):
            wrapped = plan.corrupt_updates(n_clients, inner=agg, mode=mode)
            row[mode] = round(fit_auc(train, wrapped), 4)
        row["label_flip"] = round(fit_auc(_flip_labels(train, mask), agg), 4)
        table[name] = row
        C.emit(f"resilience_{name}",
               (time.perf_counter() - t0) * 1e6 / (4 * rounds),
               f"us per round (4 fault classes x {rounds}r); AUC clean "
               f"{row['clean']:.3f} sign_flip {row['sign_flip']:.3f} "
               f"scaled_noise {row['scaled_noise']:.3f} label_flip "
               f"{row['label_flip']:.3f} at 25% corrupted",
               speedup_vs_baseline=row["sign_flip"]
               / max(row["clean"], 1e-9))
        t0 = time.perf_counter()

    # --- sync latency vs cohort size (scan-fused fit, static cohort slab)
    cohort_us = {}
    for cohort in (None, n_clients // 2, n_clients // 4):
        us = C.timeit(
            lambda c=cohort: routers.fit_federated(
                routers.make("mlp", rcfg), train, fcfg,
                key=jax.random.PRNGKey(5), rounds=rounds, cohort=c),
            warmup=1, iters=1, repeats=2 if smoke else 3)
        label = "full" if cohort is None else str(cohort)
        cohort_us[label] = round(us, 1)
        C.emit(f"resilience_sync_cohort_{label}", us,
               f"{rounds}-round scan-fused fit, cohort="
               f"{label}/{n_clients} clients")

    # --- checkpoint/resume recovery: killed-and-restored vs uninterrupted
    from repro.fed.harvest import HarvestStore
    from repro.fed.loop import FedLoop, FedLoopConfig
    from repro.fed.scenarios import ScenarioConfig, TrafficScenario
    from repro.serve.engine import EngineConfig
    from repro.serve.gateway import RoutedServer

    scfg = ScenarioConfig(n_clients=4, n_models=2, d_emb=16,
                          n_queries=400, queries_per_phase=48, phases=2,
                          straggler_frac=0.0, test_queries=32, seed=0)
    loop_rcfg = RouterConfig(d_emb=scfg.d_emb, num_models=scfg.n_models,
                             hidden=(16, 16), dropout=0.0)
    loop_fcfg = FedConfig(num_clients=scfg.n_clients, participation=1.0,
                          batch_size=32, lr=3e-3)
    lcfg = FedLoopConfig(sync_every=10 ** 9, rounds_per_sync=3,
                         min_samples=1)

    def fresh_loop(scenario):
        pool = scenario.make_pool()
        router = routers.make("mlp", loop_rcfg).init(jax.random.PRNGKey(21))
        harvest = HarvestStore(scfg.d_emb, capacity=64,
                               clients=range(scfg.n_clients))
        srv = RoutedServer(pool, router, harvest=harvest,
                           engine_cfg=EngineConfig(slots=4, max_seq=32,
                                                   chunk=4, page_size=8))
        return srv, FedLoop(srv, loop_fcfg, key=jax.random.PRNGKey(23),
                            cfg=lcfg)

    def drive(scenario, srv, loop, phase):
        # outcomes keyed statelessly on (query, model) so an interrupted
        # run replays the exact same observations after restore
        for (c, q, lam) in scenario.events(phase):
            rid = srv.submit(scenario.prompt(q), lam=lam,
                             max_new_tokens=scfg.max_new, client_id=c,
                             x=scenario.x(q, phase))
            m = srv.routed_model(rid)
            p = float(scenario.corpus["acc_table"][q, m])
            u = np.random.default_rng(q * 1_000_003 + m).random()
            srv.report_outcome(rid, float(u < p),
                               float(scenario.corpus["cost_table"][q, m]))
            loop.step()
        loop.drain()
        loop.sync()

    srv_a, loop_a = fresh_loop(TrafficScenario(scfg))   # uninterrupted twin
    for phase in range(scfg.phases):
        drive(TrafficScenario(scfg), srv_a, loop_a, phase)

    srv_b, loop_b = fresh_loop(TrafficScenario(scfg))   # killed after phase 0
    drive(TrafficScenario(scfg), srv_b, loop_b, 0)
    ckpt_path = C.REPO_ROOT / ("BENCH_resilience.ckpt.tmp")
    t0 = time.perf_counter()
    loop_b.save(ckpt_path)
    save_s = time.perf_counter() - t0
    del srv_b, loop_b

    t0 = time.perf_counter()
    srv_c, loop_c = fresh_loop(TrafficScenario(scfg))
    loop_c.restore(ckpt_path)
    restore_s = time.perf_counter() - t0
    ckpt_bytes = ckpt_path.stat().st_size
    ckpt_path.unlink()
    for phase in range(1, scfg.phases):
        drive(TrafficScenario(scfg), srv_c, loop_c, phase)

    la, lc = jax.tree.leaves(srv_a.router.state), \
        jax.tree.leaves(srv_c.router.state)
    parity = (len(la) == len(lc)
              and all(np.array_equal(np.asarray(x), np.asarray(y))
                      for x, y in zip(la, lc))
              and srv_a.router_version == srv_c.router_version)
    C.emit("resilience_recovery", restore_s * 1e6,
           f"restore a killed FedLoop ({ckpt_bytes} bytes) and resume; "
           f"save {save_s * 1e3:.1f} ms; resumed router bit-identical to "
           f"uninterrupted twin: {parity}",
           speedup_vs_baseline=1.0 if parity else 0.0)

    C.write_bench(_bench_file("resilience", smoke), meta={
        "smoke": smoke, "rounds": rounds, "n_clients": n_clients,
        "corrupt_frac": 0.25,
        "corrupted_clients": [int(i) for i in np.flatnonzero(mask)],
        "corruption_auc": table,
        "sync_us_by_cohort": cohort_us,
        "checkpoint": {"save_ms": round(save_s * 1e3, 2),
                       "restore_ms": round(restore_s * 1e3, 2),
                       "bytes": int(ckpt_bytes),
                       "resume_bit_identical": bool(parity)},
    })


# ---------------------------------------------------------------------------
# mesh: cross-silo sharded fit + engine vs single device, in this process
# on the devices it has (on CPU, force several with XLA_FLAGS=
# --xla_force_host_platform_device_count=N before jax initializes)
# ---------------------------------------------------------------------------


def _mesh_fit_slab(n_clients: int, queries: int, d_emb: int,
                   n_models: int) -> dict:
    """Stacked federated slab with PowerLaw-skewed per-client sample masks
    (the population regime the sharded fit targets: a Zipf head carries
    the data, the long tail is mostly padding)."""
    from repro.fed.scenarios import PowerLawScenario
    rng = np.random.default_rng(7)
    scen = PowerLawScenario(n_clients=n_clients, zipf_a=1.1, churn=0.15)
    counts = np.minimum(
        queries,
        np.ceil(scen.popularity(0) * n_clients * queries * 0.5)
    ).astype(np.int32)
    return {
        "x": rng.normal(size=(n_clients, queries, d_emb)).astype(np.float32),
        "m": rng.integers(0, n_models,
                          size=(n_clients, queries)).astype(np.int32),
        "acc": (rng.random((n_clients, queries)) < 0.5).astype(np.float32),
        "cost": rng.random((n_clients, queries)).astype(np.float32),
        "w": (np.arange(queries)[None] < counts[:, None]).astype(np.float32),
    }


def _mesh_fit(task: dict) -> dict:
    """Measure one federated fit configuration on ``task["devices"]``
    devices. Reports clients/s plus the FIT_TRACE_LOG growth across the
    timed repeats (zero-retrace pin)."""
    import time

    import repro.sharding as shd
    from repro.core import federated as F

    N, D = int(task["n_clients"]), int(task["queries"])
    rounds, cohort = int(task["rounds"]), task.get("cohort")
    d_emb, n_models = 16, 8
    data = _mesh_fit_slab(N, D, d_emb, n_models)
    rcfg = RouterConfig(d_emb=d_emb, num_models=n_models, hidden=(32, 32))
    fcfg = FedConfig(num_clients=N, batch_size=16, lr=1e-2)
    mesh = shd.client_mesh(task["devices"]) if task["devices"] > 1 else None
    if mesh is not None:
        data = shd.shard_clients(data, mesh)
    key = jax.random.PRNGKey(0)

    def fit():
        params, _ = F.fedavg(key, data, rcfg, fcfg, rounds=rounds,
                             cohort=cohort, mesh=mesh)
        jax.block_until_ready(params)

    t0 = time.perf_counter()
    fit()                                   # compile + warm caches
    compile_s = time.perf_counter() - t0
    n_trace = len(F.FIT_TRACE_LOG)
    times = []
    for i in range(int(task.get("repeats", 3))):
        if task.get("profile") and i == 0:
            with jax.profiler.trace(task["profile"]):
                t0 = time.perf_counter()
                fit()
                times.append(time.perf_counter() - t0)
        else:
            t0 = time.perf_counter()
            fit()
            times.append(time.perf_counter() - t0)
    per_round_clients = cohort if cohort else N
    return {"fit_s": min(times), "compile_s": compile_s,
            "clients_per_s": per_round_clients * rounds / min(times),
            "retraces": len(F.FIT_TRACE_LOG) - n_trace}


def _mesh_engine(task: dict) -> dict:
    """Measure engine decode tokens/s: a slot-saturating batch on a
    uniform pool, solo or with the KV pool sharded slot-parallel over a
    "data" mesh of ``task["devices"]``. Token parity versus the solo
    engine is pinned in tests/test_mesh.py; here we time."""
    import time

    import repro.sharding as shd
    from repro.models import init_params
    from repro.serve.engine import (TRACE_LOG, EngineConfig, ModelConfig,
                                    ServeEngine)

    slots, max_new = int(task["slots"]), int(task["max_new"])
    cfg = ModelConfig(name="mesh-bench-dense", arch_type="dense",
                      n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                      d_ff=128, vocab=257, head_dim=16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    pm = type("PM", (), {"cfg": cfg, "params": params})()
    mesh = shd.data_mesh(task["devices"]) if task["devices"] > 1 else None
    ecfg = EngineConfig(slots=slots, max_seq=128, chunk=8, page_size=None)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab, size=rng.integers(4, 12))
               for _ in range(2 * slots)]

    def run(engine):
        rids = [engine.submit(0, p, max_new) for p in prompts]
        engine.drain(rids)

    engine = ServeEngine([pm], ecfg, mesh=mesh)
    run(engine)                              # compile + warm caches
    n_trace = len(TRACE_LOG)
    times = []
    for _ in range(int(task.get("repeats", 3))):
        t0 = time.perf_counter()
        run(engine)
        times.append(time.perf_counter() - t0)
    toks = len(prompts) * max_new
    return {"engine_s": min(times), "tokens_per_s": toks / min(times),
            "retraces": len(TRACE_LOG) - n_trace}


def bench_mesh(smoke: bool, profile: str | None = None) -> None:
    """Cross-silo mesh execution vs single device, in this process over
    every device it has: the sharded federated fit (PowerLaw client
    population, full and cohort-sampled rounds) and the slot-parallel
    engine decode. On hosts with fewer cores than forced CPU devices the
    mesh path pays pure dispatch + collective overhead with no parallel
    hardware to win it back — ``meta.host_cpus`` records that so CI gates
    the throughput floor on real parallelism being present."""
    import os

    devices = len(jax.devices())
    if smoke:
        fit_cases = [("fit_256c", dict(kind="fit", n_clients=256,
                                       queries=8, rounds=2, repeats=2))]
    else:
        fit_cases = [
            ("fit_1024c", dict(kind="fit", n_clients=1024, queries=16,
                               rounds=3, repeats=3)),
            ("fit_10240c_cohort512", dict(kind="fit", n_clients=10240,
                                          queries=8, rounds=3, cohort=512,
                                          repeats=3)),
        ]
    eng_case = dict(kind="engine", slots=8, max_new=8 if smoke else 32,
                    repeats=2 if smoke else 3)

    results = {}
    for name, case in fit_cases + [("engine", eng_case)]:
        for dev in sorted({1, devices}):
            task = {**case, "devices": dev}
            if profile and dev == devices and case["kind"] == "fit":
                task["profile"] = os.path.join(profile, f"mesh_{name}")
            results[(name, dev)] = (_mesh_fit(task) if case["kind"] == "fit"
                                    else _mesh_engine(task))

    for name, case in fit_cases:
        solo, mesh = results[(name, 1)], results[(name, devices)]
        assert mesh["retraces"] == 0, f"{name}: mesh fit retraced"
        C.emit(f"mesh_{name}_1dev", solo["fit_s"] * 1e6,
               f"{solo['clients_per_s']:.0f} clients/s, single device")
        C.emit(f"mesh_{name}_{devices}dev", mesh["fit_s"] * 1e6,
               f"{mesh['clients_per_s']:.0f} clients/s, shard_map over "
               f"{devices} {jax.devices()[0].platform} devices",
               speedup_vs_baseline=solo["fit_s"] / mesh["fit_s"])
    solo, mesh = results[("engine", 1)], results[("engine", devices)]
    assert mesh["retraces"] == 0, "engine: mesh decode retraced"
    C.emit("mesh_engine_1dev", solo["engine_s"] * 1e6,
           f"{solo['tokens_per_s']:.0f} tokens/s, single device")
    C.emit(f"mesh_engine_{devices}dev", mesh["engine_s"] * 1e6,
           f"{mesh['tokens_per_s']:.0f} tokens/s, KV pool slot-parallel "
           f"over {devices} {jax.devices()[0].platform} devices",
           speedup_vs_baseline=solo["engine_s"] / mesh["engine_s"])
    C.write_bench(_bench_file("mesh", smoke), meta={
        "smoke": smoke, "devices": devices,
        "host_cpus": os.cpu_count(),
        "fit_speedup": {n: round(results[(n, 1)]["fit_s"]
                                 / results[(n, devices)]["fit_s"], 3)
                        for n, _ in fit_cases},
        "engine_speedup": round(solo["engine_s"] / mesh["engine_s"], 3),
    })


SECTIONS = ("train", "route", "serve", "engine", "paged", "preempt",
            "spec", "fedloop", "routerbench", "resilience", "mesh")


def main() -> None:
    import contextlib
    import os

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workloads — validate the harness, not perf")
    ap.add_argument("--sections", default=",".join(SECTIONS),
                    help="comma-separated subset of sections to run "
                         f"(default: all of {','.join(SECTIONS)})")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace per section into "
                         "DIR (TensorBoard format); off by default")
    args = ap.parse_args()

    sections = [s.strip() for s in args.sections.split(",") if s.strip()]
    unknown = set(sections) - set(SECTIONS)
    if unknown:
        raise SystemExit(f"unknown sections: {sorted(unknown)} "
                         f"(pick from {','.join(SECTIONS)})")
    if args.profile:
        os.makedirs(args.profile, exist_ok=True)

    for s in sections:
        if s == "mesh":
            # traces each sharded fit on its own (one trace per case)
            bench_mesh(args.smoke, profile=args.profile)
            continue
        ctx = (jax.profiler.trace(os.path.join(args.profile, s))
               if args.profile else contextlib.nullcontext())
        with ctx:
            globals()[f"bench_{s}"](args.smoke)

    for f in (_bench_file(s, args.smoke) for s in sections):
        blob = json.loads((C.REPO_ROOT / f).read_text())
        assert blob["records"], f"{f}: no records"
        assert all(np.isfinite(r["us_per_call"]) for r in blob["records"])
        print(f"{f}: {len(blob['records'])} records OK")


if __name__ == "__main__":
    main()
