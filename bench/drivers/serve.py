"""Serving driver: a routed pool of models behind ``RoutedServer``, driven
through its public entry points (``submit``, ``step``, ``routed_model``,
``report_outcome``) by a closed or an open loop.

Set-up makes the weights and the router from the seed, builds the server,
and warms every shape the mix can ask for: for each lane, each prompt
bucket of the mix's block and each admission batch of ``warm_batches``,
one coalesced prefill, then the decode chunk. On more than one chip the
server runs on ``data_mesh(chips)``: decode rows split over the chips, the
paged pool and the weights whole on each (the weights made there in
place, so that no chip holds a second copy). The closed loop also ramps
its backlog up during set-up, at most ``ramp_per_step`` submissions a
step, so that no admission batch outgrows the warmed ones.

Failures: a request fails only if the engine returns something other than
its tokens, or if the comparison with the reference rejects it. A request
still in flight when a closed-loop window ends is neither attempted nor
failed; in the open loop every request due in the window is waited for.
"""
from __future__ import annotations

import collections
import gc
import itertools
import time

import numpy as np

from bench import reference, traffic


def _next_pow2(v: int) -> int:
    return 1 << (max(int(v), 1) - 1).bit_length()


class Lanes:
    """Per-lane bookkeeping the loops keep from the public API alone:
    the FIFO of requests waiting to be admitted and the requests holding a
    decode row."""

    def __init__(self, n):
        self.queue = [collections.deque() for _ in range(n)]
        self.active = [dict() for _ in range(n)]


class Serving:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.mix = ctx.config, ctx.traffic
        self.seed = ctx.seed
        self.records = {}          # rid -> dict
        self.steps = []            # per window step: dict
        self.submit_s = []         # host seconds per RoutedServer.submit
        self.counters = collections.Counter()  # engine counters: window deltas

    # ------------------------------------------------------------- set-up
    def build(self):
        import jax
        import repro.sharding as shd
        from repro import routers
        from repro.config import RouterConfig
        from repro.fed.harvest import HarvestStore
        from repro.serve.engine import EngineConfig
        from repro.serve.gateway import PoolModel, RoutedServer
        from bench import weights

        cfg, ctx = self.cfg, self.ctx
        self.models, self.families, self.params, pool = [], [], [], []
        mesh = shd.data_mesh(ctx.chips) if ctx.chips > 1 else None
        on_mesh = {} if mesh is None else {"mesh": mesh}
        with ctx.phase("weights"):
            for lane in cfg["pool"]:
                m = cfg if lane["model"] == "self" else cfg[lane["model"]]
                fam = ctx.family(m)
                mcfg, params = weights.model_params(
                    fam, m, self.seed + lane["seed_offset"],
                    None if mesh is None else shd.named(mesh))
                self.models.append(m)
                self.families.append(fam)
                self.params.append(params)
                pool.append(PoolModel(f"{m['architectures'][0]}"
                                      f"/{lane['seed_offset']}", mcfg,
                                      params, lane["cost_per_token"]))
            r = cfg["router"]
            self.rcfg = RouterConfig(d_emb=r["d_emb"],
                                     hidden=tuple(r["hidden"]),
                                     num_models=len(pool))
            costs = [lane["cost_per_token"] for lane in cfg["pool"]]
            quality = [lane.get("quality_logit", 1.0 - i)
                       for i, lane in enumerate(cfg["pool"])]
            state = weights.router_state(
                self.rcfg, quality, costs, self.seed, r.get("head_scale", 1.0))
            calib = list(itertools.islice(traffic.stream(
                self.mix, self.seed, vocab=2, d_emb=r["d_emb"]), 96))
            self.router_state = weights.balance_router(
                state, np.stack([q.x for q in calib]),
                float(np.median(self.mix["lambdas"])))
            jax.block_until_ready((self.params, self.router_state))
        e = cfg["engine"]
        self.ecfg = EngineConfig(slots=e["slots"], max_seq=e["max_seq"],
                                 chunk=e["chunk"], page_size=e["page_size"],
                                 pages=e["pages"])
        router = routers.make(r["family"], self.rcfg, num_models=len(pool),
                              state=self.router_state)
        self.srv = RoutedServer(
            pool, router, engine_cfg=self.ecfg,
            harvest=HarvestStore(r["d_emb"], capacity=1024,
                                 clients=range(self.mix["clients"])),
            **on_mesh)
        self.lanes = Lanes(len(pool))
        self.vocab = min(m["vocab_size"] for m in self.models)
        self.gen = traffic.stream(self.mix, self.seed, vocab=self.vocab,
                                  d_emb=r["d_emb"])
        self.outcome_rng = np.random.default_rng(
            [self.seed & 0xFFFFFFFF, self.seed >> 32, 11])

    def warm(self):
        """Every (admission batch, prompt bucket) the mix can coalesce,
        on every lane, through the engine's own submit; then the route
        program through one gateway submit."""
        eng, ecfg = self.srv.engine, self.ecfg
        prompts, _, _, _ = traffic._block_sizes(self.mix)
        buckets = sorted({_next_pow2(p) for p in prompts})
        groups = [(b, s) for b in self.mix["warm_batches"] for s in buckets]
        with self.ctx.phase("warm-up"):
            for lane in range(len(self.models)):
                todo = [(b, s) for b, s in groups
                        if b <= ecfg.slots and b * -(-(s + ecfg.chunk)
                            // ecfg.page_size) <= ecfg.resolved_pages]
                while todo:
                    # one group per bucket and step: groups of a bucket
                    # admitted together would coalesce into one batch
                    slots, pages, seen = ecfg.slots, ecfg.resolved_pages, set()
                    for b, s in list(todo):
                        need = b * -(-(s + ecfg.chunk) // ecfg.page_size)
                        if s in seen or b > slots or need > pages:
                            continue
                        slots, pages = slots - b, pages - need
                        seen.add(s)
                        todo.remove((b, s))
                        for _ in range(b):
                            eng.submit(lane, np.ones((s,), np.int32),
                                       ecfg.chunk)
                    while eng.busy:
                        eng.step()
                    eng.drain()
            req = next(self.gen)
            rid = self.srv.submit("", lam=req.lam, max_new_tokens=ecfg.chunk,
                                  tokenize=lambda _: req.prompt[None],
                                  x=req.x)
            self.srv.drain([rid])
            self.srv.engine.drain()

    # -------------------------------------------------------------- loops
    def submit(self, req, in_window: bool):
        srv = self.srv
        t0 = time.perf_counter()
        rid = srv.submit("", lam=req.lam, max_new_tokens=req.max_new,
                         tokenize=lambda _: req.prompt[None],
                         client_id=req.client, x=req.x)
        t1 = time.perf_counter()
        if in_window:
            self.submit_s.append(t1 - t0)
        lane = srv.routed_model(rid)
        self.lanes.queue[lane].append(rid)
        self.records[rid] = {"req": req, "lane": lane, "emitted": 0,
                             "t_submit": t0, "t_first": None,
                             "t_done": None, "tokens": None, "error": None}
        return rid

    def step(self, in_window: bool):
        """One engine step; returns the requests it finished."""
        chunk = self.ecfg.chunk
        before = [rid for act in self.lanes.active for rid in act]
        c0 = self.srv.engine.counters() if in_window else None
        t0 = time.perf_counter()
        with self.ctx.span("bench.step"):
            finished = self.srv.step()
        t1 = time.perf_counter()
        if in_window:
            for k, v in self.srv.engine.counters().items():
                self.counters[k] += v - c0.get(k, 0)
        admitted = []
        for lane, q in enumerate(self.lanes.queue):
            while q and self.srv.status(q[0]) != "QUEUED":
                rid = q.popleft()
                self.lanes.active[lane][rid] = True
                self.records[rid]["t_first"] = t1
                admitted.append(rid)
        n_tok, rows = 0, 0
        ctx_lens = [[] for _ in self.lanes.active]
        for rid in before + admitted:
            rec = self.records[rid]
            k = min(chunk, rec["req"].max_new - rec["emitted"])
            # emitted token i >= 1 came from the decode of position S+i-1,
            # which attends S+i positions; token 0 came from the prefill
            S, e = len(rec["req"].prompt), rec["emitted"]
            ctx_lens[rec["lane"]].extend(S + i for i in range(max(e, 1),
                                                              e + k))
            rec["emitted"] += k
            n_tok += k
            rows += 1
        done = []
        for rid, payload in finished:
            rec = self.records.get(rid)
            if rec is None:
                continue
            self.lanes.active[rec["lane"]].pop(rid, None)
            rec["t_done"] = t1
            if isinstance(payload, np.ndarray):
                rec["tokens"] = np.asarray(payload)
            else:
                rec["error"] = f"{getattr(payload, 'status', payload)}"
            done.append(rid)
            self.srv.report_outcome(
                rid, float(self.outcome_rng.random() < 0.7))
        if in_window:
            self.steps.append({
                "t0": t0, "t1": t1, "tokens": n_tok, "rows": rows,
                "admitted": [(self.records[r]["lane"],
                              len(self.records[r]["req"].prompt))
                             for r in admitted],
                "contexts": ctx_lens, "finished": len(done)})
        return done

    def refill(self, cap: int, in_window: bool) -> int:
        """Submit until every lane holds its backlog in its queue, at most
        ``cap`` requests."""
        target = int(round(self.mix["backlog_per_slot"] * self.ecfg.slots))
        n = 0
        while n < cap and min(len(q) for q in self.lanes.queue) < target:
            self.submit(next(self.gen), in_window)
            n += 1
        return n

    def run_closed(self, seconds: float):
        slots_total = self.ecfg.slots * len(self.models)
        with self.ctx.phase("ramp"):
            ramp = self.mix["ramp_per_step"]
            for _ in range(4 * slots_total // ramp + 8):
                if self.refill(ramp, False) == 0 and all(
                        len(a) >= min(self.ecfg.slots, len(a) + len(q))
                        for a, q in zip(self.lanes.active,
                                        self.lanes.queue)):
                    break
                self.step(False)
        self.window_rids = set()
        with self.ctx.window() as w:
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                with self.ctx.span("bench.refill"):
                    self.refill(self.ecfg.slots, True)
                self.window_rids.update(self.step(True))
            w.end()
        self.window_s = w.seconds

    def run_open(self, seconds: float):
        """Requests are sent when due for ``seconds``; the window closes
        there. Every request due in it is then waited for, up to the mix's
        ``drain_s``, with no further arrivals: its latency counts the
        wait."""
        lateness = []
        self.window_rids = set()
        nxt = next(self.gen)
        with self.ctx.window() as w:
            t0 = self.t_open = time.perf_counter()
            while True:
                now = time.perf_counter() - t0
                if now >= seconds:
                    break
                with self.ctx.span("bench.submit"):
                    while nxt.due <= now:
                        rid = self.submit(nxt, True)
                        self.records[rid]["due"] = t0 + nxt.due
                        lateness.append(now - nxt.due)
                        self.window_rids.add(rid)
                        nxt = next(self.gen)
                        now = time.perf_counter() - t0
                if self.srv.engine.busy:
                    self.step(True)
                else:
                    wait = min(nxt.due, seconds) - now
                    if wait > 0:
                        with self.ctx.span("bench.idle"):
                            time.sleep(wait)
            w.end()
        t_end = time.perf_counter() + float(self.mix["drain_s"])
        while (time.perf_counter() < t_end and any(
                self.records[r]["t_done"] is None for r in self.window_rids)):
            self.step(False)
        self.window_s = seconds
        self.lateness = np.asarray(lateness)

    # ------------------------------------------------------------ results
    def results(self, closed: bool) -> dict:
        """End-to-end metrics and the facts the per-layer readers read."""
        recs = [self.records[r] for r in sorted(self.window_rids)]
        done = [r for r in recs if r["t_done"] is not None]
        out = {"attempted": len(recs), "recs": recs}
        steps = self.steps
        if closed:
            tok = sum(s["tokens"] for s in steps)
            span = self.window_s
            out["e2e"] = {"tokens_per_s": tok / span}
            self.ctx.log(f"window: {len(steps)} steps, {tok} tokens in "
                         f"{span:.4f} s, {len(done)} requests finished")
        else:
            ttft = np.array([(r["t_first"] - r["due"]) * 1e3 for r in done
                             if r["t_first"] is not None])
            tpot = np.array([(r["t_done"] - r["t_first"]) * 1e3
                             / max(r["req"].max_new - 1, 1) for r in done
                             if r["t_first"] is not None])
            unfinished = len(recs) - len(done)
            out["e2e"] = {
                "ttft_p95_ms": _pct(ttft, 95), "tpot_p95_ms": _pct(tpot, 95)}
            self.ctx.log(
                f"window: {len(recs)} requests due at {self.mix['rate_per_s']} "
                f"req/s, "
                f"{len(done)} finished, {unfinished} unfinished after the "
                f"drain; ttft samples {len(ttft)} (p50 {_pct(ttft, 50):.1f}"
                f" ms, p95 {_pct(ttft, 95):.1f} ms, max "
                f"{ttft.max() if len(ttft) else 0:.1f} ms); tpot samples "
                f"{len(tpot)} (p50 {_pct(tpot, 50):.2f} ms, p95 "
                f"{_pct(tpot, 95):.2f} ms); generator lateness p50 "
                f"{_pct(self.lateness * 1e3, 50):.3f} ms, max "
                f"{self.lateness.max() * 1e3 if len(self.lateness) else 0:.3f}"
                f" ms; {len(steps)} steps in the window")
        return out

    def layer_facts(self) -> dict:
        """Counts and host timings of the window for the per-layer
        readers: step times, occupancy, FLOPs of the work done, each
        kernel's FLOPs and bytes over every decode step, as each lane's
        model family counts them, and the engine's counter deltas."""
        from bench.readers import PAGED_ATTN
        steps, ecfg, fams = self.steps, self.ecfg, self.families
        slots_total = ecfg.slots * len(self.models)
        flops = 0.0
        for s in steps:
            for lane, ctxs in enumerate(s["contexts"]):
                m = self.models[lane]
                flops += sum(fams[lane].token_flops(m, c) for c in ctxs)
            for lane, n in s["admitted"]:
                flops += fams[lane].prefill_flops(self.models[lane], n)
        # per decode step of a chunk, each lane's attention call reads the
        # rows that are still inside their requests
        cost = collections.defaultdict(lambda: [0, 0])
        for s in steps:
            for lane, ctxs in enumerate(s["contexts"]):
                m, fam = self.models[lane], fams[lane]
                if not ctxs:
                    continue
                each = {PAGED_ATTN: fam.decode_attn_cost(m, ctxs)}
                if hasattr(fam, "kernel_costs"):
                    each.update(fam.kernel_costs(m, ctxs))
                for name, (f, b) in each.items():
                    cost[name][0] += f
                    cost[name][1] += b
        span = steps[-1]["t1"] - steps[0]["t0"] if steps else 0.0
        return {
            "step_s": [s["t1"] - s["t0"] for s in steps],
            "occupancy": [s["rows"] / slots_total for s in steps],
            "submit_s": list(self.submit_s),
            "model_flops": flops,
            "kernel_cost": {k: tuple(v) for k, v in cost.items()},
            "counters": dict(self.counters),
            "window_span_s": span,
        }

    # -------------------------------------------------------- correctness
    def check(self, control: bool = False) -> dict:
        """Compare with the references once the window has closed: every
        finished request's route, and the served tokens of a sample drawn
        from the seed, the longest of each lane among them. The server's
        state is freed first. Returns the compared numbers and the rids
        the comparison rejected."""
        done = [self.records[r] for r in sorted(self.window_rids)
                if self.records[r]["tokens"] is not None]
        self.srv = None
        self.lanes = None
        gc.collect()
        lim = self.cfg.get("limits", {})
        out = {"numbers": {}, "rejected": set(), "control": {}}
        if not done:
            return out
        # routes: all finished requests. Compared only where the
        # configuration sets a limit: the readings must separate the
        # program from the control first
        x = np.stack([r["req"].x for r in done])
        lam = np.array([r["req"].lam for r in done])
        chosen = np.array([r["lane"] for r in done])
        g = reference.route_gaps(self.router_state, x, lam, chosen)
        route = float(g.max())
        if "route_gap" in lim:
            out["numbers"]["route_gap"] = (route, lim["route_gap"])
            out["rejected"].update(
                r["req"].index for r, v in zip(done, g)
                if v > lim["route_gap"])
        ctl_route = (float(reference.route_gaps(
            self.router_state, x, lam, chosen, control=True).max())
            if control else None)
        if control:
            out["control"]["route_gap"] = ctl_route
        self.ctx.log(f"routes: {len(done)} compared, widest gap {route:.6g}"
                     + (f", fp8 control {ctl_route:.6g}" if control else "")
                     + ("" if "route_gap" in lim else " (not compared)"))
        # tokens: a sample per lane, with the lane's longest request in it
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF,
                                     self.seed >> 32, 13])
        k = int(self.mix["check"]["per_lane"])
        tok_lim = lim.get("tok_gap")
        for lane, (m, fam) in enumerate(zip(self.models, self.families)):
            mine = [r for r in done if r["lane"] == lane]
            if not mine:
                continue
            longest = max(mine, key=lambda r: len(r["req"].prompt)
                          + r["req"].max_new)
            rest = [r for r in mine if r is not longest]
            pick = [longest] + [rest[i] for i in sorted(rng.choice(
                len(rest), size=min(k - 1, len(rest)), replace=False))]
            worst, n_tok, ctl = 0.0, 0, 0.0
            for r in pick:
                gaps = fam.token_gaps(self.params[lane], m,
                                      r["req"].prompt, r["tokens"],
                                      length=self.ecfg.max_seq)
                n_tok += len(gaps)
                worst = max(worst, float(gaps.max()))
                if tok_lim is not None and gaps.max() > tok_lim[lane]:
                    out["rejected"].add(r["req"].index)
                if control:
                    ctl = max(ctl, float(fam.token_gaps(
                        self.params[lane], m, r["req"].prompt, r["tokens"],
                        length=self.ecfg.max_seq, control=True).max()))
            name = f"tok_gap.lane{lane}"
            out["numbers"][name] = (worst, None if tok_lim is None
                                    else tok_lim[lane])
            if control:
                out["control"][name] = ctl
            self.ctx.log(f"check lane {lane}: {len(pick)} requests, "
                         f"{n_tok} served tokens compared, widest gap "
                         f"{worst:.6g}" + (f", fp8 control {ctl:.6g}"
                                           if control else ""))
        return out


def _pct(a, p) -> float:
    a = np.asarray(a, np.float64)
    return float(np.percentile(a, p)) if a.size else float("nan")


def _traces_since(mark: int) -> list:
    """The engine's own log of jit traces (one entry per program traced)
    past ``mark``: a shape that warm-up missed shows here."""
    from repro.serve.engine import TRACE_LOG
    return list(TRACE_LOG)[mark:]


def run(ctx) -> dict:
    """Set up, warm, measure one window, check; returns the run's
    result for the harness."""
    from repro.serve.engine import TRACE_LOG
    s = Serving(ctx)
    closed = ctx.traffic["driver"] == "closed"
    s.build()
    s.warm()
    mark = len(TRACE_LOG)
    if closed:
        s.run_closed(ctx.seconds)
    else:
        s.run_open(ctx.seconds)
    late = _traces_since(mark)
    ctx.log(f"programs traced after warm-up: {len(late)} {late}")
    res = s.results(closed)
    facts = s.layer_facts()
    ctx.read_memory()
    t0 = time.perf_counter()
    chk = s.check(control=ctx.control)
    ctx.log(f"comparison with the reference: {time.perf_counter() - t0:.3f}"
            " s")
    # an error, or (open loop) no answer by the end of the drain
    errored = {r["req"].index for r in res["recs"]
               if r["error"] or r["t_done"] is None}
    failed = errored | chk["rejected"]
    return {"attempted": res["attempted"], "failed": len(failed),
            "e2e": res["e2e"], "facts": facts, "checks": chk["numbers"],
            "control": chk["control"]}

