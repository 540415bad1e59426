"""Federated sync driver: the harvest -> fit -> swap path of ``FedLoop``.

Set-up builds what a sync touches and nothing else: the router (drawn from
the seed), a ``RoutedServer`` over the configuration's pool whose engine
never serves (no model weights are made), and the harvest rings, filled
with seeded evaluations. Then the first ``check_syncs`` syncs run through
the window's own call and feed (a fresh batch of evaluations appended to
every client, or to ``fresh_clients`` of them drawn from the seed, then
``FedLoop.sync()``), and the window repeats the same until its seconds
are up. ``sync_ms`` is the window over the syncs in it.

The mix's optional ``cohort`` is the loop's: each FedAvg round trains
that many clients, drawn in the fit. On more than one chip the loop fits
on ``client_mesh(chips)``: each chip holds a block of the clients, and
the program pads the stack with empty clients to a multiple of the chips.

Correctness: the plain reference (``bench.fedref``) replays the first
syncs from the same router state, evaluations, keys and cohort draws; the
compared numbers are each round's loss and, after the last of them, the
norm of every parameter leaf's change.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from bench import fedref, traffic, weights


def _host(tree):
    import jax
    return jax.tree.map(lambda a: np.asarray(a, np.float32), tree)


class Rings:
    """The benchmark's own copy of every client's last ``capacity``
    evaluations, oldest first: what the sync should see."""

    def __init__(self, clients, capacity):
        self.rows = [collections.deque(maxlen=capacity)
                     for _ in range(clients)]

    def add(self, c, batch):
        for i in range(len(batch["m"])):
            self.rows[c].append(tuple(batch[k][i] for k in
                                      ("x", "m", "acc", "cost")))

    def stack(self, capacity, d):
        out = {"x": np.zeros((len(self.rows), capacity, d), np.float32),
               "m": np.zeros((len(self.rows), capacity), np.int32),
               "acc": np.zeros((len(self.rows), capacity), np.float32),
               "cost": np.zeros((len(self.rows), capacity), np.float32),
               "w": np.zeros((len(self.rows), capacity), np.float32)}
        for c, ring in enumerate(self.rows):
            if not ring:
                continue
            k = len(ring)
            for key, col in zip(("x", "m", "acc", "cost"), zip(*ring)):
                out[key][c, :k] = np.stack(col)
            out["w"][c, :k] = 1.0
        return out


def run(ctx) -> dict:
    import jax
    import repro.sharding as shd
    from repro import routers
    from repro.config import FedConfig, RouterConfig
    from repro.fed.harvest import HarvestStore
    from repro.fed.loop import FedLoop, FedLoopConfig
    from repro.serve.gateway import PoolModel, RoutedServer

    cfg, mix, seed = ctx.config, ctx.traffic, ctx.seed
    r = cfg["router"]
    costs = [lane["cost_per_token"] for lane in cfg["pool"]]
    quality = [lane.get("quality_logit", 1.0 - i)
               for i, lane in enumerate(cfg["pool"])]
    n, cap = mix["clients"], mix["capacity"]
    fcfg = FedConfig(num_clients=n)
    loop_kw = {}
    if "cohort" in mix:
        loop_kw["cohort"] = int(mix["cohort"])
    if ctx.chips > 1:
        loop_kw["mesh"] = shd.client_mesh(ctx.chips)
    # the stack as the program fits it: padded with empty clients (no
    # rows, no weight) to a multiple of the chips
    n_stack = -(-n // ctx.chips) * ctx.chips
    fresh_n = mix.get("fresh_clients")
    pick = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 17])

    def fresh_ids():
        """The clients that append before a sync: all of them, or
        ``fresh_clients`` drawn from the seed."""
        if fresh_n is None:
            return range(n)
        return np.sort(pick.choice(n, size=int(fresh_n), replace=False))

    with ctx.phase("router and harvest"):
        rcfg = RouterConfig(d_emb=r["d_emb"], hidden=tuple(r["hidden"]),
                            num_models=len(costs))
        state0 = weights.router_state(rcfg, quality, costs, seed,
                                      r.get("head_scale", 1.0))
        models = [cfg if lane["model"] == "self" else cfg[lane["model"]]
                  for lane in cfg["pool"]]
        pool = [PoolModel(f"lane{i}", ctx.family(m).model_config(m), None,
                          lane["cost_per_token"])
                for i, (m, lane) in enumerate(zip(models, cfg["pool"]))]
        srv = RoutedServer(pool, routers.make(r["family"], rcfg,
                                              num_models=len(costs),
                                              state=state0),
                           harvest=HarvestStore(r["d_emb"], capacity=cap,
                                                clients=range(n)))
        evals = traffic.Evaluations(mix, seed, costs, r["d_emb"])
        rings = Rings(n_stack, cap)
        key0 = jax.random.fold_in(jax.random.PRNGKey(3), seed & 0x7FFFFFFF)
        key0 = jax.random.fold_in(key0, seed >> 31)
        loop = FedLoop(srv, fcfg, key=key0,
                       cfg=FedLoopConfig(sync_every=10 ** 9, min_samples=1,
                                         **loop_kw))

        def feed(k, clients=range(n)):
            for c in clients:
                b = evals.rows(c, k)
                rings.add(c, b)
                for i in range(k):
                    srv.harvest.record(c, b["x"][i], int(b["m"][i]),
                                       float(b["acc"][i]),
                                       float(b["cost"][i]))

        feed(cap)
        before = _host(srv.router.state)
    fresh = mix["fresh_per_client"]
    stacks, losses = [], []
    with ctx.phase("first syncs"):
        for _ in range(mix["check_syncs"]):
            feed(fresh, fresh_ids())
            stacks.append(rings.stack(cap, r["d_emb"]))
            losses.append(list(loop.sync()["loss"]))
        after = _host(srv.router.state)
    syncs = 0
    with ctx.window() as w:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < ctx.seconds:
            with ctx.span("bench.harvest"):
                feed(fresh, fresh_ids())
            with ctx.span("bench.sync"):
                loop.sync()
            syncs += 1
        w.end()
    sync_ms = 1e3 * w.seconds / syncs
    ctx.log(f"window: {syncs} syncs in {w.seconds:.4f} s, {sync_ms:.4f} ms "
            "a sync (window over syncs)")
    ctx.read_memory()
    del srv, loop
    t1 = time.perf_counter()
    numbers, control, rejected = check(ctx, before, after, stacks, losses,
                                       key0, fcfg, rcfg,
                                       loop_kw.get("cohort"))
    ctx.log(f"comparison with the reference: {time.perf_counter() - t1:.3f}"
            " s")
    return {"attempted": syncs + len(stacks), "failed": rejected,
            "e2e": {"sync_ms": sync_ms},
            "facts": {"syncs": syncs, "window_span_s": w.seconds},
            "checks": numbers, "control": control}


def _keys(key0, k):
    """The keys FedLoop hands its first ``k`` syncs: one split each."""
    import jax
    out, key = [], key0
    for _ in range(k):
        key, sub = jax.random.split(key)
        out.append(sub)
    return out


def _fc(fcfg) -> tuple:
    """The federated settings the reference reads, hashable."""
    return tuple(sorted(dict(lr=fcfg.lr, batch_size=fcfg.batch_size,
                             participation=fcfg.participation,
                             weight_decay=fcfg.weight_decay,
                             clip_norm=fcfg.clip_norm).items()))


def replay(state, stacks, keys, fcfg, rcfg, rounds, dtype="float32",
           cohort=None, exchange_left_out=0):
    """The reference's syncs: (state after the last, per-round losses)."""
    fc = _fc(fcfg)
    losses = []
    for data, key in zip(stacks, keys):
        steps = max(1, -(-data["x"].shape[1] // fcfg.batch_size)) * \
            fcfg.local_epochs
        state, l = fedref.sync(state, data, key, fc_items=fc, rounds=rounds,
                               steps=steps, dropout=rcfg.dropout,
                               dtype=dtype, cohort=cohort,
                               exchange_left_out=exchange_left_out)
        losses.extend(np.asarray(l, np.float64).tolist())
    return _host(state), losses


def gaps(before, after, ref_after, losses, ref_losses, grad_norms):
    """(per-round relative loss gaps, worst leaf's change gap, leaves left
    out): over the leaves that move, the gap between the program's and the
    reference's norm of the leaf's change, over the reference's norm of
    that leaf's or of the median leaf's change, whichever is larger."""
    import jax
    lg = [abs(a - b) / abs(b) for a, b in zip(losses, ref_losses)]
    d_p = [float(np.linalg.norm(a - b)) for a, b in zip(
        jax.tree.leaves(after), jax.tree.leaves(before))]
    d_r = [float(np.linalg.norm(a - b)) for a, b in zip(
        jax.tree.leaves(ref_after), jax.tree.leaves(before))]
    g = jax.tree.leaves(grad_norms)
    keep = [i for i in range(len(g)) if g[i] >= 1e-3 * np.median(g)]
    med = float(np.median([d_r[i] for i in keep]))
    pg = max(abs(d_p[i] - d_r[i]) / max(d_r[i], med) for i in keep)
    return lg, pg, len(g) - len(keep)


def check(ctx, before, after, stacks, losses, key0, fcfg, rcfg, cohort=None):
    """The compared numbers (with their limits), and the control's."""
    import jax
    flat = [x for per in losses for x in per]
    rounds = len(losses[0])
    keys = _keys(key0, len(stacks))
    ref_after, ref_losses = replay(before, stacks, keys, fcfg, rcfg, rounds,
                                   cohort=cohort)
    gn = fedref.first_grad(before, stacks[0], jax.random.PRNGKey(0),
                           fc_items=_fc(fcfg), dropout=rcfg.dropout)
    lg, pg, left_out = gaps(before, after, ref_after, flat, ref_losses, gn)
    # compared: the numbers the configuration gives a limit; the first
    # round's loss is read and logged but has none (its control does not
    # read three times the program's)
    lim = ctx.config.get("limits", {}).get("sync", {})
    numbers = {k: (v, lim[k]) for k, v in (("first_loss_gap", lg[0]),
                                           ("param_change_gap", pg))
               if k in lim}
    ctx.log(f"check: {len(stacks)} syncs ({len(flat)} rounds) replayed; "
            f"loss gap per round {[float(f'{g:.3g}') for g in lg]}, worst "
            f"leaf's change gap {pg:.6g}; {left_out} leaves left out by the "
            "gradient rule")
    control = {}
    if ctx.control:
        c_after, c_losses = replay(before, stacks, keys, fcfg, rcfg, rounds,
                                   dtype="bfloat16", cohort=cohort)
        clg, cpg, _ = gaps(before, c_after, ref_after, c_losses, ref_losses,
                           gn)
        # a fault planted in the reference put in the program's place: half
        # of every client's rows left out, the mean taken over the rest
        half = [dict(d, w=np.where(np.arange(d["w"].shape[1]) <
                                   d["w"].shape[1] // 2, d["w"], 0.0))
                for d in stacks]
        h_after, h_losses = replay(before, half, keys, fcfg, rcfg, rounds,
                                   cohort=cohort)
        hlg, hpg, _ = gaps(before, h_after, ref_after, h_losses, ref_losses,
                           gn)
        control = {"first_loss_gap": clg[0], "param_change_gap": cpg,
                   "half_batch.first_loss_gap": hlg[0],
                   "half_batch.param_change_gap": hpg}
        if cohort is not None and ctx.chips > 1:
            # a fault planted in the reference: the cohort's exchange
            # between chips left out
            x_after, x_losses = replay(before, stacks, keys, fcfg, rcfg,
                                       rounds, cohort=cohort,
                                       exchange_left_out=ctx.chips)
            xlg, xpg, _ = gaps(before, x_after, ref_after, x_losses,
                               ref_losses, gn)
            control.update({"no_exchange.first_loss_gap": xlg[0],
                            "no_exchange.param_change_gap": xpg})
            ctx.log(f"control (exchange between chips left out): loss gap "
                    f"per round {[float(f'{g:.3g}') for g in xlg]}, change "
                    f"gap {xpg:.6g}")
        ctx.log(f"control (bfloat16): loss gap per round "
                f"{[float(f'{g:.3g}') for g in clg]}, change gap {cpg:.6g}; "
                f"half of the rows left out: loss gap per round "
                f"{[float(f'{g:.3g}') for g in hlg]}, change gap {hpg:.6g}")
    # a gap can not be laid to one sync: every replayed sync fails with it
    bad = len(stacks) if any(l is not None and v > l
                             for v, l in numbers.values()) else 0
    return numbers, control, bad
