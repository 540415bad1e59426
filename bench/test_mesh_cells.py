"""CPU tests of the four-chip cells: the harness hands a cell's ``chips``
to its driver, which then runs the program on its own mesh; the plain
reference replays a cohort fit; the trace reduction keeps each device's
busy time; and on four virtual CPU devices (a subprocess with
``--xla_force_host_platform_device_count=4``, as ``tests/test_mesh.py``
runs them) a tiny served cell and a tiny cohort sync read ``correct``,
while each fault that such a cell can have makes it read false."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import harness, readers, trace
from bench.test_bench import SEED, _limits, _tiny_root

ROOT = harness.ROOT
MS = 1_000_000


# ------------------------------------------------------- one chip, as before


class _Stop(Exception):
    pass


@pytest.mark.parametrize("mix", ["chat", "fedsync"])
def test_one_chip_builds_the_program_exactly_as_before(mix, tmp_path,
                                                        monkeypatch):
    """With ``chips`` 1 and no new key in the mix, the drivers build
    ``RoutedServer`` and ``FedLoopConfig`` with the arguments they were
    given before four-chip cells existed: no mesh, no cohort."""
    from repro.fed import loop
    from repro.serve import gateway
    from bench import weights
    root = _tiny_root(tmp_path)
    seen = {}
    real_cfg, real_params = loop.FedLoopConfig, weights.model_params
    real_server = gateway.RoutedServer

    def server(pool, router, **kw):
        seen["server"] = sorted(kw)
        if mix == "chat":
            raise _Stop
        return real_server(pool, router, **kw)

    def loop_cfg(**kw):
        seen["loop"] = kw
        raise _Stop

    def params(fam, m, seed, *rest):
        seen.setdefault("weights", []).append(rest)
        return real_params(fam, m, seed, *rest)

    monkeypatch.setattr(gateway, "RoutedServer", server)
    monkeypatch.setattr(loop, "FedLoopConfig", loop_cfg)
    monkeypatch.setattr(weights, "model_params", params)
    with pytest.raises(_Stop):
        harness.run_cell(f"tiny_{mix}", SEED, 1.0, False, root=root,
                         require_chip=False, log=lambda s: None)
    if mix == "chat":
        assert seen["server"] == ["engine_cfg", "harvest"]
        assert seen["weights"] == [(None,), (None,)]
    else:
        assert seen["server"] == ["harvest"]
        assert seen["loop"] == {"sync_every": 10 ** 9, "min_samples": 1}


def test_chips_reach_the_context_and_the_readers(tmp_path, monkeypatch):
    """``run_cell`` hands the cell's ``chips`` to the driver's context and
    to the readers' run; ``mfu`` divides by every chip's peak."""
    from bench.drivers import serve
    root = _four_chip_root(tmp_path)
    seen = []

    def run(ctx):
        seen.append(ctx.chips)
        raise _Stop

    monkeypatch.setattr(serve, "run", run)
    for cell in ("tiny_chat", "tiny4_chat"):
        with pytest.raises(_Stop):
            harness.run_cell(cell, SEED, 1.0, False, root=root,
                             require_chip=False, log=lambda s: None)
    assert seen == [1, 4]
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    facts = {"model_flops": 197e12, "window_span_s": 2.0}
    one = harness.Run(facts=facts, trace=None, peaks=peaks)
    four = harness.Run(facts=facts, trace=None, peaks=peaks, chips=4)
    assert one.chips == 1
    assert readers.mfu(one) == pytest.approx(50.0)
    assert readers.mfu(four) == pytest.approx(12.5)


# ------------------------------------------------- the reference's cohort


def _slab(n, d_rows, d_emb, seed):
    rng = np.random.default_rng(seed)
    counts = rng.integers(d_rows // 4, d_rows + 1, size=n)
    return {"x": rng.normal(size=(n, d_rows, d_emb)).astype(np.float32),
            "m": rng.integers(0, 2, size=(n, d_rows)).astype(np.int32),
            "acc": (rng.random((n, d_rows)) < 0.5).astype(np.float32),
            "cost": rng.random((n, d_rows)).astype(np.float32),
            "w": (np.arange(d_rows)[None] < counts[:, None]).astype(
                np.float32)}


@pytest.mark.parametrize("cohort", [None, 4, 8])
def test_fedref_replays_the_programs_cohort_draw(cohort):
    """``fedref.sync`` with a cohort draws the same clients, keys and
    masks as ``core.federated.fedavg(cohort=)``: at a small size its
    parameters and losses agree with the program's to float32 rounding,
    and a replay without the cohort does not."""
    import jax
    from repro import routers
    from repro.config import FedConfig, RouterConfig
    from bench import fedref, weights
    from bench.drivers import fedsync
    rcfg = RouterConfig(d_emb=32, hidden=(64, 64), num_models=2)
    fcfg = FedConfig(num_clients=12, batch_size=16)
    data = _slab(12, 48, 32, 3)
    state = weights.router_state(rcfg, [1.0, 0.0], [1.0, 0.5], SEED)
    key = jax.random.PRNGKey(7)
    kw = {} if cohort is None else {"cohort": cohort}
    got, hist = routers.fit_federated(
        routers.make("mlp", rcfg, num_models=2, state=state),
        jax.tree.map(np.asarray, data), fcfg, key=key, rounds=3, **kw)
    ref, ref_loss = fedsync.replay(state, [data], [key], fcfg, rcfg, 3,
                                   cohort=cohort)
    before = fedsync._host(state)
    gn = fedref.first_grad(before, data, jax.random.PRNGKey(0),
                           fc_items=fedsync._fc(fcfg), dropout=rcfg.dropout)

    def worst(after):
        """The benchmark's own number: the worst leaf's change gap."""
        return fedsync.gaps(before, after, ref, [], [], gn)[1]

    assert worst(fedsync._host(got.state)) < 0.02
    np.testing.assert_allclose(hist["loss"], ref_loss, rtol=1e-4)
    if cohort is not None:
        other, _ = fedsync.replay(state, [data], [key], fcfg, rcfg, 3)
        assert worst(other) > 0.05


def test_a_cohort_as_large_as_the_stack_is_every_client():
    """As in the program, a cohort no smaller than the stack draws
    nothing: every client trains every round."""
    import jax
    from repro.config import FedConfig, RouterConfig
    from bench import weights
    from bench.drivers import fedsync
    rcfg = RouterConfig(d_emb=16, hidden=(32, 32), num_models=2)
    fcfg = FedConfig(num_clients=4, batch_size=16)
    data = _slab(4, 32, 16, 5)
    state = weights.router_state(rcfg, [1.0, 0.0], [1.0, 0.5], SEED)
    key = jax.random.PRNGKey(1)
    a, la = fedsync.replay(state, [data], [key], fcfg, rcfg, 2, cohort=4)
    b, lb = fedsync.replay(state, [data], [key], fcfg, rcfg, 2)
    assert la == lb
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(x, y)


# ----------------------------------------------- the trace on four devices


def test_four_device_trace_averages_busy_and_keeps_each_device():
    """Four device planes, one of which idles through the window: busy is
    averaged over the four, and each device's own busy time is kept."""
    devs = [f"/device:TPU:{i}" for i in range(4)]
    ops = {d: [("fusion.1", 0, 4 * MS),
               ("all-gather-start.2", 4 * MS, MS),
               ("all-gather-done.2", 5 * MS, MS),
               ("all-reduce.7", 6 * MS, 2 * MS)] for d in devs[:3]}
    ops[devs[3]] = [("fusion.1", 0, MS)]          # idles 9 of 10 ms
    mods = {d: [("jit_fedavg_fit(1)", 0, 8 * MS)] for d in devs}
    red = trace.reduce_events(ops, [("bench.window", 0, 10 * MS, {})],
                              (0, 10 * MS), mods)
    assert red["busy_by_device"] == {
        devs[0]: pytest.approx(0.008), devs[1]: pytest.approx(0.008),
        devs[2]: pytest.approx(0.008), devs[3]: pytest.approx(0.001)}
    assert red["busy_s"] == pytest.approx(0.025 / 4)
    run = harness.Run(facts={"syncs": 2, "step_s": [0.1] * 4},
                      trace=red, peaks=None, chips=4)
    assert readers.idle_share(run) == pytest.approx(37.5)


# ---------------------------------------- four virtual devices, in a child

#: the child: runs one tiny four-chip cell through ``harness.run_cell``,
#: once as it is and once with each planted fault, and prints one JSON
#: line a run
CHILD = r"""
import collections, json, pathlib, sys
import numpy as np
root, cell, faults = pathlib.Path(sys.argv[1]), sys.argv[2], sys.argv[3:]
sys.path[:0] = [".", "src"]
import jax
from bench import harness
from repro.serve import engine, gateway
from repro.fed import loop

seen = {}
real_server, real_cfg = gateway.RoutedServer, loop.FedLoopConfig


def server(*a, **kw):
    if "mesh" in kw:
        seen["mesh"] = dict(kw["mesh"].shape)
    return real_server(*a, **kw)


def loop_cfg(**kw):
    seen["mesh"] = dict(kw["mesh"].shape)
    seen["cohort"] = kw.get("cohort")
    return real_cfg(**kw)


gateway.RoutedServer, loop.FedLoopConfig = server, loop_cfg
hits = collections.Counter()


def patch(owner, name, value):
    # sets owner.name; returns what puts it back
    old = getattr(owner, name)
    setattr(owner, name, value)
    return lambda: setattr(owner, name, old)


def altered_token():
    real = engine.ServeEngine._decode_chunk

    def broken(self, lane):
        real(self, lane)
        for st in lane.active.values():
            st.chunks[-1] = st.chunks[-1].copy()
            st.chunks[-1][-1] = (st.chunks[-1][-1] + 1) % lane.pm.cfg.vocab
            hits["altered"] += 1
    return patch(engine.ServeEngine, "_decode_chunk", broken)


def no_kv_exchange():
    # the prefill's K/V reach only the chip that computed their row: each
    # chip's copy of the replicated pool takes the pages of its own rows,
    # and the other rows' pages stay as they were there
    real = engine._write_pages_fn

    def make(cfg):
        write = real(cfg)

        def partial(pool, kv, pages):
            # prefill cache leaves are (L, B, Hkv, S, hd)
            leaf = jax.tree.leaves(kv)[0]
            B = leaf.shape[1]
            own = {}
            for d, idx in leaf.sharding.devices_indices_map(
                    leaf.shape).items():
                own[d] = np.zeros((B,), bool)
                own[d][idx[1]] = True
            if all(o.all() for o in own.values()):
                return write(pool, kv, pages)
            pages = np.asarray(pages)
            per_dev = {}
            for d, o in own.items():
                p = np.where(o[:, None], pages, 0).astype(pages.dtype)
                copy = jax.tree.map(lambda a: a.copy(), pool)
                per_dev[d] = write(copy, kv, jax.numpy.asarray(p))
            hits["groups"] += 1

            def join(*leaves):
                ref = leaves[0]
                bufs = [next(s.data for s in lf.addressable_shards
                             if s.device == d)
                        for d, lf in zip(per_dev, leaves)]
                return jax.make_array_from_single_device_arrays(
                    ref.shape, ref.sharding, bufs)
            return jax.tree.map(join, *per_dev.values())
        return partial
    return patch(engine, "_write_pages_fn", make)


def state_kept():
    from repro import routers

    def unchanged(router, data, fcfg, *, key, rounds=None, **kw):
        hits["kept"] += 1
        return router, {"loss": [0.0] * (rounds or 1), "eval": []}
    return patch(routers, "fit_federated", unchanged)


def half_rows():
    from repro.fed.harvest import HarvestStore
    real = HarvestStore.as_federated_data

    def half(self, *a, **kw):
        d = real(self, *a, **kw)
        w = np.asarray(d["w"]).copy()
        w[:, w.shape[1] // 2:] = 0.0
        hits["half"] += 1
        return dict(d, w=w)
    return patch(HarvestStore, "as_federated_data", half)


def no_cohort_exchange():
    # the masked psum that gathers the cohort's rows across chips, left
    # out: each chip trains on the cohort rows it holds itself
    from repro.core import federated
    real = federated.jax.lax.psum

    def local(x, axis_name, **kw):
        if axis_name == "clients":
            hits["psum"] += 1
            return x
        return real(x, axis_name, **kw)

    def fresh_fits():
        # the compiled fits are cached: drop them, so that the next fit is
        # traced again, with or without the exchange
        for f in vars(federated).values():
            if hasattr(f, "cache_clear"):
                f.cache_clear()
    fresh_fits()
    undo = patch(federated.jax.lax, "psum", local)

    def restore():
        undo()
        fresh_fits()
    return restore


for fault in [None] + faults:
    restore = globals()[fault]() if fault is not None else None
    res = harness.run_cell(cell, 2**33 + 17, 2.0, False, root=root,
                           require_chip=False, control=fault is None,
                           log=lambda s: None)
    print(json.dumps({"fault": fault, "correct": res["correct"],
                      "attempted": res["attempted"],
                      "failed": res["failed"],
                      "checks": res["checks"],
                      "control": res.get("control", {}),
                      "count": res["device"]["count"], "seen": seen,
                      "hits": dict(hits)}), flush=True)
    if restore is not None:
        restore()
    hits.clear()
"""

SYNC_FAULTS = ["state_kept", "half_rows", "no_cohort_exchange"]


def _child(root, cell, faults):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", CHILD, str(root), cell,
                        *faults], cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-4000:])
    lines = [json.loads(x) for x in p.stdout.splitlines()
             if x.startswith("{")]
    return {x["fault"]: x for x in lines}


#: the tiny four-chip cells: (name, its mix's file, the tiny mix's own
#: keys, the end-to-end metric it reports)
FOUR_CHIP_CELLS = (
    ("tiny4_chat", "chat", {"warm_batches": [1, 2, 4, 8]},
     ("ttft_p95_ms", "tpot_p95_ms")),
    ("tiny4_saturate", "saturate", {
        "warm_batches": [1, 2, 4, 8], "ramp_per_step": 2,
        "prompt_tokens": {"median": 24, "sigma": 0.3, "min": 17, "max": 32},
        "output_tokens": {"median": 16, "sigma": 0.0, "min": 16,
                          "max": 16}}, ("tokens_per_s",)),
    ("tiny4_sync", "fedsync", {"capacity": 256, "fresh_per_client": 16,
                                    "clients": 20, "cohort": 8,
                                    "fresh_clients": 4}, ("sync_ms",)),
)


def _four_chip_root(tmp):
    """The tiny tree with three cells on four chips: the four-chip chat
    and sync mixes, cut for the CPU (8 rows a lane, 2 a chip; 20 clients,
    a cohort of 8), and the closed loop with prompts of one bucket and
    outputs of one length: the rows of a lane finish together and its
    backlog refills them in one group, whose prefill splits over the
    chips whatever the host's speed (the open loop's groups depend on
    it)."""
    root = _tiny_root(tmp)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    conf = root / "bench" / "configs" / "tiny.json"
    c = json.loads(conf.read_text())
    c["limits"] = _limits()
    c["engine"].update(slots=8, pages=64)
    conf.write_text(json.dumps(c))
    shape = {"prompt_tokens": {"median": 24, "sigma": 0.7, "min": 8,
                               "max": 64},
             "output_tokens": {"median": 16, "sigma": 0.5, "min": 8,
                               "max": 40},
             "block": 12, "check": {"per_lane": 3}}
    for name, mix, extra, e2e in FOUR_CHIP_CELLS:
        m = json.loads((ROOT / "bench" / "traffic" / f"{mix}.json")
                       .read_text())
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps({**m, **shape, **extra}))
        spec["workloads"].append({"name": name, "config": "tiny",
                                  "traffic": name, "chips": 4, "why": "x"})
        for metric in spec["end_to_end"]:
            if metric["name"] in e2e:
                metric["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def four_chip_root(tmp_path_factory):
    return _four_chip_root(tmp_path_factory.mktemp("four"))


@pytest.fixture(scope="module")
def served4(four_chip_root):
    return _child(four_chip_root, "tiny4_chat", ["altered_token"])


@pytest.fixture(scope="module")
def closed4(four_chip_root):
    return _child(four_chip_root, "tiny4_saturate", ["no_kv_exchange"])


@pytest.fixture(scope="module")
def synced4(four_chip_root):
    return _child(four_chip_root, "tiny4_sync", SYNC_FAULTS)


def _over(res):
    return [k for k, v in res["control"].items()
            if k in res["checks"] and v > res["checks"][k]["limit"]]


def test_four_chip_served_cell_is_correct_on_a_data_mesh(served4):
    res = served4[None]
    assert res["count"] == 4 and res["seen"]["mesh"] == {"data": 4}
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert _over(res), (res["control"], res["checks"])


def test_four_chip_cohort_sync_is_correct_on_a_client_mesh(synced4):
    res = synced4[None]
    assert res["count"] == 4
    assert res["seen"] == {"mesh": {"clients": 4}, "cohort": 8}
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert _over(res), (res["control"], res["checks"])
    assert res["control"]["no_exchange.param_change_gap"] > \
        res["checks"]["param_change_gap"]["limit"]


@pytest.mark.parametrize("fault,hit", [("altered_token", "altered"),
                                       ("no_kv_exchange", "groups")])
def test_four_chip_served_fault_is_incorrect(served4, closed4, fault, hit):
    """A token altered where the decode produces it (the open loop), and
    the prefill's K/V left on the chip that computed them (the closed
    loop, whose admission groups split over the chips)."""
    res = {**served4, **closed4}[fault]
    assert res["hits"].get(hit, 0) > 0, res["hits"]
    assert not res["correct"] and res["failed"] > 0, res["checks"]


@pytest.mark.parametrize("fault,hit", [("state_kept", "kept"),
                                       ("half_rows", "half"),
                                       ("no_cohort_exchange", "psum")])
def test_four_chip_sync_fault_is_incorrect(synced4, fault, hit):
    res = synced4[fault]
    assert res["hits"].get(hit, 0) > 0, res["hits"]
    assert not res["correct"] and res["failed"] > 0, res["checks"]
