"""Cross-silo mesh execution: the sharded federated fit, the sharded
serve engine, and the mesh-aware FedLoop must match the single-device
paths on a fixed key — across mesh shapes — with donation audited and zero
retraces once warm. Subprocesses force the device count (XLA_FLAGS must be
set before jax initializes — never in this process).

The mesh fit's parity contract (``repro.core.federated``, shared with
``chip_smoke.py --chips 4``): params within ``MESH_PARAM_ATOL`` absolute
and the per-round loss within ``MESH_LOSS_RTOL`` relative of the
in-process fit, on every mesh shape. The loss is a diagnostic whose
float reduction the compiler orders its own way in the shard_map program
— even on a 1-device mesh it differs from the in-process fit by an ulp or
two — so it is never pinned bitwise. On the CPU backend the params are
additionally bit-for-bit whenever each device trains >= 2 clients; with
one client per device XLA lowers the batch-of-1 client update through a
different dot-reduction order.
"""
import os
import subprocess
import sys

import pytest

ENV = {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"}


def _run(code: str, devices: int = 8, timeout: int = 560, **env):
    full = (f"import os; os.environ['XLA_FLAGS']="
            f"'--xla_force_host_platform_device_count={devices}';" + code)
    out = subprocess.run([sys.executable, "-c", full], capture_output=True,
                         text=True, timeout=timeout, env={**ENV, **env})
    assert out.returncode == 0, (out.stdout[-1000:], out.stderr[-3000:])
    return out.stdout


_FIT_PRELUDE = """
import jax, jax.numpy as jnp, numpy as np
import repro.sharding as shd
from repro.config import FedConfig, RouterConfig
from repro.core import federated as F

def slab(N, D, d, M, seed=0):
    rng = np.random.default_rng(seed)
    counts = rng.integers(1, D + 1, size=N)
    return {"x": rng.normal(size=(N, D, d)).astype(np.float32),
            "m": rng.integers(0, M, size=(N, D)).astype(np.int32),
            "acc": (rng.random((N, D)) < 0.5).astype(np.float32),
            "cost": rng.random((N, D)).astype(np.float32),
            "w": (np.arange(D)[None] < counts[:, None]).astype(np.float32)}

def maxdiff(a, b):
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

def assert_mesh_parity(ref, ref_loss, got, got_loss, exact_params):
    dp, dl = F.mesh_fit_gap(ref, ref_loss, got, got_loss)
    assert dp <= F.MESH_PARAM_ATOL and dl <= F.MESH_LOSS_RTOL, (dp, dl)
    if exact_params:
        assert dp == 0.0, dp

N, D, d, M = 8, 8, 8, 3
rcfg = RouterConfig(d_emb=d, num_models=M, hidden=(16,))
fcfg = FedConfig(num_clients=N, batch_size=4, lr=1e-2)
data = slab(N, D, d, M)
key = jax.random.PRNGKey(0)
"""


@pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
def test_fit_parity_across_mesh_shapes(n_dev):
    """Plain FedAvg on an n_dev-device clients mesh against the in-process
    fit: the parity contract on every shape, bit-for-bit params while
    each device trains >= 2 of the 8 clients (n_dev < 8)."""
    out = _run(_FIT_PRELUDE + f"""
ref, ref_hist = F.fedavg(key, data, rcfg, fcfg, rounds=3)
mesh = shd.client_mesh({n_dev})
dsh = shd.shard_clients(data, mesh)
got, hist = F.fedavg(key, dsh, rcfg, fcfg, rounds=3, mesh=mesh)
assert_mesh_parity(ref, ref_hist["loss"], got, hist["loss"],
                   exact_params={n_dev} < 8)
print("FIT_PARITY_OK")
""")
    assert "FIT_PARITY_OK" in out


def test_fit_parity_aggregators_and_cohort():
    """Every Aggregator strategy — including the sort-based and mask-based
    ones (trimmed-mean, median, secure-agg, norm-clip, buffered-async
    with staleness) — and cohort sampling run on the mesh bit-for-bit the
    in-process round, because the mesh round gathers the full update
    stack in global client order and aggregates replicated."""
    out = _run(_FIT_PRELUDE + """
from repro.fed.aggregators import (BufferedAsyncAggregator,
                                   MedianAggregator, NormClipAggregator,
                                   SecureAggAggregator,
                                   TrimmedMeanAggregator)
N4 = 4
data4 = slab(N4, 4, d, M, seed=1)
fcfg4 = FedConfig(num_clients=N4, batch_size=4, lr=1e-2)
mesh = shd.client_mesh(2)
d4 = shd.shard_clients(data4, mesh)
cases = [dict(aggregator=TrimmedMeanAggregator(trim_frac=0.25)),
         dict(aggregator=MedianAggregator()),
         dict(aggregator=SecureAggAggregator(scale=0.1)),
         dict(aggregator=NormClipAggregator(clip=0.5)),
         dict(aggregator=BufferedAsyncAggregator(staleness_alpha=0.5),
              staleness=np.arange(N4, dtype=np.float32)),
         dict(dp_sigma=1e-3)]
for kw in cases:
    ref, rh = F.fedavg(key, data4, rcfg, fcfg4, rounds=2, **kw)
    got, gh = F.fedavg(key, d4, rcfg, fcfg4, rounds=2, mesh=mesh, **kw)
    assert maxdiff(ref, got) == 0.0, kw
    # params are bit-for-bit; the loss DIAGNOSTIC is psum-reduced on the
    # mesh, so its float summation order may differ by rounding.
    np.testing.assert_allclose(rh["loss"], gh["loss"], atol=1e-6)
# cohort sampling: the masked-psum cohort exchange is bit-for-bit as long
# as each device trains >= 2 cohort clients (1-per-device hits the same
# batch-of-1 dot lowering as the degenerate full fit).
dsh8 = shd.shard_clients(data, mesh)
ref, _ = F.fedavg(key, data, rcfg, fcfg, rounds=2, cohort=4)
got, _ = F.fedavg(key, dsh8, rcfg, fcfg, rounds=2, cohort=4, mesh=mesh)
assert maxdiff(ref, got) == 0.0
print("AGG_PARITY_OK")
""", timeout=560)
    assert "AGG_PARITY_OK" in out


def test_fit_families_parity_on_mesh():
    """The mf (loss_fn) and kmeans (one-shot protocol) families ride the
    mesh bit-for-bit through the unified fit entry point."""
    out = _run(_FIT_PRELUDE + """
from repro import routers
rcfg_f = RouterConfig(d_emb=d, num_models=M, hidden=(16,), mf_rank=4,
                      k_local=2, k_global=3)
mesh = shd.client_mesh(4)
dsh = shd.shard_clients(data, mesh)
for family in ("mf", "kmeans"):
    r = routers.make(family, rcfg_f)
    r = r.init(jax.random.PRNGKey(1)) if family == "mf" else r
    ref, _ = routers.fit_federated(r, data, fcfg, key=key, rounds=2)
    got, _ = routers.fit_federated(r, dsh, fcfg, key=key, rounds=2,
                                   mesh=mesh)
    assert maxdiff(ref.state, got.state) == 0.0, family
print("FAMILY_PARITY_OK")
""")
    assert "FAMILY_PARITY_OK" in out


def test_mesh_fit_zero_retrace_and_cohort_redraws():
    """The compiled mesh fit is built once: repeat fits — including fresh
    cohort draws from different keys — append nothing to FIT_TRACE_LOG."""
    out = _run(_FIT_PRELUDE + """
mesh = shd.client_mesh(4)
dsh = shd.shard_clients(data, mesh)
F.fedavg(key, dsh, rcfg, fcfg, rounds=2, cohort=4, mesh=mesh)
n0 = len(F.FIT_TRACE_LOG)
for s in range(3):
    F.fedavg(jax.random.PRNGKey(s + 1), dsh, rcfg, fcfg, rounds=2,
             cohort=4, mesh=mesh)
assert len(F.FIT_TRACE_LOG) == n0, F.FIT_TRACE_LOG
print("RETRACE_OK")
""")
    assert "RETRACE_OK" in out


def test_mesh_fit_donation_audit():
    """Memory contract of the mesh fit, in bytes. (1) The compiled fit
    sees the slab SHARDED: per-device argument bytes are ~slab/n_dev, and
    temp memory never materializes a second copy of the slab — it stays
    flat as the slab grows 4x (the fit's fixed working set, ~53 KB for
    this router on the installed XLA, exceeds the smallest slab itself).
    (2) ``donate_data=True`` consumes the sharded slab — its buffers are
    deleted after the fit and total ``jax.live_arrays()`` bytes drop by
    the slab, so a per-sync harvest stack doesn't linger until GC."""
    out = _run(_FIT_PRELUDE + """
from repro.core import mlp_router as R
live = lambda: sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.live_arrays())
Nb = 16
fcfgb = FedConfig(num_clients=Nb, batch_size=16, lr=1e-2)
mesh = shd.client_mesh(4)
fit = F._scan_fit_cached(rcfg, fcfgb, "adamw", 4, False, 0.0, None, None,
                         None, mesh, 2, True)
temp = {}
for Db in (64, 256):
    dsh = shd.shard_clients(jax.tree.map(jnp.asarray, slab(Nb, Db, d, M,
                                                           seed=2)), mesh)
    slab_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                     for a in jax.tree.leaves(dsh))
    ma = fit.lower(R.init_mlp_router(key=key, cfg=rcfg), key,
                   dsh).compile().memory_analysis()
    assert ma.argument_size_in_bytes < slab_bytes // 2, (
        ma.argument_size_in_bytes, slab_bytes)
    temp[Db] = (ma.temp_size_in_bytes, slab_bytes)
(t64, s64), (t256, s256) = temp[64], temp[256]
assert t256 - t64 < (s256 - s64) // 2, temp

dsh = shd.shard_clients(jax.tree.map(jnp.asarray, slab(Nb, 64, d, M,
                                                       seed=2)), mesh)
slab_bytes = s64

base = live()
params, _ = F.fedavg(key, dsh, rcfg, fcfgb, rounds=2, mesh=mesh,
                     donate_data=True)
jax.block_until_ready(params)
assert all(a.is_deleted() for a in jax.tree.leaves(dsh))
after = live()
assert after <= base - slab_bytes // 2, (base, after, slab_bytes)
print("DONATION_OK")
""")
    assert "DONATION_OK" in out


_ENGINE_PRELUDE = """
import jax, jax.numpy as jnp, numpy as np
import repro.sharding as shd
from repro import routers
from repro.config import ModelConfig, RouterConfig
from repro.models import init_params
from repro.serve import gateway
from repro.serve.engine import EngineConfig, TRACE_LOG

TINY = ModelConfig(name="tiny-dense-mesh", arch_type="dense", n_layers=2,
                   d_model=32, n_heads=2, n_kv_heads=1, d_ff=64, vocab=97,
                   head_dim=16)

def make_server(mesh, ecfg):
    router = routers.make(
        "kmeans", RouterConfig(d_emb=16, num_models=1),
        state={"centroids": jnp.zeros((1, 16)),
               "A": jnp.array([[0.9]]), "C": jnp.array([[0.1]]),
               "n": jnp.ones((1, 1))})
    pool = [gateway.PoolModel("tiny", TINY,
                              init_params(jax.random.PRNGKey(0), TINY),
                              0.1)]
    return gateway.RoutedServer(pool, router, engine_cfg=ecfg, mesh=mesh)

PROMPTS = ["the quick brown fox", "jumps over", "a lazy dog today ok",
           "one two three", "counting to five now", "zig zag", "rome as"]
MAXN = [5, 3, 8, 6, 4, 7, 5]

def run(server):
    rids = [server.submit(p, lam=0.5, max_new_tokens=m)
            for p, m in zip(PROMPTS, MAXN)]
    done = server.drain()
    return [done[r].tolist() for r in rids]
"""


def test_engine_token_parity_sharded_vs_solo():
    """Slot-parallel ("data") and mixed ("data","heads") meshes emit
    tokens bit-identical to the solo engine on uniform AND paged pools,
    and a warm mesh engine decodes with zero retraces."""
    out = _run(_ENGINE_PRELUDE + """
for page_size in (None, 16):
    ecfg = EngineConfig(slots=8, max_seq=64, chunk=4, page_size=page_size)
    solo = run(make_server(None, ecfg))
    for mk in (lambda: shd.data_mesh(2), lambda: shd.data_mesh(8),
               lambda: shd.make_mesh({"data": 2, "heads": 1})):
        assert run(make_server(mk(), ecfg)) == solo, (page_size, mk)
srv = make_server(shd.data_mesh(8),
                  EngineConfig(slots=8, max_seq=64, chunk=4))
run(srv)
n0 = len(TRACE_LOG)
run(srv)
assert len(TRACE_LOG) == n0
print("ENGINE_PARITY_OK")
""")
    assert "ENGINE_PARITY_OK" in out


def test_engine_decode_kernels_on_mesh():
    """With the Pallas decode kernels selected (interpreted on the CPU),
    a mesh engine runs them per device under shard_map — XLA cannot
    partition a Mosaic kernel — and stays token-identical to the solo
    engine on uniform and paged pools."""
    out = _run(_ENGINE_PRELUDE + """
from repro.kernels import ops
assert ops._default_impl() == "pallas"
for page_size in (None, 16):
    ecfg = EngineConfig(slots=8, max_seq=64, chunk=4, page_size=page_size)
    solo = run(make_server(None, ecfg))
    assert run(make_server(shd.data_mesh(4), ecfg)) == solo, page_size
print("KERNEL_MESH_OK")
""", devices=4, REPRO_KERNELS="pallas")
    assert "KERNEL_MESH_OK" in out


def test_engine_spec_decode_on_mesh():
    """Speculative decode (draft pools + verify) on a sharded engine stays
    bit-identical to the solo speculative engine."""
    out = _run(_ENGINE_PRELUDE + """
ecfg = EngineConfig(slots=4, max_seq=64, chunk=4, page_size=None, spec_k=3)
solo = run(make_server(None, ecfg))
assert run(make_server(shd.data_mesh(2), ecfg)) == solo
print("SPEC_PARITY_OK")
""", devices=2)
    assert "SPEC_PARITY_OK" in out


def test_fedloop_mesh_sync_and_checkpoint():
    """FedLoopConfig(mesh=...): the mesh sync holds the mesh parity
    contract against the solo sync; save() under a live mesh restores
    bit-for-bit into a loop on a DIFFERENT mesh shape (state checkpoints
    as host arrays, placement is per-fit)."""
    out = _run("""
import pathlib, tempfile
import jax, jax.numpy as jnp, numpy as np
import repro.sharding as shd
from repro import routers
from repro.config import FedConfig, ModelConfig, RouterConfig
from repro.fed.harvest import HarvestStore
from repro.fed.loop import FedLoop, FedLoopConfig
from repro.models import init_params
from repro.serve.engine import EngineConfig
from repro.serve.gateway import PoolModel, RoutedServer

TINY = ModelConfig(name="fedloop-tiny", arch_type="dense", n_layers=2,
                   d_model=32, n_heads=2, n_kv_heads=1, d_ff=64, vocab=97,
                   head_dim=16, dtype="float32")
D_EMB, N_CLIENTS, CAP = 8, 3, 32
RCFG = RouterConfig(d_emb=D_EMB, num_models=2, hidden=(16, 16),
                    dropout=0.0)
FCFG = FedConfig(num_clients=N_CLIENTS, participation=1.0, batch_size=16,
                 lr=3e-3)

def make_loop(mesh, engine_mesh=None):
    params = init_params(jax.random.PRNGKey(0), TINY)
    pool = [PoolModel("m0", TINY, params, 0.1),
            PoolModel("m1", TINY, params, 0.5)]
    router = routers.make("mlp", RCFG).init(jax.random.PRNGKey(1))
    harvest = HarvestStore(D_EMB, capacity=CAP, clients=range(N_CLIENTS))
    srv = RoutedServer(pool, router, harvest=harvest,
                       engine_cfg=EngineConfig(slots=4, max_seq=32,
                                               chunk=4, page_size=8),
                       mesh=engine_mesh)
    return srv, FedLoop(srv, FCFG, key=jax.random.PRNGKey(7),
                        cfg=FedLoopConfig(sync_every=10**9,
                                          rounds_per_sync=2,
                                          min_samples=1, mesh=mesh))

def drive(srv, loop, n):
    rng = np.random.default_rng(0)
    for i in range(n):
        x = rng.normal(size=(D_EMB,)).astype(np.float32)
        rid = srv.submit("three word prompt", lam=0.5, max_new_tokens=4,
                         client_id=i % N_CLIENTS, x=x)
        m = srv.routed_model(rid)
        srv.report_outcome(rid, float(rng.random() < 0.4 + 0.3 * m),
                           0.1 + 0.4 * m)
        loop.step()
    loop.drain()

from repro.core import federated as F

srv_m, loop_m = make_loop(shd.client_mesh(3),
                          engine_mesh=shd.data_mesh(2))
drive(srv_m, loop_m, 9)
hm = loop_m.sync()
srv_s, loop_s = make_loop(None)
drive(srv_s, loop_s, 9)
hs = loop_s.sync()
# the mesh parity contract (one client per device: not bitwise)
dp, dl = F.mesh_fit_gap(loop_s.server.router.state, hs["loss"],
                        loop_m.server.router.state, hm["loss"])
assert dp <= F.MESH_PARAM_ATOL and dl <= F.MESH_LOSS_RTOL, (dp, dl)

p = pathlib.Path(tempfile.mkdtemp()) / "loop.ckpt"
loop_m.save(p)
srv_r, loop_r = make_loop(shd.client_mesh(1))
loop_r.restore(p)
for a, b in zip(jax.tree.leaves(loop_m.server.router.state),
                jax.tree.leaves(loop_r.server.router.state)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
drive(srv_r, loop_r, 3)      # the restored loop syncs on ITS mesh shape
loop_r.sync()
print("FEDLOOP_MESH_OK")
""", devices=6)
    assert "FEDLOOP_MESH_OK" in out


def test_mesh_validation_errors():
    """Ragged stacks, non-dividing cohorts, and pytree-knob requests fail
    with actionable errors instead of silently falling back; padding via
    pad_client_axis makes a ragged stack mesh-eligible."""
    out = _run(_FIT_PRELUDE + """
mesh = shd.client_mesh(4)
rag = slab(6, D, d, M, seed=3)
try:
    shd.shard_clients(rag, mesh)
    raise SystemExit("ragged stack placed")
except ValueError as e:
    assert "pad_client_axis" in str(e)
padded, stal = F.pad_client_axis(rag, 4, np.ones((6,), np.float32))
assert padded["x"].shape[0] == 8 and stal.shape[0] == 8
assert float(padded["w"][6:].sum()) == 0.0
dsh = shd.shard_clients(padded, mesh)
fcfg8 = FedConfig(num_clients=8, batch_size=4, lr=1e-2)
F.fedavg(key, dsh, rcfg, fcfg8, rounds=1, mesh=mesh)
try:
    F.fedavg(key, dsh, rcfg, fcfg8, rounds=1, mesh=mesh, cohort=2)
    raise SystemExit("cohort=2 on a 4-device mesh fit")
except ValueError as e:
    assert "cohort" in str(e)
try:
    F.fedavg(key, dsh, rcfg, fcfg8, rounds=1, mesh=mesh,
             freeze={"layers": True})
    raise SystemExit("freeze on the mesh path fit")
except ValueError as e:
    assert "mesh path supports only" in str(e)
print("VALIDATION_OK")
""")
    assert "VALIDATION_OK" in out
