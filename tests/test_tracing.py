"""The program's names for a profiler trace: its jitted stages are named
after their work, its host spans bracket what they dispatched and carry
their arguments, and the engine's paged-decode page counters are exact.
Also: ``admission_lat`` is queue wait alone, stamped when admission takes a
request off the queue."""
import glob
import os
import time

import jax
import numpy as np
import pytest

from repro import routers
from repro.config import FedConfig, ModelConfig, RouterConfig
from repro.core import federated as F
from repro.fed.harvest import HarvestStore
from repro.fed.loop import FedLoop, FedLoopConfig
from repro.serve import engine as eng
from repro.serve import gateway
from repro.serve.engine import EngineConfig, ServeEngine

TINY = ModelConfig(name="tiny-dense-trace", arch_type="dense", n_layers=2,
                   d_model=32, n_heads=2, n_kv_heads=1, d_ff=64, vocab=97,
                   head_dim=16)
PAGED = EngineConfig(slots=2, max_seq=32, chunk=4, page_size=4)


@pytest.fixture(scope="module")
def pool():
    from repro.models import init_params
    return [gateway.PoolModel("tiny", TINY,
                              init_params(jax.random.PRNGKey(0), TINY), 0.1)]


def _prompt(n):
    return np.arange(1, n + 1, dtype=np.int32)


def _host_events(trace_dir):
    """(name, start_ns, end_ns, args) of every host event in the trace."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)) for e in line.events]
    return out


def test_stages_are_named_after_their_work():
    """Each jitted stage's module is ``jit_<name>``; no name holds the
    paged kernel's, which the kernel's roofline matches against
    ``module/op``."""
    fns = {"engine_prefill": eng._prefill_fn(TINY),
           "engine_admit_slot": eng._admit_fn(TINY),
           "engine_write_pages": eng._write_pages_fn(TINY),
           "engine_decode_paged": eng._chunk_paged_fn(TINY, 4),
           "engine_decode": eng._chunk_fn(TINY, 4),
           "engine_draft": eng._draft_fn(TINY, 2),
           "engine_verify": eng._verify_fn(TINY, 2),
           "engine_verify_paged": eng._verify_paged_fn(TINY, 2),
           "fedavg_fit": F._make_scan_fit(lambda p, d, k: (p, 0.0), 2),
           "gateway_route": gateway.RoutedServer._make_route_fn(None)}
    for name, fn in fns.items():
        assert fn.__name__ == name
        assert "paged_decode_attention" not in name
    fit = fns["fedavg_fit"].lower(np.float32(1.0), jax.random.PRNGKey(0),
                                  np.zeros(3, np.float32))
    assert fit.as_text().startswith("module @jit_fedavg_fit")


def test_decode_page_counters_are_exact(pool):
    """Two prompts (5 and 3 tokens) on a paged lane of 2 slots, pages of 4
    positions, chunks of 4: each chunk counts ceil((pos + 4) / 4) pages a
    row and the whole (2, 8) table."""
    e = ServeEngine(pool, PAGED)
    e.submit(0, _prompt(5), 6)
    e.submit(0, _prompt(3), 4)
    e.step()        # rows at pos 5 and 3: 3 + 2 pages; the 3-token one ends
    c = e.counters()
    assert (c["decode_pages_valid"], c["decode_pages_mapped"]) == (5, 16)
    e.step()        # the 5-token row at pos 9: 4 pages
    c = e.counters()
    assert (c["decode_pages_valid"], c["decode_pages_mapped"]) == (9, 32)
    assert not e.busy
    uniform = ServeEngine(pool, EngineConfig(slots=2, max_seq=32, chunk=4,
                                             page_size=None))
    uniform.submit(0, _prompt(5), 6)
    uniform.drain()
    c = uniform.counters()
    assert c["decode_pages_valid"] == c["decode_pages_mapped"] == 0
    assert c["decode_pages_read"] == 0


def test_decode_pages_read_counts_whole_blocks(pool, monkeypatch):
    """With blocks of 2 pages (a 256-byte block target over TINY's 128-byte
    bf16 pages), each chunk counts every slot's pages at its end rounded up to
    whole blocks, at most the 8-page table: the idle slot, at pos 0, still
    costs one block. The count lies between the valid and mapped pages."""
    from repro.kernels import decode_attention
    monkeypatch.setattr(decode_attention, "PAGED_BLOCK_BYTES", 256)
    e = ServeEngine(pool, PAGED)
    e.submit(0, _prompt(5), 6)
    e.submit(0, _prompt(3), 4)
    assert e._lanes[0].block_pages == 2
    e.step()        # 3 and 2 pages at the chunk's end: blocks of 4 and 2
    c = e.counters()
    assert (c["decode_pages_valid"], c["decode_pages_read"],
            c["decode_pages_mapped"]) == (5, 6, 16)
    e.step()        # 4 pages, and the idle slot's 1: blocks of 4 and 2
    c = e.counters()
    assert (c["decode_pages_valid"], c["decode_pages_read"],
            c["decode_pages_mapped"]) == (9, 12, 32)
    long = ServeEngine(pool, EngineConfig(slots=1, max_seq=20, chunk=4,
                                          page_size=4))
    long.submit(0, _prompt(13), 4)
    long.step()     # 5 pages of a 5-page table: blocks of 6, capped at 5
    assert long.counters()["decode_pages_read"] == 5


@pytest.mark.parametrize("page_size", [4, None], ids=["paged", "uniform"])
def test_admission_latency_is_queue_wait(pool, page_size, monkeypatch):
    """The stamp is taken when admission dequeues a request, before its
    prefill runs: a prefill slowed by 0.3 s does not show in it."""
    ecfg = EngineConfig(slots=2, max_seq=32, chunk=4, page_size=page_size)
    e = ServeEngine(pool, ecfg)
    e.submit(0, _prompt(5), 4)
    e.drain()                                   # warm every program
    real = eng._prefill_fn

    def slow(cfg):
        fn = real(cfg)

        def call(*a):
            time.sleep(0.3)
            return fn(*a)
        return call

    monkeypatch.setattr(eng, "_prefill_fn", slow)
    n0 = len(e.admission_lat)
    e.submit(0, _prompt(5), 4)
    t0 = time.perf_counter()
    e.step()
    assert time.perf_counter() - t0 >= 0.3
    lat, = list(e.admission_lat)[n0:]
    assert 0.0 <= lat < 0.3


def test_spans_bracket_the_work_and_carry_their_args(pool, tmp_path):
    """One ``engine.step`` per step, one ``engine.prefill`` per bucket
    group (lane, rows, bucket) and one ``engine.decode`` per lane chunk
    (lane, rows), each inside its step; one ``gateway.route`` per routed
    submit; ``fed.stack`` and ``fed.fit`` inside each ``fed.sync``."""
    router = routers.make("mlp", RouterConfig(d_emb=8, num_models=1,
                                              hidden=(16, 16))).init(
        jax.random.PRNGKey(1))
    srv = gateway.RoutedServer(
        pool, router, engine_cfg=PAGED,
        harvest=HarvestStore(8, capacity=8, clients=range(2)))
    loop = FedLoop(srv, FedConfig(num_clients=2, batch_size=4),
                   key=jax.random.PRNGKey(2),
                   cfg=FedLoopConfig(sync_every=10 ** 9, min_samples=1))
    x = np.ones((8,), np.float32)

    def work():
        rids = [srv.submit("", max_new_tokens=6, client_id=c, x=x,
                           tokenize=lambda _, n=n: _prompt(n)[None])
                for c, n in ((0, 5), (1, 12))]
        for rid in rids:
            srv.report_outcome(rid, 1.0)
        srv.drain(rids)
        loop.sync()

    work()                                      # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    work()
    jax.profiler.stop_trace()
    ev = _host_events(str(tmp_path))

    def named(n):
        return [e for e in ev if e[0] == n]

    steps, pre, dec = (named(f"engine.{k}")
                       for k in ("step", "prefill", "decode"))
    assert len(steps) == 2                      # 6 tokens in chunks of 4
    assert sorted(tuple(sorted(e[3].items())) for e in pre) == [
        (("bucket", 8), ("lane", 0), ("rows", 1)),
        (("bucket", 16), ("lane", 0), ("rows", 1))]
    assert [e[3] for e in dec] == [{"lane": 0, "rows": 2}] * 2
    for _, s, t, _ in pre + dec:
        assert any(a <= s and t <= b for _, a, b, _ in steps)
    assert len(named("gateway.route")) == 2
    (_, s0, s1, _), = named("fed.sync")
    for n in ("fed.stack", "fed.fit"):
        (_, a, b, _), = named(n)
        assert s0 <= a <= b <= s1
