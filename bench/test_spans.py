"""CPU tests of the program's spans, programs and counters as the
harness reads them: ``bench.trace``'s reduction of them on synthetic
events, ``bench.spans``' readers on hand-made numbers, and traced tiny
runs of a served and a sync cell through ``harness.run_cell`` whose span
counts agree exactly with the steps, submits and syncs the loops count."""
from __future__ import annotations

import pytest

from bench import harness, spans, trace
from bench.test_bench import SEED, _tiny_root

MS = 1_000_000


class _Run:
    def __init__(self, facts, tr):
        self.facts, self.trace = facts, tr


def _events():
    """A 10 ms window: one step whose prefill and decode leave three idle
    gaps on the device, then a fit and a route that outlast the window."""
    ops = {"/device:TPU:0": [
        ("fusion.1", 0, 2 * MS), ("while.3", 3 * MS, 2 * MS),
        ("while.3", int(5.5 * MS), int(1.5 * MS)), ("fusion.2", 9 * MS,
                                                     2 * MS)]}
    modules = {"/device:TPU:0": [
        ("jit_engine_prefill(3)", 0, 2 * MS),
        ("jit_engine_decode_paged(7)", 3 * MS, 4 * MS),
        ("jit_engine_write_pages(5)", int(7.5 * MS), MS // 2),
        ("jit_fedavg_fit(1)", 9 * MS, 2 * MS)]}        # 1 ms in the window
    host = [("bench.window", 0, 10 * MS, {}), ("bench.step", 0, 8 * MS, {}),
            ("engine.step", MS // 2, 7 * MS, {}),
            ("engine.prefill", MS, int(1.2 * MS),
             {"lane": 0, "rows": 2, "bucket": 64}),
            ("engine.decode", 3 * MS, int(4.2 * MS), {"lane": 1, "rows": 8}),
            ("engine.prefill", -2 * MS, MS, {}),        # before the window
            ("fed.fit", int(9.2 * MS), int(0.8 * MS), {}),
            ("gateway.route", int(9.5 * MS), MS, {})]
    return ops, modules, host, (0, 10 * MS)


def _reduce(ops, modules, host, window):
    return trace.reduce_events(ops, host, window, modules)


def test_program_spans_and_modules_are_clipped_to_the_window():
    ops, modules, host, window = _events()
    red = _reduce(ops, modules, host, window)
    assert [(n, s) for n, s, _, _ in red["spans"]] == [
        ("engine.step", pytest.approx(0.0005)),
        ("engine.prefill", pytest.approx(0.001)),
        ("engine.decode", pytest.approx(0.003)),
        ("fed.fit", pytest.approx(0.0092)),
        ("gateway.route", pytest.approx(0.0095))]
    assert red["spans"][1][3] == {"lane": 0, "rows": 2, "bucket": 64}
    assert red["spans"][4][2] == pytest.approx(0.001)  # not clipped
    assert red["module_s"] == {
        "jit_engine_prefill(3)": pytest.approx(0.002),
        "jit_engine_decode_paged(7)": pytest.approx(0.004),
        "jit_engine_write_pages(5)": pytest.approx(0.0005),
        "jit_fedavg_fit(1)": pytest.approx(0.001)}
    # busy time, operations and gap lengths are as the harness's
    # reduction of its own spans alone gives them
    base = trace.reduce_events(ops, [h for h in host
                                     if h[0].startswith("bench.")], window)
    for k in ("busy_s", "window_s", "op_s", "device_ops"):
        assert red[k] == base[k], k
    assert [g[1] for g in red["idle_gaps"]] == [g[1] for g in
                                                base["idle_gaps"]]
    assert base["spans"] == [] and base["module_s"] == {}


def test_gaps_are_named_by_the_innermost_span_of_either_kind():
    red = _reduce(*_events())
    assert red["idle_gaps"] == [
        ["bench.step", pytest.approx(0.002)],       # 7-9 ms: no program span
        ["engine.step", pytest.approx(0.001)],      # 2-3 ms: the step's own
        ["engine.decode", pytest.approx(0.0005)]]   # 5-5.5 ms


def test_self_time_counts_nested_children_once():
    assert trace.self_time((0, 10), [(2, 4), (3, 5), (9, 12), (-1, 1),
                                     (20, 30)]) == 5
    assert trace.self_time((0, 10), []) == 10


def test_readers_on_hand_made_numbers():
    run = _Run({"step_s": [0.5, 0.5], "syncs": 2,
                "counters": {"decode_pages_valid": 9,
                             "decode_pages_mapped": 32,
                             "decode_pages_read": 12}},
               _reduce(*_events()))
    assert spans.route_ms(run) == pytest.approx(1.0)
    # 7 ms step less 1.2 ms of prefill and 4.2 ms of decode
    assert spans.step_host_ms(run) == pytest.approx(1.6)
    assert spans.prefill_ms(run) == pytest.approx(1.25)    # 2.5 ms / 2
    assert spans.fit_ms(run) == pytest.approx(0.5)         # 1 ms / 2
    assert spans.decode_page_util(run) == pytest.approx(28.125)
    assert spans.decode_pages_read_share(run) == pytest.approx(37.5)
    assert spans.inside_share(run, spans.DECODE_MODULE,
                              "engine.decode") == pytest.approx(100.0)
    assert spans.inside_share(run, spans.FIT_MODULE,
                              "fed.fit") == pytest.approx(80.0)
    assert spans.stack_ms(run) is None


def test_readers_read_none_where_their_input_is_absent():
    empty = _Run({}, None)
    for fn in list(spans.SERVED.values()) + list(spans.SYNCED.values()):
        assert fn(empty) is None, fn.__name__
    assert spans.inside_share(empty, spans.FIT_MODULE, "fed.fit") is None
    # the host spans of a CPU trace, with no program on a device line
    cpu = _Run({"step_s": [0.5], "syncs": 3},
               {"spans": [["fed.stack", 0.0, 0.002, {}]], "module_s": {}})
    assert spans.stack_ms(cpu) == pytest.approx(2.0)
    assert spans.prefill_ms(cpu) is None and spans.fit_ms(cpu) is None
    # a program that does not name its stages: ``jit_run`` for all of them
    unnamed = _Run({"step_s": [0.5], "syncs": 3},
                   {"spans": [], "module_s": {"jit_run(7)": 0.3}})
    assert spans.prefill_ms(unnamed) is None
    assert spans.fit_ms(unnamed) is None


# ------------------------------------------------------ traced tiny cells


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return _tiny_root(tmp_path_factory.mktemp("tiny_spans"))


def _traced(tiny, workload):
    logs, keep = [], {}
    res = harness.run_cell(workload, SEED, 3.0, True, root=tiny,
                           require_chip=False, log=logs.append, keep=keep)
    run = keep["run"]
    counts = {}
    for sp in run.trace["spans"]:
        counts[sp[0]] = counts.get(sp[0], 0) + 1
    return res, run, keep["e2e"], counts, logs


def test_traced_chat_counts_one_span_per_step_and_submit(tiny):
    res, run, e2e, counts, logs = _traced(tiny, "tiny_chat")
    steps = len(run.facts["step_s"])
    assert steps > 0 and counts["engine.step"] == steps
    assert counts["gateway.route"] == len(run.facts["submit_s"]) > 0
    assert counts["engine.decode"] >= steps
    assert "programs traced after warm-up: 0 []" in logs
    assert set(e2e) == {"ttft_p95_ms", "tpot_p95_ms"}
    for name in ("route_ms", "step_host_ms", "decode_page_util",
                 "decode_pages_read_share"):
        v = spans.SERVED[name](run)
        assert v is not None and v > 0, name
    assert 0 < spans.decode_page_util(run) <= 100
    assert spans.prefill_ms(run) is None       # no device line off the chip
    assert set(res["metrics"]) >= {"step_ms.chat", "submit_ms.chat",
                                   "route_ms.chat", "decode_page_util.chat",
                                   "decode_pages_read_share.chat"}
    assert "prefill_ms.chat" not in res["metrics"]


def test_traced_saturate_carries_every_counter_delta(tiny):
    """Every counter of ``ServeEngine.counters()`` reaches the readers as
    its window delta, so that a counter the program adds needs no edit."""
    from repro.serve.engine import EngineConfig, ServeEngine
    res, run, _, counts, _ = _traced(tiny, "tiny_saturate")
    c = run.facts["counters"]
    names = ServeEngine([], EngineConfig()).counters()
    assert set(c) == set(names)
    assert 0 < c["decode_pages_valid"] <= c["decode_pages_read"] \
        <= c["decode_pages_mapped"]
    assert c["sheds"] == c["expiries"] == 0
    assert counts["engine.step"] == len(run.facts["step_s"])
    assert set(res["metrics"]) >= {"step_host_ms.saturate",
                                   "decode_page_util.saturate",
                                   "decode_pages_read_share.saturate",
                                   "occupancy.saturate", "step_ms.saturate"}


def test_traced_sync_counts_one_of_each_span_per_sync(tiny):
    res, run, e2e, counts, _ = _traced(tiny, "tiny_fedsync")
    syncs = run.facts["syncs"]
    assert syncs > 0
    assert counts == {"fed.sync": syncs, "fed.stack": syncs,
                      "fed.fit": syncs}
    assert spans.stack_ms(run) > 0
    assert spans.fit_ms(run) is None
    assert set(e2e) == {"sync_ms"}
    assert "stack_ms.fedsync" in res["metrics"]
    assert "fit_ms.fedsync" not in res["metrics"]
