"""CPU tests of the benchmark: lookups by name (a model family among
them), the traffic generator, the trace reduction, the Qwen family's
numbers against the parent's, the refusal to run without a chip, and the
comparison that decides ``correct`` (with its control and a planted fault)
on a tiny copy of the mixed-pool cell."""
from __future__ import annotations

import collections
import copy
import itertools
import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import harness, readers, trace, traffic

ROOT = harness.ROOT
SPEC = harness.load_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
MIXES = {p.stem: json.loads(p.read_text())
         for p in (ROOT / "bench" / "traffic").glob("*.json")}
SERVED = sorted(k for k, m in MIXES.items() if m["driver"] != "sync")
SYNCED = sorted(k for k, m in MIXES.items() if m["driver"] == "sync")
SEED = 2**33 + 17          # larger than 32 signed bits, as the driver's are


# ---------------------------------------------------------------- lookups


@pytest.mark.parametrize("workload", CELLS)
def test_every_cell_resolves_by_name(workload):
    r = harness.resolve(SPEC, workload)
    names = {m["name"] for m in r["e2e"]}
    assert "setup_s" in names and len(names) >= 2
    assert r["per_layer"], "every cell reports a per-layer metric"
    for m in r["per_layer"]:
        assert m["moves"] in names, (m["name"], names)
        assert callable(harness.load_reader(r["readers"][m["name"]]))
    conf = next(c for c in SPEC["configs"] if c["name"] == r["cell"]["config"])
    assert (ROOT / conf["file"]).is_file()
    for key in conf["reduced"]:
        assert key in r["config"] and key in r["config"].get("published",
                                                              {})


def _copy_tree(dst: pathlib.Path):
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    for sub in ("configs", "traffic", "metrics", "models"):
        shutil.copytree(ROOT / "bench" / sub, dst / "bench" / sub)


def test_new_cell_is_found_from_new_files_alone(tmp_path):
    """A configuration, a mix, a metric and a cell added as files and
    BENCHMARK.json entries resolve with no other file edited."""
    _copy_tree(tmp_path)
    b = tmp_path / "bench"
    conf = json.loads((b / "configs" / "qwen2-1.5b-x2.json").read_text())
    conf["name"] = "new-config"
    (b / "configs" / "new-config.json").write_text(json.dumps(conf))
    mix = json.loads((b / "traffic" / "chat.json").read_text())
    mix["rate_per_s"] = 3.0
    (b / "traffic" / "new_mix.json").write_text(json.dumps(mix))
    (b / "metrics" / "new_metric.new_cell.py").write_text(
        "def read(run):\n    return 42.0\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "new-config", "source": "x",
                            "file": "bench/configs/new-config.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "new_cell", "config": "new-config",
                              "traffic": "new_mix", "chips": 1, "why": "x"})
    spec["end_to_end"].append({"name": "new_e2e", "unit": "ms",
                               "better": "lower", "bound": 0.1,
                               "source": "host_clock",
                               "workloads": ["new_cell"]})
    spec["per_layer"].append({"name": "new_metric.new_cell", "unit": "ms",
                              "better": "lower", "source": "host_clock",
                              "layer": "gateway", "moves": "new_e2e",
                              "workloads": ["new_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    r = harness.resolve(harness.load_spec(tmp_path), "new_cell", tmp_path)
    assert r["config"]["name"] == "new-config"
    assert r["traffic"]["rate_per_s"] == 3.0
    assert {m["name"] for m in r["e2e"]} == {"new_e2e", "setup_s"}
    assert [m["name"] for m in r["per_layer"]] == ["new_metric.new_cell"]
    assert harness.load_reader(r["readers"]["new_metric.new_cell"])(
        None) == 42.0


def test_a_missing_family_is_a_spec_error(tmp_path):
    """A served model's family is the file its architecture names; where
    that file is missing, the cell does not resolve, and the error names
    the path."""
    _copy_tree(tmp_path)
    conf = tmp_path / "bench" / "configs" / "qwen2-1.5b-x2.json"
    c = json.loads(conf.read_text())
    c["architectures"] = ["NoSuchForCausalLM"]
    conf.write_text(json.dumps(c))
    with pytest.raises(harness.SpecError, match="NoSuchForCausalLM.py"):
        harness.resolve(harness.load_spec(tmp_path), "chat_qwen2x2",
                        tmp_path)


# ---------------------------------------------------------------- traffic


def _take(mix, seed, n):
    return list(itertools.islice(traffic.stream(mix, seed, vocab=151936,
                                                d_emb=768), n))


@pytest.mark.parametrize("name", SERVED)
def test_traffic_is_seeded_and_in_range(name):
    mix = MIXES[name]
    n = 2 * mix["block"]
    a, b, c = _take(mix, SEED, n), _take(mix, SEED, n), _take(mix, 5, n)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
        assert x.lam == y.lam and x.due == y.due and np.array_equal(x.x, y.x)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    # another seed: the same sizes in each block, in another order
    for blk in range(2):
        sl = slice(blk * mix["block"], (blk + 1) * mix["block"])
        for key in ("max_new", "lam"):
            assert sorted(getattr(r, key) for r in a[sl]) == sorted(
                getattr(r, key) for r in c[sl])
        assert sorted(len(r.prompt) for r in a[sl]) == sorted(
            len(r.prompt) for r in c[sl])
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    for r in a:
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert o["min"] <= r.max_new <= o["max"]
        assert r.lam in mix["lambdas"] and 0 <= r.client < mix["clients"]
        assert abs(np.linalg.norm(r.x) - 1) < 1e-5
        assert r.prompt.min() >= 1
    lens = [len(r.prompt) for r in a[:mix["block"]]]
    assert abs(np.median(lens) / p["median"] - 1) < 0.1
    if mix["driver"] == "open":
        due = np.array([r.due for r in a])
        assert np.all(np.diff(due) > 0)
        rate = mix["block"] / due[mix["block"] - 1]
        assert abs(rate / mix["rate_per_s"] - 1) < 0.1


@pytest.mark.parametrize("name", SYNCED)
def test_evaluations_are_seeded_and_in_range(name):
    mix, costs = MIXES[name], [1.0, 0.5]
    a, b, c = (traffic.Evaluations(mix, s, costs, 768) for s in
               (SEED, SEED, 5))
    ra, rb, rc = a.rows(3, 64), b.rows(3, 64), c.rows(3, 64)
    for k in ra:
        assert np.array_equal(ra[k], rb[k])
    assert not np.array_equal(ra["x"], rc["x"])
    assert set(np.unique(ra["m"])) <= {0, 1}
    assert set(np.unique(ra["acc"])) <= {0.0, 1.0}
    assert np.all((ra["cost"] >= 0.25) & (ra["cost"] < 1.5))
    assert np.allclose(np.linalg.norm(ra["x"], axis=1), 1.0, atol=1e-5)


# ------------------------------------------------------------------ trace


class _Run:
    def __init__(self, facts, tr, peaks, chips=1):
        self.facts, self.trace, self.peaks = facts, tr, peaks
        self.chips = chips


def test_trace_reduction_arithmetic():
    ms = 1_000_000
    ops = {"/device:TPU:0": [
        ("fusion.1", 0, 2 * ms),
        ("_while.3", 3 * ms, 4 * ms),                # 3..7 ms
        ("paged_decode_attention_pallas.1", 4 * ms, 1 * ms),   # nested
        ("paged_decode_attention_pallas.1", 5 * ms, 1 * ms),
        ("fusion.2", 9 * ms, 2 * ms),                # clipped at 10 ms
    ]}
    spans = [("bench.window", 0, 10 * ms, {}), ("bench.step", 0, 8 * ms, {}),
             ("bench.refill", 8 * ms, 2 * ms, {})]
    red = trace.reduce_events(ops, spans, (0, 10 * ms))
    assert red["window_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx(0.007)        # 0-2, 3-7, 9-10
    assert readers.idle_share(_Run({}, red, None)) == pytest.approx(30.0)
    assert trace.kernel_seconds(red, "paged_decode_attention") == \
        pytest.approx(0.002)
    assert red["idle_gaps"] == [["bench.refill", pytest.approx(0.002)],
                                ["bench.step", pytest.approx(0.001)]]
    assert red["device_ops"][0] == ["_while.3", pytest.approx(0.004)]
    named = trace._named([("%while.3 = (s32[]) while(...)", 5, 2),
                          ("%fusion.1 = f32[2] fusion(...)", 20, 1)],
                         [("jit_run(7)", 4, 10), ("jit_prefill", 19, 5)])
    assert [n for n, _, _ in named] == ["jit_run(7)/while.3",
                                        "jit_prefill/fusion.1"]
    # roofline: 1.638 MB in 2 ms at 819 GB/s is 1% of the kernel's time
    peaks = {"hbm_bytes_per_s": 819e9, "flops_bf16": 197e12}
    run = _Run({"kernel_cost": {"paged_decode_attention": (1.0,
                                                           819e9 * 2e-5)}},
               red, peaks)
    assert readers.paged_attn_roofline(run) == pytest.approx(1.0)
    # a compute-bound call: the FLOPs set the least time
    run.facts = {"kernel_cost": {"paged_decode_attention": (197e12 * 1e-3,
                                                           1.0)}}
    assert readers.paged_attn_roofline(run) == pytest.approx(50.0)
    # any kernel a family costs, by the same arithmetic
    run.facts = {"kernel_cost": {"fusion.2": (1.0, 819e9 * 1e-5)}}
    assert readers.roofline(run, "fusion.2") == pytest.approx(1.0)
    assert readers.roofline(run, "paged_decode_attention") is None
    run = _Run({"model_flops": 197e12 * 0.5, "window_span_s": 2.0}, red,
               peaks)
    assert readers.mfu(run) == pytest.approx(25.0)
    assert readers.paged_attn_roofline(_Run({}, None, peaks)) is None


def test_recorded_trace_finds_its_window(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    red = trace.reduce_dir(str(tmp_path))
    assert red["window_s"] > 0
    assert red["busy_s"] == 0.0          # the CPU has no TPU plane
    assert readers.idle_share(_Run({}, red, None)) == pytest.approx(100.0)


# ------------------------------------------- the Qwen family, as it was

#: the parent's numbers (its ``bench.yardstick``): token_flops(m, 777),
#: prefill_flops(m, 333), and the paged attention's FLOPs and bytes of one
#: decode step at contexts (17, 300, 1024) times num_hidden_layers
YARDSTICK_PINS = {
    "qwen3-8b_qwen2-1.5b": (8419573760.0, 2330585563136.0, 395476992.0,
                            99753984),
    "qwen3-8b_qwen2-1.5b/second_lane": (3220807680.0, 882623963136.0,
                                        230694912.0, 38965248),
    "qwen2-1.5b-x2": (3220807680.0, 882623963136.0, 230694912.0, 38965248),
}


def _published(name):
    conf, _, sub = name.partition("/")
    m = json.loads((ROOT / "bench" / "configs" / f"{conf}.json").read_text())
    return m[sub] if sub else m


@pytest.mark.parametrize("name", sorted(YARDSTICK_PINS))
def test_qwen_family_costs_match_the_parents_yardstick(name):
    m = _published(name)
    fam = harness.load_family(m)
    tok, pre, f, b = YARDSTICK_PINS[name]
    assert fam.token_flops(m, 777) == tok
    assert fam.prefill_flops(m, 333) == pre
    assert fam.decode_attn_cost(m, [17, 300, 1024]) == (f, b)
    assert not hasattr(fam, "kernel_costs")


#: the parent's readings on the tiny mixed-pool models from SEED: the
#: float64 sum and absolute sum of every weight, and the reference's and
#: the control's gaps of fixed served tokens after a fixed prompt
TINY_PINS = {
    "Qwen3ForCausalLM": (
        1513.8900968381204, 65183.79068684345,
        [4.732832908630371, 2.4549460411071777, 3.300588369369507,
         3.4944705963134766, 2.7612075805664062, 3.0194129943847656,
         3.0277621746063232, 3.450672149658203, 1.950348138809204,
         4.583173751831055, 2.4446139335632324, 3.171985626220703],
        [0.0, 0.0, 0.13392138481140137, 0.0, 0.0, 0.15558218955993652,
         0.1273331642150879, 0.0, 0.0, 0.0, 0.0, 0.4947376251220703]),
    "Qwen2ForCausalLM": (
        1313.4532025186345, 60740.35983352084,
        [1.3161770105361938, 1.1600381135940552, 1.2983677387237549,
         0.32275688648223877, 0.6960111856460571, 0.16045546531677246,
         1.2482489347457886, 0.9573291540145874, 0.5515042543411255,
         1.049033522605896, 1.1925886869430542, 0.9186522960662842],
        [0.0, 0.0, 0.0, 0.0, 0.0, 0.0035493969917297363, 0.0, 0.0, 0.0,
         0.0, 0.0, 0.03616952896118164]),
}


@pytest.mark.parametrize("arch", sorted(TINY_PINS))
def test_qwen_family_weights_and_gaps_match_the_parent(arch, tmp_path):
    import jax
    from bench import weights
    conf = json.loads((_tiny_root(tmp_path) / "bench" / "configs"
                       / "tiny.json").read_text())
    m = next(x for x in (conf, conf["second_lane"])
             if x["architectures"][0] == arch)
    fam = harness.load_family(m)
    cfg, params = weights.model_params(fam, m, SEED)
    assert cfg.name == f"{arch}-2L-256"
    leaves = [np.asarray(a, np.float64) for a in jax.tree.leaves(params)]
    total, absolute, gaps, control = TINY_PINS[arch]
    assert sum(float(a.sum()) for a in leaves) == pytest.approx(total,
                                                                rel=1e-9)
    assert sum(float(np.abs(a).sum()) for a in leaves) == pytest.approx(
        absolute, rel=1e-9)
    prompt = (np.arange(24) * 37 + 5) % 511 + 1
    served = (np.arange(12) * 91 + 3) % 512
    g = fam.token_gaps(params, m, prompt, served, length=128)
    c = fam.token_gaps(params, m, prompt, served, length=128, control=True)
    assert g == pytest.approx(gaps, abs=1e-5)
    assert c == pytest.approx(control, abs=1e-5)


# ------------------------------------------------------ no chip, no result


def _bench_run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", CELLS[0],
         "--seed", str(SEED), "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_exits_nonzero_with_no_result():
    p = _bench_run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench_run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# ---------------------------------------------- tiny cell: correct, failed


def _tiny_root(root: pathlib.Path) -> pathlib.Path:
    """A tree with the mixed-pool configuration cut to a tiny size (every
    key but the widths and depth as the chip runs it) under both mixes."""
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    for sub in ("metrics", "models"):
        shutil.copytree(ROOT / "bench" / sub, root / "bench" / sub)
    (root / "src").symlink_to(ROOT / "src")
    conf = json.loads((ROOT / "bench" / "configs"
                       / "qwen3-8b_qwen2-1.5b.json").read_text())
    for m in (conf, conf["second_lane"]):
        m.update(num_hidden_layers=2, hidden_size=256, intermediate_size=512,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=64,
                 vocab_size=512)
    conf["engine"] = {"slots": 4, "max_seq": 128, "chunk": 8,
                      "page_size": 16, "pages": 24}
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(conf))
    spec = copy.deepcopy(SPEC)
    spec["configs"] = [{"name": "tiny", "source": "x",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "x"}]
    spec["workloads"] = []
    for cell in SPEC["workloads"]:
        mix = json.loads((ROOT / "bench" / "traffic"
                          / f"{cell['traffic']}.json").read_text())
        mix.update(prompt_tokens={"median": 24, "sigma": 0.7, "min": 8,
                                  "max": 64},
                   output_tokens={"median": 16, "sigma": 0.5, "min": 8,
                                  "max": 40},
                   block=12, warm_batches=[1, 2], check={"per_lane": 3})
        if mix["driver"] == "closed":
            mix["ramp_per_step"] = 2
        elif mix["driver"] == "open":
            mix.update(rate_per_s=6.0, drain_s=30)
        else:
            mix.update(capacity=256, fresh_per_client=16)
        name = f"tiny_{cell['traffic']}"
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
        spec["workloads"].append(dict(cell, name=name, config="tiny",
                                      traffic=name))
        for m in spec["end_to_end"] + spec["per_layer"]:
            if cell["name"] in m.get("workloads", []):
                m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def _limits():
    """The limits the chip readings set, of both configurations."""
    out = {}
    for f in ("qwen2-1.5b-x2.json", "qwen3-8b_qwen2-1.5b.json"):
        out.update(json.loads((ROOT / "bench" / "configs" / f).read_text())
                   ["limits"])
    return out


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = _tiny_root(tmp_path_factory.mktemp("tiny"))
    conf = root / "bench" / "configs" / "tiny.json"
    c = json.loads(conf.read_text())
    c["limits"] = _limits()
    conf.write_text(json.dumps(c))
    return root


def _run(tiny, workload, **kw):
    return harness.run_cell(workload, SEED, 4.0, False, root=tiny,
                            require_chip=False, log=lambda s: None, **kw)


#: a model family that no file of the benchmark names: the Qwen decoder
#: under a marked ModelConfig name, counting the calls the harness makes
MARKED_FAMILY = """
import dataclasses

from bench.models import _qwen

CALLS = {}


def _count(name):
    CALLS[name] = CALLS.get(name, 0) + 1


def model_config(m):
    _count("model_config")
    return dataclasses.replace(_qwen.model_config(m), name="marked-family")


def leaf(key, names, shape, dtype, m):
    _count("leaf")
    return None


def token_gaps(*a, **kw):
    _count("token_gaps")
    return _qwen.token_gaps(*a, **kw)


def token_flops(m, context):
    _count("token_flops")
    return _qwen.token_flops(m, context)


def prefill_flops(m, n):
    _count("prefill_flops")
    return _qwen.prefill_flops(m, n)


def decode_attn_cost(m, contexts):
    _count("decode_attn_cost")
    return _qwen.decode_attn_cost(m, contexts)


def kernel_costs(m, contexts):
    return {"marked_kernel": (1.0, float(len(contexts)))}
"""


def test_new_family_is_served_checked_and_costed_through_its_file(tmp_path):
    """A configuration whose architecture names a family file that only a
    new file holds is built, served, compared with its reference and
    costed through that file, with no other file of the benchmark
    edited."""
    root = _tiny_root(tmp_path)
    (root / "bench" / "models" / "MarkedForCausalLM.py").write_text(
        MARKED_FAMILY)
    conf = root / "bench" / "configs" / "tiny.json"
    c = json.loads(conf.read_text())
    c["architectures"] = ["MarkedForCausalLM"]
    c["limits"] = _limits()
    conf.write_text(json.dumps(c))
    keep = {}
    res = harness.run_cell("tiny_saturate", SEED, 3.0, True, root=root,
                           require_chip=False, log=lambda s: None,
                           keep=keep)
    assert res["correct"], res["checks"]
    fam = harness.load_family(c, root)
    assert fam.__file__ == str(root / "bench" / "models"
                               / "MarkedForCausalLM.py")
    for name in ("model_config", "leaf", "token_gaps", "token_flops",
                 "prefill_flops", "decode_attn_cost"):
        assert fam.CALLS.get(name, 0) > 0, name
    from bench import weights
    assert weights.model_params(fam, c, SEED)[0].name == "marked-family"
    cost = keep["run"].facts["kernel_cost"]
    assert set(cost) == {"paged_decode_attention", "marked_kernel"}
    assert cost["marked_kernel"][1] > 0


def test_closed_loop_counts_only_errors_and_rejections(tiny):
    """In the closed loop nothing is shed or expired, and a request still in
    flight when the window ends is neither attempted nor failed."""
    from bench.drivers import serve
    r = harness.resolve(harness.load_spec(tiny), "tiny_saturate", tiny)
    ctx = harness.Context(config=r["config"], traffic=r["traffic"],
                          seed=SEED, seconds=3.0, trace=False, control=False,
                          t_start=0.0, log=lambda s: None)
    s = serve.Serving(ctx)
    s.build()
    s.warm()
    s.run_closed(3.0)
    counters = s.srv.engine.counters()
    assert counters["sheds"] == counters["expiries"] == 0
    assert counters["preemptions"] == counters["cancels"] == 0
    res = s.results(closed=True)
    in_flight = [rid for rid, rec in s.records.items()
                 if rec["t_done"] is None]
    assert in_flight, "the window should end with requests in flight"
    assert not set(in_flight) & s.window_rids
    assert res["attempted"] == len(s.window_rids) > 0
    assert all(s.records[r]["tokens"] is not None for r in s.window_rids)
    assert all(len(s.records[r]["tokens"]) == s.records[r]["req"].max_new
               for r in s.window_rids)


def test_tiny_cells_are_correct_and_the_control_fails(tiny):
    """Every mix passes the comparison at the limits the chip readings set;
    the control (fp8 models and router, or the bfloat16 refit), read on
    the same inputs, fails at least one of the numbers."""
    for cell in ("tiny_saturate", "tiny_chat", "tiny_fedsync"):
        res = _run(tiny, cell, control=True)
        assert res["correct"], res["checks"]
        assert res["failed"] == 0 and res["attempted"] > 0
        assert set(res["metrics"]) >= {"setup_s"}
        over = [k for k, v in res["control"].items()
                if k in res["checks"] and v > res["checks"][k]["limit"]]
        assert over, (res["control"], res["checks"])


def test_an_altered_token_makes_the_run_incorrect(tiny, monkeypatch):
    """The fault of a served cell: a token altered where the decode chunk
    produces it."""
    from repro.serve import engine
    real = engine.ServeEngine._decode_chunk
    hits = collections.Counter()

    def broken(self, lane):
        real(self, lane)
        for st in lane.active.values():
            st.chunks[-1] = st.chunks[-1].copy()
            st.chunks[-1][-1] = (st.chunks[-1][-1] + 1) % lane.pm.cfg.vocab
            hits["altered"] += 1

    monkeypatch.setattr(engine.ServeEngine, "_decode_chunk", broken)
    res = _run(tiny, "tiny_saturate")
    assert hits["altered"] > 0
    assert not res["correct"] and res["failed"] > 0


def test_a_sync_that_keeps_its_state_is_incorrect(tiny, monkeypatch):
    """The fault of a training step: the refit returns the router it was
    given."""
    from repro import routers

    def unchanged(router, data, fcfg, *, key, rounds=None, **kw):
        return router, {"loss": [0.0] * (rounds or 1), "eval": []}

    monkeypatch.setattr(routers, "fit_federated", unchanged)
    res = _run(tiny, "tiny_fedsync")
    assert not res["correct"] and res["failed"] > 0
    assert res["checks"]["param_change_gap"]["value"] == pytest.approx(1.0)


def test_a_sync_on_half_the_rows_is_incorrect(tiny, monkeypatch):
    """The other fault of a training step: half of every client's rows
    left out of the fit, the mean taken over the rest."""
    from repro.fed.harvest import HarvestStore
    real = HarvestStore.as_federated_data

    def half(self, *a, **kw):
        d = real(self, *a, **kw)
        w = np.asarray(d["w"]).copy()
        w[:, w.shape[1] // 2:] = 0.0
        return dict(d, w=w)

    monkeypatch.setattr(HarvestStore, "as_federated_data", half)
    res = _run(tiny, "tiny_fedsync")
    assert not res["correct"] and res["failed"] > 0
