"""The harness: finds a cell's configuration, traffic mix, driver and
per-layer readers by the names in ``BENCHMARK.json``, runs one window, and
prints one result line.

Everything that belongs to one configuration, mix or metric sits in a file
of its own, found by name:

- ``bench/configs/<config>.json``  the configuration as it is run;
- ``bench/traffic/<traffic>.json``  the mix's parameters; its ``driver``
  key names the driver (``bench/drivers/``) that runs it;
- ``bench/metrics/<metric>.py``  a reader with ``read(run) -> float|None``.

A cell, a configuration or a metric is added by adding such files and
entries in ``BENCHMARK.json``; nothing here names one.
"""
from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parents[1]

#: traffic ``driver`` -> module under bench/drivers with ``run(ctx)``
DRIVER_MODULES = {"closed": "serve", "open": "serve", "sync": "fedsync"}


class SpecError(Exception):
    """The benchmark's files do not resolve."""


class NoChip(Exception):
    """No accelerator, or fewer chips than the cell asks for."""


# --------------------------------------------------------------- lookups


def load_spec(root=ROOT) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def _json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SpecError(f"{path} is missing")
    return json.loads(path.read_text())


def resolve(spec: dict, workload: str, root=ROOT) -> dict:
    """The cell named ``workload`` with its configuration, mix, driver and
    the end-to-end and per-layer metrics it reports."""
    root = pathlib.Path(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(known: {sorted(cells)})")
    cell = cells[workload]
    confs = {c["name"]: c for c in spec["configs"]}
    if cell["config"] not in confs:
        raise SpecError(f"workload {workload}: no config {cell['config']!r}")
    config = _json(root / confs[cell["config"]]["file"])
    mix = _json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    if mix.get("driver") not in DRIVER_MODULES:
        raise SpecError(f"traffic {cell['traffic']}: unknown driver "
                        f"{mix.get('driver')!r}")
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    readers = {m["name"]: root / "bench" / "metrics" / f"{m['name']}.py"
               for m in layer}
    for name, path in readers.items():
        if not path.is_file():
            raise SpecError(f"metric {name}: reader {path} is missing")
    return {"cell": cell, "config": config, "traffic": mix, "e2e": e2e,
            "per_layer": layer, "readers": readers,
            "driver": DRIVER_MODULES[mix["driver"]]}


def load_reader(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------- context


class Window:
    def __init__(self):
        self.t0 = self.t1 = None

    def end(self):
        self.t1 = time.perf_counter()

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Context:
    """What a driver sees of the harness."""

    def __init__(self, *, config, traffic, seed, seconds, trace, control,
                 t_start, log=None):
        self.config, self.traffic = config, traffic
        self.seed, self.seconds = seed, float(seconds)
        self.trace, self.control = bool(trace), bool(control)
        self.t_start = t_start
        self.phases = []               # (name, seconds)
        self.setup_s = None
        self.memory_peak = None
        self.trace_dir = None
        self.compiles = {"setup": 0, "window": 0, "after": 0}
        self._where = "setup"
        self._log = log or (lambda s: print(s, file=sys.stderr, flush=True))

    def count_compiles(self):
        """Count, from JAX's own monitoring events, the programs compiled
        or loaded from the cache in set-up and inside the window."""
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles[self._where] += 1

    def log(self, msg: str):
        self._log(msg)

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        c0 = self.compiles["setup"]
        yield
        dt = time.perf_counter() - t0
        self.phases.append((name, dt))
        self.log(f"set-up phase {name}: {dt:.3f} s, "
                 f"{self.compiles['setup'] - c0} programs compiled or "
                 "loaded from the cache")

    def span(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        import jax
        w = Window()
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.trace_dir)
        self._where = "window"
        w.t0 = time.perf_counter()
        self.setup_s = w.t0 - self.t_start
        try:
            with self.span("bench.window"):
                yield w
        finally:
            if w.t1 is None:
                w.end()
            self._where = "after"
            if self.trace:
                jax.profiler.stop_trace()
            self.log(f"window: {w.seconds:.4f} s; programs compiled or "
                     f"loaded inside it: {self.compiles['window']}")

    def read_memory(self):
        import jax
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        self.memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
        self.log(f"device memory: peak {self.memory_peak} bytes of "
                 f"{max(s.get('bytes_limit', 0) for s in stats)}")


# ------------------------------------------------------------------- run


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (first device: {devs[0].platform} "
                     f"{devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_cache():
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else a fixed directory inside the checkout."""
    import os
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root=ROOT, t_start=None, require_chip=True, control=False,
             log=None) -> dict:
    """One run of one cell. Returns the result object (without printing)
    and the compared numbers under ``checks``."""
    t_start = time.perf_counter() if t_start is None else t_start
    spec = load_spec(root)
    r = resolve(spec, workload, root)
    src = pathlib.Path(root) / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    ctx = Context(config=r["config"], traffic=r["traffic"], seed=seed,
                  seconds=seconds, trace=trace, control=control,
                  t_start=t_start, log=log)
    with ctx.phase("import and jax start"):
        try:
            importlib.import_module("repro.serve.gateway")
        except ImportError as e:
            raise SpecError(f"the program (src/repro) is not in the "
                            f"checkout: {e}") from None
        ctx.count_compiles()
        if require_chip:
            dev = device_info(r["cell"]["chips"])
            cache = enable_cache()
        else:
            import jax
            d0 = jax.devices()[0]
            dev = {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(jax.devices())}
            cache = None
    ctx.log(f"device {dev}; compile cache {cache}")
    driver = importlib.import_module(f"bench.drivers.{r['driver']}")
    out = driver.run(ctx)
    ctx.log("set-up phases: " + ", ".join(f"{n} {s:.3f} s"
                                          for n, s in ctx.phases)
            + f"; setup_s {ctx.setup_s:.3f}; programs compiled or loaded "
            f"in set-up {ctx.compiles['setup']}, in the window "
            f"{ctx.compiles['window']}")
    dev["memory_peak_bytes"] = int(ctx.memory_peak or 0)
    checks = {k: {"value": v, "limit": lim}
              for k, (v, lim) in out["checks"].items()}
    correct = (out["attempted"] > 0 and out["failed"] == 0 and bool(checks)
               and all(c["limit"] is not None and c["value"] <= c["limit"]
                       for c in checks.values()))
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "device": dev}
    if trace:
        from bench import trace as tr
        red = tr.reduce_dir(ctx.trace_dir)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        from bench import yardstick
        run = Run(facts=out["facts"], trace=red,
                  peaks=yardstick.peaks(dev["kind"]) if require_chip
                  else None)
        for m in r["per_layer"]:
            v = load_reader(r["readers"][m["name"]])(run)
            if v is not None:
                result["metrics"][m["name"]] = {"value": float(v),
                                                "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        ctx.log(f"trace: busy {red['busy_s']:.4f} s of {red['window_s']:.4f}"
                f" s; planes {red['planes']}; device lines {red['lines']}; "
                f"clocks {red['clock']}")
    else:
        for m in r["e2e"]:
            v = ctx.setup_s if m["name"] == "setup_s" else out["e2e"].get(
                m["name"])
            if v is not None:
                result["metrics"][m["name"]] = {"value": float(v),
                                                "unit": m["unit"]}
    if control:
        result["control"] = out.get("control", {})
    result["checks"] = checks
    return result


class Run:
    """What a per-layer reader gets: the driver's counts and host timings
    (``facts``), the reduced trace, and the chip's peaks (None off the
    chip)."""

    def __init__(self, *, facts, trace, peaks):
        self.facts, self.trace, self.peaks = facts, trace, peaks


def main(argv=None) -> int:
    import argparse
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start)
    except (SpecError, NoChip) as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    except Exception:       # noqa: BLE001 — no result line on any failure
        traceback.print_exc()
        return 1
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return 0
