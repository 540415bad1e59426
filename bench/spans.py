"""The program's own spans, programs and counters in a traced run, and the
per-layer readings they give.

The program names its work on the device trace's clock:

- host spans (``jax.profiler.TraceAnnotation``): ``engine.step`` (the whole
  ``ServeEngine.step``), ``engine.prefill`` (one coalesced bucket group,
  dispatch through the fetch of its first tokens; args ``lane``, ``rows``,
  ``bucket``), ``engine.decode`` (one lane's decode chunk, dispatch through
  the fetch of its tokens; args ``lane``, ``rows``), ``gateway.route``
  (the route program, dispatch through fetch), ``fed.sync`` (the whole
  ``FedLoop.sync``), ``fed.stack`` (the harvest stacked into the client
  slab) and ``fed.fit`` (the fit through the fetch of its losses);
- programs, each named by the function handed to ``jax.jit``, so that the
  device's "XLA Modules" line shows ``jit_engine_prefill``,
  ``jit_engine_write_pages``, ``jit_engine_decode_paged``,
  ``jit_gateway_route``, ``jit_fedavg_fit`` and so on;
- two exact counters of ``ServeEngine.counters()``: ``decode_pages_valid``
  and ``decode_pages_mapped``, summed over paged decode chunks.

``extra`` reduces a trace to what these need, added beside
``bench.trace.reduce_dir``'s reduction and leaving every number of it as
it was: ``spans`` (program spans that start in the window), ``module_s``
(device seconds of each program), ``module_in_span_s`` (the part of them
inside each kind of program span) and ``named_gaps`` (the longest idle
gaps, each named by the innermost span of either kind). The readers below
take a ``harness.Run`` so extended, and the window's counter deltas and
step count in its facts, and return None where the run holds nothing to
read.

The harness's run of a cell does not read any of this yet. ``run_cell``
makes one traced run of a cell through ``bench.harness.run_cell`` and
keeps, besides, what these readers need:

  python3 -m bench.spans --workload <name> --seed <n> --seconds <s>
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import glob
import os

from bench import trace as tr

#: prefixes of the program's host spans
PROGRAM = ("engine.", "gateway.", "fed.")
#: the host spans that name an idle gap: the harness's and the program's
NAMING = ("bench.",) + PROGRAM
#: the programs a prefill is made of
PREFILL_MODULES = ("jit_engine_prefill", "jit_engine_write_pages")
FIT_MODULE = "jit_fedavg_fit"
DECODE_MODULE = "jit_engine_decode_paged"
#: the counters a decode chunk adds to
PAGE_COUNTERS = ("decode_pages_valid", "decode_pages_mapped")


# -------------------------------------------------------------- reduction


def read_dir(trace_dir: str):
    """The events of the one trace under ``trace_dir``: (device ops,
    device modules, host spans, window). Ops and modules are
    ``{plane: [(name, start_ns, dur_ns)]}`` of each TPU plane; host spans
    ``[(name, start_ns, dur_ns, args)]`` of the spans that ``NAMING``
    lists; the window is the ``bench.window`` span's (start_ns, end_ns)."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found "
                           f"{paths}")
    pd = ProfileData.from_file(paths[0])
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name in (tr.OPS_LINE, tr.MODULES_LINE):
                    out = ops if line.name == tr.OPS_LINE else modules
                    out[plane.name] = [(e.name, e.start_ns, e.duration_ns)
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(NAMING):
                        host.append((e.name, e.start_ns, e.duration_ns,
                                     dict(e.stats)))
    wins = [(s, s + d) for n, s, d, _ in host if n == tr.WINDOW_SPAN]
    if not wins:
        raise RuntimeError(f"no {tr.WINDOW_SPAN} span in the trace")
    return ops, modules, host, wins[0]


def _overlap(starts, merged, s, e):
    """Length of [s, e) covered by the merged, sorted intervals, whose
    starts are ``starts``."""
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    out = 0
    for a, b in merged[i:]:
        if a >= e:
            break
        out += max(0, min(b, e) - max(a, s))
    return out


def self_time(span, children):
    """A span's duration less the part its children cover: ``span`` is
    (start, end), ``children`` (start, end) pairs in the same unit, clipped
    to the span, overlaps counted once."""
    s, e = span
    kids = [(max(a, s), min(b, e)) for a, b in children if b > s and a < e]
    return e - s - sum(b - a for a, b in tr._union(kids))


def innermost(spans, t):
    """The name of the shortest span (start_ns, end_ns, name) that covers
    ``t``, or "host" where none does."""
    inside = [(e - s, n) for s, e, n in spans if s <= t <= e]
    return min(inside)[1] if inside else "host"


def extra(ops, modules, host, window, top=10) -> dict:
    """What the program's names add to a trace's reduction (see the module
    docstring). Device seconds are averaged over the devices, like
    ``bench.trace``'s."""
    w0, w1 = window
    n_dev = max(len(modules), 1)
    prog = [(n, s, d, a) for n, s, d, a in host if n.startswith(PROGRAM)]
    spans = [[n, (s - w0) * 1e-9, d * 1e-9, a] for n, s, d, a in prog
             if w0 <= s < w1]
    by_name = collections.defaultdict(list)
    for n, s, d, _ in prog:
        by_name[n].append((s, s + d))
    covered = {n: tr._union(iv) for n, iv in by_name.items()}
    starts = {n: [a for a, _ in m] for n, m in covered.items()}
    module_s = collections.Counter()
    inside = collections.defaultdict(collections.Counter)
    for events in modules.values():
        for name, s, d in events:
            s, e = max(s, w0), min(s + d, w1)
            if e <= s:
                continue
            mod = tr.short_name(name)
            module_s[mod] += (e - s) * 1e-9 / n_dev
            for span, merged in covered.items():
                inside[mod][span] += (_overlap(starts[span], merged, s, e)
                                      * 1e-9 / n_dev)
    named = [(s, s + d, n) for n, s, d, _ in host if n != tr.WINDOW_SPAN]
    gaps = []
    for dev_ops in ops.values():
        merged = tr._union([(max(s, w0), min(s + d, w1))
                            for _, s, d in dev_ops if s + d > w0 and s < w1])
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    gaps.sort(key=lambda g: g[0] - g[1])
    return {"spans": spans, "module_s": dict(module_s),
            "module_in_span_s": {m: dict(c) for m, c in inside.items()},
            "named_gaps": [[innermost(named, (a + b) / 2), (b - a) * 1e-9]
                           for a, b in gaps[:top]]}


# ---------------------------------------------------------------- readers


def _spans(run, name):
    t = run.trace or {}
    return [sp for sp in t.get("spans", []) if sp[0] == name]


def mean_span_ms(run, name):
    """Mean duration of the window's ``name`` spans, ms."""
    d = [sp[2] for sp in _spans(run, name)]
    return 1e3 * sum(d) / len(d) if d else None


def route_ms(run):
    return mean_span_ms(run, "gateway.route")


def stack_ms(run):
    return mean_span_ms(run, "fed.stack")


def step_host_ms(run):
    """Mean self time of ``engine.step``: the step less its
    ``engine.prefill`` and ``engine.decode`` children, ms."""
    steps = _spans(run, "engine.step")
    if not steps:
        return None
    kids = [(sp[1], sp[1] + sp[2]) for sp in run.trace["spans"]
            if sp[0] in ("engine.prefill", "engine.decode")]
    return 1e3 * sum(self_time((sp[1], sp[1] + sp[2]), kids)
                     for sp in steps) / len(steps)


def module_ms(run, prefixes, count):
    """Device seconds of the programs whose names start with ``prefixes``
    over ``count`` (steps or syncs of the window), ms. None where no such
    program ran: off the chip, or in a program that does not name them."""
    mods = [s for m, s in (run.trace or {}).get("module_s", {}).items()
            if m.startswith(prefixes)]
    return 1e3 * sum(mods) / count if mods and count else None


def prefill_ms(run):
    return module_ms(run, PREFILL_MODULES, len(run.facts.get("step_s", [])))


def fit_ms(run):
    return module_ms(run, (FIT_MODULE,), run.facts.get("syncs"))


def decode_page_util(run):
    """Per cent of the mapped page table's pages that held a valid
    position of an active row, over the window's decode chunks."""
    valid, mapped = (run.facts.get(k) for k in PAGE_COUNTERS)
    return 100.0 * valid / mapped if mapped else None


def inside_share(run, prefix, span):
    """Per cent of the device time of the programs named ``prefix...``
    that lies inside ``span`` spans: that the spans cover what they
    dispatched, on the device's clock."""
    t = run.trace or {}
    mods = [m for m in t.get("module_s", {}) if m.startswith(prefix)]
    total = sum(t["module_s"][m] for m in mods)
    if not total:
        return None
    return 100.0 * sum(t["module_in_span_s"][m].get(span, 0.0)
                       for m in mods) / total


#: the readings of a served cell and of a sync cell, by name
SERVED = {"route_ms": route_ms, "prefill_ms": prefill_ms,
          "step_host_ms": step_host_ms, "decode_page_util": decode_page_util}
SYNCED = {"stack_ms": stack_ms, "fit_ms": fit_ms}


# ------------------------------------------------------------ traced run


def run_cell(workload, seed, seconds, **kw):
    """One traced run of a cell through ``harness.run_cell`` that also keeps
    the trace's ``extra`` and, for a served cell, the window's deltas of
    ``PAGE_COUNTERS``. Returns (the harness's result, a ``harness.Run``
    with both added, the run's end-to-end numbers)."""
    from unittest import mock

    from bench import harness
    from bench.drivers import fedsync, serve
    kept = {}
    real_reduce, real_step = tr.reduce_dir, serve.Serving.step
    pages = dict.fromkeys(PAGE_COUNTERS, 0)

    def reduce_dir(trace_dir):
        red = real_reduce(trace_dir)
        more = extra(*read_dir(trace_dir))
        red.update({k: v for k, v in more.items() if k not in red})
        kept["trace"] = red
        return red

    def step(self, in_window):
        c0 = self.srv.engine.counters()
        done = real_step(self, in_window)
        if in_window:
            c1 = self.srv.engine.counters()
            for k in PAGE_COUNTERS:       # absent before the counters
                pages[k] += c1.get(k, 0) - c0.get(k, 0)
        return done

    def keep(real):
        def run(ctx):
            kept["out"] = out = real(ctx)
            return out
        return run

    with contextlib.ExitStack() as stack:
        stack.enter_context(mock.patch.object(tr, "reduce_dir", reduce_dir))
        stack.enter_context(mock.patch.object(serve.Serving, "step", step))
        for mod in (serve, fedsync):
            stack.enter_context(mock.patch.object(mod, "run",
                                                  keep(mod.run)))
        res = harness.run_cell(workload, seed, seconds, True, **kw)
    facts = dict(kept["out"]["facts"])
    if "step_s" in facts:
        facts.update(pages)
    run = harness.Run(facts=facts, trace=kept["trace"], peaks=None)
    return res, run, kept["out"]["e2e"]


def main(argv=None) -> int:
    import argparse
    import json
    import time
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description="One traced run of a cell, "
                                 "read through the program's own spans.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    res, run, e2e = run_cell(args.workload, args.seed, args.seconds,
                             t_start=t_start)
    line = {"workload": args.workload, "seed": args.seed,
            "correct": res["correct"], "failed": res["failed"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "traced_e2e": e2e,
            "readings": {k: fn(run) for k, fn in (
                SYNCED if "syncs" in run.facts else SERVED).items()},
            "decode_in_span": inside_share(run, DECODE_MODULE,
                                           "engine.decode"),
            "fit_in_span": inside_share(run, FIT_MODULE, "fed.fit"),
            "spans": dict(collections.Counter(
                sp[0] for sp in run.trace["spans"])),
            "span_ms": {n: mean_span_ms(run, n) for n in sorted(
                {sp[0] for sp in run.trace["spans"]})},
            "module_s": run.trace["module_s"],
            "named_gaps": run.trace["named_gaps"],
            "idle_gaps": run.trace["idle_gaps"],
            "busy_s": run.trace["busy_s"], "window_s": run.trace["window_s"],
            "facts": {k: run.facts[k] for k in ("syncs",) + PAGE_COUNTERS
                      if k in run.facts}}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
