"""Reduction of a profiler trace to the numbers the readers need.

The window is the host span ``bench.window`` that the harness opens around
it. Within it, on each TPU device plane, the "XLA Ops" line holds one event
per operation that ran on the device (operations inside a loop nest inside
their loop's event), and the "XLA Modules" line one per program run, named
by the function handed to ``jax.jit`` (``jit_<name>``). Busy time is the
union of the operations' intervals; the idle share is one minus busy over
the window. A kernel's time is the sum of the durations of its own events.

Host spans (``jax.profiler.TraceAnnotation``) are kept where their name is
lower-case and dotted (``SPAN_NAME``): the harness's ``bench.*`` and every
span of the program (``engine.step``, ``gateway.route``, ``fed.fit``...),
with their args; JAX's own host events do not match. Each gap between busy
intervals is named by the innermost kept span that covers its middle: what
the host was doing while the device waited.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the host spans kept: the harness's and the program's, not JAX's own
SPAN_NAME = re.compile(r"^[a-z_]+\.[a-z_.]+$")


def short_name(text: str) -> str:
    """An operation's name without its HLO text: ``%while.37 = (...)``
    becomes ``while.37``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _named(ops, modules):
    """(module/op, start, dur) for each op: the name of the program
    (module) whose event encloses the op, then the op's short name, so
    that ops of two programs that share an instruction name stay apart."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for name, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        mod = (short_name(mods[i][0]) if i >= 0 and s < mods[i][1]
               + mods[i][2] else "?")
        out.append((f"{mod}/{short_name(name)}", s, d))
    return out


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


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
    return e - s - sum(b - a for a, b in _union(kids))


def innermost(spans, t):
    """The name of the shortest span (start_ns, end_ns, name) that covers
    ``t``, or "host" where none does."""
    inside = [(e - s, n) for s, e, n in spans if s <= t <= e]
    return min(inside)[1] if inside else "host"


def _programs(modules, host, window, n_dev):
    """The program's spans that start in the window, as [name, start_s
    (from the window's start), dur_s, args]; the device seconds of each
    program (``module_s``), and the part of them inside each kind of
    program span (``module_in_span_s``)."""
    w0, w1 = window
    prog = [h for h in host if not h[0].startswith("bench.")]
    spans = [[n, (s - w0) * 1e-9, d * 1e-9, a] for n, s, d, a in prog
             if w0 <= s < w1]
    by_name = collections.defaultdict(list)
    for n, s, d, _ in prog:
        by_name[n].append((s, s + d))
    covered = {n: _union(iv) for n, iv in by_name.items()}
    starts = {n: [a for a, _ in m] for n, m in covered.items()}
    module_s = collections.Counter()
    inside = collections.defaultdict(collections.Counter)
    for events in modules.values():
        for name, s, d in events:
            s, e = max(s, w0), min(s + d, w1)
            if e <= s:
                continue
            mod = short_name(name)
            module_s[mod] += (e - s) * 1e-9 / n_dev
            for span, merged in covered.items():
                inside[mod][span] += (_overlap(starts[span], merged, s, e)
                                      * 1e-9 / n_dev)
    return {"spans": spans, "module_s": dict(module_s),
            "module_in_span_s": {m: dict(c) for m, c in inside.items()}}


def reduce_events(device_ops, host_spans, window, modules=None):
    """device_ops: {device: [(name, start_ns, dur_ns)]}; host_spans:
    [(name, start_ns, dur_ns, args)]; window: (start_ns, end_ns); modules:
    {device: [(name, start_ns, dur_ns)]} of the "XLA Modules" lines.

    Returns busy_s and window_s (busy averaged over the devices), each
    device's own busy seconds (``busy_by_device``, so that a chip that
    idles while the others work shows), the ten operations with the most
    device time, the ten longest idle gaps named
    by the innermost host span that covers them, every operation's summed
    time (``op_s``), and the program's spans and programs (see
    ``_programs``)."""
    w0, w1 = window
    busy, gaps = {}, []
    op_s = collections.Counter()
    for dev, ops in sorted(device_ops.items()):
        ivs = []
        for name, s, d in ops:
            e = s + d
            if e <= w0 or s >= w1:
                continue
            s, e = max(s, w0), min(e, w1)
            ivs.append((s, e))
            op_s[name] += (e - s) * 1e-9
        merged = _union(ivs)
        busy[dev] = sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    named = [(s, s + d, n) for n, s, d, _ in host_spans if n != WINDOW_SPAN]
    gaps.sort(key=lambda g: g[0] - g[1])
    n_dev = max(len(device_ops), 1)
    red = {
        "busy_s": sum(busy.values()) / n_dev,
        "busy_by_device": busy,
        "window_s": (w1 - w0) * 1e-9,
        "device_ops": [[n, s / n_dev] for n, s in op_s.most_common(10)],
        "idle_gaps": [[innermost(named, (a + b) / 2), (b - a) * 1e-9]
                      for a, b in gaps[:10]],
        "op_s": {n: s / n_dev for n, s in op_s.items()},
    }
    red.update(_programs(modules or {}, host_spans, window, n_dev))
    return red


def kernel_seconds(red: dict, name: str) -> float:
    """Summed device seconds of every operation whose name contains
    ``name`` (averaged over the devices)."""
    return sum(s for n, s in red["op_s"].items() if name in n)


def reduce_dir(trace_dir: str) -> dict:
    """Read the one ``.xplane.pb`` the profiler wrote under ``trace_dir``
    and reduce it (see ``reduce_events``)."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found "
                           f"{paths}")
    pd = ProfileData.from_file(paths[0])
    device_ops, modules, host_spans, planes, lines = {}, {}, [], [], set()
    for plane in pd.planes:
        planes.append(plane.name)
        if plane.name.startswith("/device:TPU:"):
            by_line = {}
            for line in plane.lines:
                lines.add(line.name)
                if line.name in (OPS_LINE, MODULES_LINE):
                    by_line[line.name] = [(e.name, e.start_ns,
                                           e.duration_ns)
                                          for e in line.events]
            if OPS_LINE in by_line:
                device_ops[plane.name] = _named(
                    by_line[OPS_LINE], by_line.get(MODULES_LINE, []))
            if MODULES_LINE in by_line:
                modules[plane.name] = by_line[MODULES_LINE]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if SPAN_NAME.match(e.name):
                        host_spans.append((e.name, e.start_ns,
                                           e.duration_ns, dict(e.stats)))
    wins = [(s, s + d) for n, s, d, _ in host_spans if n == WINDOW_SPAN]
    if not wins:
        raise RuntimeError(f"no {WINDOW_SPAN} span in the trace")
    red = reduce_events(device_ops, host_spans, wins[0], modules)
    red["planes"] = sorted(set(planes))
    red["lines"] = sorted(lines)
    starts = [s for ops in device_ops.values() for _, s, _ in ops]
    red["clock"] = {"window_ns": list(wins[0]),
                    "device_ns": [min(starts), max(starts)] if starts
                    else None}
    return red
