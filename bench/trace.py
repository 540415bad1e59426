"""Reduction of a profiler trace to the numbers the readers need.

The window is the host span ``bench.window`` that the harness opens around
it. Within it, on each TPU device plane, the "XLA Ops" line holds one event
per operation that ran on the device (operations inside a loop nest inside
their loop's event). Busy time is the union of those events' intervals;
the idle share is one minus busy over the window. A kernel's time is the
sum of the durations of its own events. Each gap between busy intervals is
named by the innermost ``bench.*`` host span that covers its middle: what
the host was doing while the device waited.
"""
from __future__ import annotations

import collections
import glob
import os

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def short_name(text: str) -> str:
    """An operation's name without its HLO text: ``%while.37 = (...)``
    becomes ``while.37``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _named(ops, modules):
    """(module/op, start, dur) for each op: the name of the program
    (module) whose event encloses the op, then the op's short name, so
    that ops of two programs that share an instruction name stay apart."""
    import bisect
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


def reduce_events(device_ops, host_spans, window):
    """device_ops: {device: [(name, start_ns, dur_ns)]}; host_spans:
    [(name, start_ns, dur_ns)]; window: (start_ns, end_ns).

    Returns busy_s and window_s (busy averaged over the devices), the ten
    operations with the most device time, the ten longest idle gaps named
    by the host span that covers them, and every operation's summed time
    (``op_s``)."""
    w0, w1 = window
    busy, gaps = [], []
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
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    spans = [(s, s + d, name) for name, s, d in host_spans
             if name.startswith("bench.") and name != WINDOW_SPAN]

    def what(a, b):
        mid = (a + b) / 2
        inside = [(e - s, n) for s, e, n in spans if s <= mid <= e]
        return min(inside)[1] if inside else "host"

    gaps.sort(key=lambda g: g[0] - g[1])
    n_dev = max(len(device_ops), 1)
    return {
        "busy_s": sum(busy) / n_dev,
        "window_s": (w1 - w0) * 1e-9,
        "device_ops": [[n, s / n_dev] for n, s in op_s.most_common(10)],
        "idle_gaps": [[what(a, b), (b - a) * 1e-9] for a, b in gaps[:10]],
        "op_s": {n: s / n_dev for n, s in op_s.items()},
    }


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
    device_ops, host_spans, planes, lines = {}, [], [], set()
    for plane in pd.planes:
        planes.append(plane.name)
        if plane.name.startswith("/device:TPU:"):
            by_line = {}
            for line in plane.lines:
                lines.add(line.name)
                by_line[line.name] = [(e.name, e.start_ns, e.duration_ns)
                                      for e in line.events]
            if OPS_LINE in by_line:
                device_ops[plane.name] = _named(
                    by_line[OPS_LINE], by_line.get(MODULES_LINE, []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host_spans.append((e.name, e.start_ns,
                                           e.duration_ns))
    wins = [(s, s + d) for n, s, d in host_spans if n == WINDOW_SPAN]
    if not wins:
        raise RuntimeError(f"no {WINDOW_SPAN} span in the trace")
    red = reduce_events(device_ops, host_spans, wins[0])
    red["planes"] = sorted(set(planes))
    red["lines"] = sorted(lines)
    starts = [s for ops in device_ops.values() for _, s, _ in ops]
    red["clock"] = {"window_ns": list(wins[0]),
                    "device_ns": [min(starts), max(starts)] if starts
                    else None}
    return red
