"""Arithmetic shared by the per-layer readers under ``bench/metrics/``.
Each reader takes a ``harness.Run`` and returns a number, or None where
the run holds nothing to read."""
from __future__ import annotations

from bench import trace as tr

#: the paged decode attention kernel, as its events are named in a trace
PAGED_ATTN = "paged_decode_attention"


def idle_share(run):
    """Per cent of the traced window in which no operation ran."""
    t = run.trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def mean_ms(values):
    """Mean of host timings in ms: their sum over their count, so that the
    clock's error is taken once over the whole sum."""
    return 1e3 * sum(values) / len(values) if values else None


def step_ms(run):
    return mean_ms(run.facts.get("step_s", []))


def submit_ms(run):
    return mean_ms(run.facts.get("submit_s", []))


def occupancy(run):
    occ = run.facts.get("occupancy", [])
    return 100.0 * sum(occ) / len(occ) if occ else None


def mfu(run):
    """Model FLOPs of the window's work (prefill of admitted prompts and
    decode of every emitted token) over the window's seconds at the bf16
    peak of every chip the cell runs on, per cent."""
    f, s = run.facts.get("model_flops", 0.0), run.facts.get("window_span_s")
    if not f or not s or run.peaks is None:
        return None
    return 100.0 * f / (s * run.chips * run.peaks["flops_bf16"])


def roofline(run, name):
    """The least time kernel ``name``'s useful work needs (its bytes at
    the HBM bandwidth or its FLOPs at the peak, whichever is longer, as
    its model family counts them in ``facts["kernel_cost"]``) over its
    summed device time, per cent."""
    t = run.trace
    if not t or run.peaks is None:
        return None
    k = tr.kernel_seconds(t, name)
    flops, b = run.facts.get("kernel_cost", {}).get(name, (0.0, 0.0))
    if k <= 0 or not b:
        return None
    least = max(b / run.peaks["hbm_bytes_per_s"],
                flops / run.peaks["flops_bf16"])
    return 100.0 * least / k


def paged_attn_roofline(run):
    """The paged decode attention kernel's roofline share: K/V of the
    valid positions, queries and outputs."""
    return roofline(run, PAGED_ATTN)
