"""Readers of the program's own spans, programs and counters in a traced
run, for the per-layer metrics under ``bench/metrics/``.

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
- the counters of ``ServeEngine.counters()``, among them
  ``decode_pages_valid``, ``decode_pages_mapped`` and
  ``decode_pages_read``, summed over paged decode chunks.

``bench.trace.reduce_dir`` keeps the spans (``spans``), each program's
device seconds (``module_s``) and the part of them inside each kind of
span (``module_in_span_s``); the serving driver puts the window's delta of
every counter in ``facts["counters"]``. Each reader takes a
``harness.Run`` and returns None where the run holds nothing to read.
"""
from __future__ import annotations

from bench import trace as tr

#: the programs a prefill is made of
PREFILL_MODULES = ("jit_engine_prefill", "jit_engine_write_pages")
FIT_MODULE = "jit_fedavg_fit"
DECODE_MODULE = "jit_engine_decode_paged"


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
    return 1e3 * sum(tr.self_time((sp[1], sp[1] + sp[2]), kids)
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


def _page_share(run, counter):
    """Per cent of the mapped page table's pages that ``counter`` counted,
    over the window's decode chunks."""
    c = run.facts.get("counters", {})
    mapped = c.get("decode_pages_mapped")
    return 100.0 * c[counter] / mapped if mapped and counter in c else None


def decode_page_util(run):
    """Per cent of the mapped pages that held a valid position of an
    active row."""
    return _page_share(run, "decode_pages_valid")


def decode_pages_read_share(run):
    """Per cent of the mapped pages that the decode kernel fetched."""
    return _page_share(run, "decode_pages_read")


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
          "step_host_ms": step_host_ms, "decode_page_util": decode_page_util,
          "decode_pages_read_share": decode_pages_read_share}
SYNCED = {"stack_ms": stack_ms, "fit_ms": fit_ms}
