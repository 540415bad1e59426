"""The knee of an open-loop cell, found once by a sweep: one process, one
set-up, one window per offered rate.

  python3 -m bench.sweep --workload <name> --rates 4,6,8 --seconds 30

For each rate it prints the requests due and finished by the window's
close, the queue left at the close, the tails of time to first token and
time per output token (all due requests waited for), and the steps after
which a lane kept a request queued though it had a free row: admission
waiting for pages. The knee is the highest rate whose requests are still
finished at the rate they are due, with no backlog growing through the
window.
"""
import argparse
import json
import pathlib
import sys
import time
from collections import Counter

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import harness, traffic  # noqa: E402
from bench.drivers import serve  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=4242)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    r = harness.resolve(harness.load_spec(), args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    ctx = harness.Context(config=r["config"], traffic=dict(r["traffic"]),
                          seed=args.seed, seconds=args.seconds, trace=False,
                          control=False, t_start=t_start,
                          chips=r["cell"]["chips"])
    ctx.count_compiles()
    dev = harness.device_info(r["cell"]["chips"])
    harness.enable_cache()
    s = serve.Serving(ctx)
    s.build()
    s.warm()
    ctx.log(f"device {dev}; set-up {time.perf_counter() - t_start:.1f} s")
    page_waits = Counter()
    step = s.step

    def counted(in_window):
        done = step(in_window)
        page_waits["steps"] += any(
            q and len(a) < s.ecfg.slots
            for q, a in zip(s.lanes.queue, s.lanes.active))
        return done

    s.step = counted
    for rate in (float(x) for x in args.rates.split(",")):
        s.mix = dict(r["traffic"], rate_per_s=rate)
        s.gen = traffic.stream(s.mix, args.seed, vocab=s.vocab,
                               d_emb=s.rcfg.d_emb)
        s.records, s.steps, s.submit_s = {}, [], []
        s.run_open(args.seconds)
        recs = [s.records[i] for i in s.window_rids]
        t_close = s.t_open + args.seconds
        by_close = sum(1 for x in recs
                       if x["t_done"] is not None and x["t_done"] <= t_close)
        ttft = np.array([(x["t_first"] - x["due"]) * 1e3 for x in recs
                         if x["t_first"] is not None])
        tpot = np.array([(x["t_done"] - x["t_first"]) * 1e3
                         / max(x["req"].max_new - 1, 1) for x in recs
                         if x["t_done"] is not None])
        steps = [st["t1"] - st["t0"] for st in s.steps]
        print(json.dumps({
            "rate_per_s": rate, "due": len(recs),
            "finished_by_close": by_close,
            "unfinished_at_close": len(recs) - by_close,
            "finished_after_drain": int(sum(x["t_done"] is not None
                                            for x in recs)),
            "ttft_p50_ms": float(np.percentile(ttft, 50)),
            "ttft_p95_ms": float(np.percentile(ttft, 95)),
            "tpot_p50_ms": float(np.percentile(tpot, 50)),
            "tpot_p95_ms": float(np.percentile(tpot, 95)),
            "step_ms_mean": 1e3 * float(np.mean(steps)) if steps else None,
            "max_admit_batch": max((Counter(
                (lane, serve._next_pow2(n)) for lane, n in st["admitted"]
            ).most_common(1)[0][1] for st in s.steps if st["admitted"]),
                default=0),
            "page_waits": page_waits.pop("steps", 0),
            "window_compiles": ctx.compiles["window"]}), flush=True)
        ctx.compiles["window"] = 0
        while s.srv.engine.busy:        # the next rate starts from idle
            s.step(False)
    ctx.read_memory()
    return 0


if __name__ == "__main__":
    sys.exit(main())
