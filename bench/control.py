"""Readings that set the limits of ``correct``, several seeds in one
process: for each seed, one run of the cell with its comparison, plus the
control (the reference in float8 e4m3, the step below bfloat16, put in the
program's place) read at the same prompts and served tokens.

  python3 -m bench.control --workload <name> --seeds 1,2,3 --seconds 10

Prints one JSON line per seed: the compared numbers as the program reads
them, and as the control reads them. The benchmark's own runs never run
the control.
"""
import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(args.workload, seed, args.seconds, False,
                               t_start=time.perf_counter(), control=True)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "program": {k: c["value"] for k, c in
                                      res["checks"].items()},
                          "control": res["control"],
                          "metrics": res["metrics"],
                          "memory_peak_bytes":
                              res["device"]["memory_peak_bytes"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
