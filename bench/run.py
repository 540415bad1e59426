"""Run one benchmark cell once and print its result line:

  python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

See ``bench/harness.py``. Exits non-zero, with no result line, where JAX
finds no TPU or fewer chips than the cell asks for, or where the program
is not in the checkout.
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
