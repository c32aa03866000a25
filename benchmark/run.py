"""The benchmark's one command: one process, one cell, one run.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints progress lines, then as its last stdout line one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` with ``--trace 1``). Exits non-zero and prints no result when
JAX finds no TPU, or not as many chips as the cell asks for, or when the
program it measures is not in the checkout. See ``harness.py``.
"""

import time

T_PROCESS_START = time.perf_counter()   # before every other import

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    try:
        from benchmark import harness
    except ImportError as e:
        print(f"benchmark FAILED: cannot import the harness: {e}",
              file=sys.stderr)
        return 1
    try:
        return harness.main(sys.argv[1:], T_PROCESS_START)
    except ImportError as e:
        # benchmark/ alone, without the program it measures.
        print(f"benchmark FAILED: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
