"""Run one cell of the benchmark once; the last line of stdout is its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. See bench/harness.py.
"""
import time

T_START = time.time()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    rc = harness.main(sys.argv[1:], t_start=T_START)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
