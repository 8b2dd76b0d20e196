"""Run one benchmark cell once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The last line of standard output is the
run's result as one JSON object; see ``benchmark/harness/main.py``.
"""

import time

PROCESS_T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))

if __name__ == "__main__":
    sys.path.insert(0, HERE)
    from harness.main import main
    sys.exit(main(sys.argv[1:], t0=PROCESS_T0))
