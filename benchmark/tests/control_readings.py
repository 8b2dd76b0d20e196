"""The control's readings at a cell's own size, through the harness.

Each seed is one run of the cell (``harness.main.run_cell``) with the
control driver (``drivers/control.py``) in the system's place and a
window of one pass over the pool: the same pool, the same comparison
with the reference, and the same ``wrong_verdicts`` as a run of the
system. The control has to come out not correct.

    python3 benchmark/tests/control_readings.py --cell <cell> --seeds 1 2 3 [--platform tpu]

The control runs on the host, so the platform only has to be present;
``test_control.py`` runs it at tiny sizes.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from harness import main  # noqa: E402


def readings(cell: str, seed: int, overrides=None, platform="cpu") -> dict:
    t0 = time.perf_counter()
    out = main.run_cell(["--workload", cell, "--seed", str(seed),
                         "--seconds", "0", "--trace", "0"], t0,
                        platform=platform, overrides=overrides,
                        cache_dir=None, driver="control")
    return {"cell": cell, "seed": seed, "correct": out["correct"],
            "attempted": out["attempted"],
            "control_wrong_verdicts": out["compared"]["wrong_verdicts"][
                "value"],
            "seconds": round(time.perf_counter() - t0, 3)}


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--platform", default="cpu")
    args = ap.parse_args()
    for s in args.seeds:
        print(json.dumps(readings(args.cell, s, platform=args.platform)),
              flush=True)
