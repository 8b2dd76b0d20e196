"""CPU rehearsals of the benchmark: JAX on the CPU, the compile cache off, the benchmark and the system on the
path. Run with ``python3 -m pytest benchmark/tests``."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

#: Tiny mixes per cell: the same generator and harness at a size the
#: CPU runs in seconds.
TINY = {
    "etcd-10k.staggered": {"n_ops": 300, "pool": 4},
    "etcd-independent.34x300": {"n_ops": 60, "keys": 6, "pool": 4},
}
