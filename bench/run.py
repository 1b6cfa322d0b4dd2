#!/usr/bin/env python3
"""Run one cell of the benchmark named in BENCHMARK.json.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``:
every number compared beside its limit. The same numbers are the last
lines of standard error. Without a TPU, or with fewer chips than the
cell asks for, it exits with code 3 and prints no result.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
# the TPU runtime's own logs go inside the checkout, not to /tmp/tpu_logs
os.environ.setdefault("TPU_LOG_DIR", str(HERE / ".tpu_logs"))
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

from benchlib.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
