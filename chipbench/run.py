"""Benchmark entry point.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the accelerator of this machine and
prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared beside its limit.
Without a TPU, or on a device kind that ``peaks.json`` lacks, it exits
non-zero and prints no result.  See ``bench.py`` for what a run does.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

# libtpu otherwise writes its logs to a fixed /tmp/tpu_logs, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")
# the program under test
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import bench  # noqa: E402

if __name__ == "__main__":
    sys.exit(bench.main(t_start=T_START))
