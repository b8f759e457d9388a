"""Run one benchmark cell on the accelerators of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the result as one JSON line, last on standard output, and the
numbers its check compared, each beside its limit, last on standard
error.  Exits non-zero, with no result line, where JAX finds no TPU,
fewer chips than the cell asks for, or a device kind without published
peaks.  Run it from the root of a checkout: it imports the system under
test from ``src/``.
"""
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime logs to a fixed directory under /tmp unless told
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
