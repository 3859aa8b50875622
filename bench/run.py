"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero without a result line when JAX finds no TPU (or fewer
chips than the cell asks for), when Pallas would run in interpret mode,
or when a kernel fell back to XLA in a compiled step.  The last line of
standard output is the result (see ``harness/driver.py``).
"""
import sys
import time

T_PROCESS = time.time()

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

if __name__ == "__main__":
    from harness import driver
    sys.exit(driver.run(sys.argv[1:], T_PROCESS))
