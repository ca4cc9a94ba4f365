"""Run one benchmark cell; see `bench/harness.py`.

    python bench/run.py --workload engine.skew --seed 7 --seconds 45 --trace 0
"""
import time

T_IMPORT = time.perf_counter()

import sys  # noqa: E402

from harness import main, process_start  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=min(process_start(), T_IMPORT)))
