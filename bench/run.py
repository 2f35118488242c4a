"""Run one benchmark cell once: ``python3 bench/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>`` from the checkout root (also
``python3 -m bench.run``).  See ``bench/harness.py``."""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from bench.harness import main
    sys.exit(main(t_start=T_START))
