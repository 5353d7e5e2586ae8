"""Entry point: run one benchmark cell once (see ``bench/harness.py``).

    python3 bench/run.py --workload braille_q.sessions --seed 7 --seconds 10 --trace 0
"""

import sys
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench.harness import entry

    entry(T_START)
