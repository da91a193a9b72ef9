"""The benchmark's own tests: ``python3 -m pytest benchmark/tests -q``
(CPU, seconds; the rehearsals of the three cells take about a minute).
They are not part of the repo's tier-1 run, which collects ``tests/``."""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)
