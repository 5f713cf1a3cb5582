"""Puts ``bench/`` on the import path for the benchmark's own tests."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[2] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
