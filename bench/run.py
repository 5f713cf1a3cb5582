"""Run one benchmark cell once on the chip this process is started on.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.  See ``benchkit/cli.py``.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

from benchkit.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
