"""CPU tests of the chip benchmark's own code: the harness is imported from
``chipbench/`` and the program from ``src/``."""

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))
