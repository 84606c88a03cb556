"""The benchmark's own tests: ``python -m pytest bench/tests``.  They run
on the CPU at toy sizes; nothing here measures a time."""

import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
