"""CPU tests of the benchmark: tiny widths, the program's Pallas kernels in
interpret mode.  Run with ``JAX_PLATFORMS=cpu python -m pytest bench/tests``
from the repository root."""
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
